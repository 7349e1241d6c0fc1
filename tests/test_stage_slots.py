"""Batch staging into reused host slots (``launch/ops.py`` ``_SlotRing``).

  * batches dispatched back to back without a fetch each come back with
    their own payloads' result: a slot is written again only once the
    result of the batch that last read it is ready, and a batch goes to
    the slot given out last where that result is already ready;
  * a tail batch fills a slot's leading rows and equals ``np.stack``;
  * after warm-up, no served batch counts ``stage.slot_alloc``;
  * a batch larger than the slots reallocates them once, and warming
    every size from 1 to B grows the ring by doubling;
  * the RNS route keeps fresh object arrays and counts nothing.
"""
import numpy as np
import pytest

from repro import telemetry
from repro.launch import ops as op_registry
from repro.launch.engine import ServeEngine

N = 64
NUMERIC_OPS = ("fft", "rfft", "polymul", "polymul-real", "polymul-mod")


def _bind(op, n=N, **ctx):
    return op_registry.get_op(op).bind(n, op_registry.OpContext(**ctx))


def _counters(rec):
    return {name: c for name, (c, _) in rec.snapshot()["counters"].items()
            if name.startswith("stage.")}


class _Pending:
    """A result whose readiness the test sets and whose wait it observes:
    ``jax.block_until_ready`` calls its ``block_until_ready``."""

    def __init__(self, ready=False):
        self.ready = ready
        self.waited = False

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        self.waited = True
        self.ready = True
        return self


@pytest.mark.parametrize("op", NUMERIC_OPS)
def test_back_to_back_batches_keep_their_own_rows(op, rng):
    bound = _bind(op)
    bound.warmup(4)
    batches = [[bound.random_payload(rng) for _ in range(size)]
               for size in (4, 4, 3)]
    outs = [bound.execute(payloads) for payloads in batches]
    for payloads, out in zip(batches, outs):
        rows = bound.to_numpy(out)
        assert rows.shape[0] == len(payloads)
        for payload, row in zip(payloads, rows):
            bound.verify(payload, row)


def test_a_slot_waits_for_the_batch_that_last_read_it(rng):
    bound = _bind("polymul-real")
    pending = [_Pending() for _ in range(4)]
    results = iter(pending)
    bound.fn = lambda *operands: next(results)
    payloads = [bound.random_payload(rng) for _ in range(2)]
    bound.execute(payloads)
    bound.execute(payloads)
    assert not any(p.waited for p in pending)
    bound.execute(payloads)            # slot 0 again: batch 0 must be done
    assert [p.waited for p in pending] == [True, False, False, False]
    bound.execute(payloads)            # slot 1 again: batch 1
    assert [p.waited for p in pending] == [True, True, False, False]


def test_a_batch_reuses_the_last_slot_once_its_reader_is_done(rng):
    bound = _bind("polymul-real")
    done = [_Pending(ready=True) for _ in range(3)]
    results = iter(done)
    bound.fn = lambda *operands: next(results)
    payloads = [bound.random_payload(rng) for _ in range(2)]
    slots = []
    for _ in done:
        bound.execute(payloads)
        slots.append(bound._slots._last)
    assert slots == [0, 0, 0]
    assert not any(p.waited for p in done)


@pytest.mark.parametrize("op", ["fft", "polymul-real"])
def test_tail_batch_fills_the_leading_rows(op, rng):
    bound = _bind(op)
    bound.warmup(4)
    payloads = [bound.random_payload(rng) for _ in range(3)]
    rec = telemetry.Recorder()
    with rec.current():
        operands = bound.stack(payloads)
    want = op_registry._stack_operands(bound, payloads)
    assert len(operands) == len(want) == bound.spec.arity
    for got, fresh in zip(operands, want):
        assert got.shape == fresh.shape == (3, N)
        assert np.array_equal(np.asarray(got), fresh)
    assert bound._slots.rows == 4
    assert _counters(rec) == {}


@pytest.mark.parametrize("batches", [1, 3])
def test_served_batches_reuse_the_warmed_slots(batches, rng):
    engine = ServeEngine(max_batch=4, max_pending=64)
    engine.register("polymul-real", N)
    warm = telemetry.Recorder()
    with warm.current():
        engine.warmup()
    assert _counters(warm) == {"stage.slot_alloc": 1}
    for rid in range(4 * batches):
        engine.submit("polymul-real", N,
                      engine.bound("polymul-real", N).random_payload(rng),
                      rid=rid)
    stats = engine.run(4 * batches)
    assert stats["spans"]["serve.dispatch"]["count"] == batches
    assert "stage.slot_alloc" not in stats["counters"]


def test_buckets_taking_turns_each_write_one_slot(rng):
    """With one batch in flight, a bucket whose batches alternate with
    another's finds its last batch fetched and writes the same slot."""
    engine = ServeEngine(max_batch=2, max_pending=64)
    ops = ("fft", "polymul-real")
    for op in ops:
        engine.register(op, N)
    engine.warmup()
    for rid in range(12):
        op = ops[rid % 2]
        engine.submit(op, N, engine.bound(op, N).random_payload(rng),
                      rid=rid)
    engine.run(12)
    for op in ops:
        slots = engine.bound(op, N)._slots
        assert slots._last == 0
        assert slots._readers[1] is None


def test_a_larger_batch_reallocates_once(rng):
    bound = _bind("polymul-real")
    bound.warmup(2)
    rec = telemetry.Recorder()
    batches = [[bound.random_payload(rng) for _ in range(4)]
               for _ in range(3)]
    with rec.current():
        outs = [bound.execute(payloads) for payloads in batches]
    assert _counters(rec) == {"stage.slot_alloc": 1}
    assert bound._slots.rows == 4
    for payloads, out in zip(batches, outs):
        for payload, row in zip(payloads, bound.to_numpy(out)):
            bound.verify(payload, row)


@pytest.mark.parametrize("top, rings, rows",
                         [(8, 4, 8), (6, 4, 8), (9, 5, 16)])
def test_warming_every_size_doubles_the_ring(top, rings, rows):
    bound = _bind("polymul-real")
    rec = telemetry.Recorder()
    with rec.current():
        for size in range(1, top + 1):
            bound.warmup(size)
    assert _counters(rec) == {"stage.slot_alloc": rings}
    assert bound._slots.rows == rows


def test_rns_route_keeps_fresh_object_arrays(rng):
    bound = _bind("polymul-mod", modulus_bits=60)
    assert bound.route == "polymul-mod-rns"
    payloads = [bound.random_payload(rng) for _ in range(2)]
    rec = telemetry.Recorder()
    with rec.current():
        operands = bound.stack(payloads)
        rows = bound.to_numpy(bound.execute(payloads))
    assert all(isinstance(c, np.ndarray) and c.dtype == object
               for c in operands)
    assert bound._slots is None
    assert _counters(rec) == {}
    for payload, row in zip(payloads, rows):
        bound.verify(payload, row)
