"""The traffic generators repeat exactly from the seed."""
import numpy as np
import pytest

from chipbench import harness


def _mod(name):
    return harness.load_module(harness.HERE / "traffic" / f"{name}.py")


WL = {"ops": {"fft": 1, "polymul-real": 3}, "rate_per_s": 500}


def test_poisson_schedule_repeats_from_the_seed():
    poisson = _mod("poisson")
    g1, o1 = poisson.schedule(WL, np.random.default_rng(2**31 + 5), 400)
    g2, o2 = poisson.schedule(WL, np.random.default_rng(2**31 + 5), 400)
    assert np.array_equal(g1, g2) and o1 == o2


def test_poisson_seeds_reorder_the_same_work():
    poisson = _mod("poisson")
    g1, o1 = poisson.schedule(WL, np.random.default_rng(1), 400)
    g2, o2 = poisson.schedule(WL, np.random.default_rng(2), 400)
    assert not np.array_equal(g1, g2) and o1 != o2
    assert np.array_equal(np.sort(g1), np.sort(g2))
    assert sorted(o1) == sorted(o2)
    assert o1.count("fft") == 100
    assert np.mean(g1) == pytest.approx(1 / 500, rel=0.02)


class _Client:
    max_batch = 4

    def __init__(self):
        self.sent = []

    def submit(self, op, t_due=None, *, block=True):
        self.sent.append(op)
        return True


def test_closed_loop_keeps_its_outstanding_split_over_ops():
    closed = _mod("closed")
    client = _Client()
    t = closed.Traffic({"ops": {"fft": 1, "polymul-real": 1},
                        "outstanding": 16}, client, None, 1.0)
    t.start()
    assert client.sent.count("fft") == 8 and len(client.sent) == 16

    class R:
        op = "fft"
    t.on_complete([R(), R()])
    assert client.sent[-2:] == ["fft", "fft"]


def test_closed_loop_refuses_a_split_that_forms_tail_batches():
    closed = _mod("closed")
    with pytest.raises(ValueError):
        closed.Traffic({"ops": {"fft": 1, "polymul-real": 1},
                        "outstanding": 12}, _Client(), None, 1.0)


def test_a_run_repeats_its_requests_from_the_seed(tiny_root):
    """Same seed: the same payloads in the same order and the same sample
    indices; another seed: other payloads."""
    cell = harness.Cell.load("paper-n16384-closed", root=tiny_root,
                             bench_file=tiny_root / "BENCHMARK.json")

    def first(seed):
        s = harness.Session(cell, seed, t_start=0.0)
        s.compiles.close()
        return ([s._next_index("fft") for _ in range(40)],
                s.pools["fft"][:2].copy())

    (i1, p1), (i2, p2), (i3, p3) = first(7), first(7), first(8)
    assert i1 == i2 and np.array_equal(p1, p2)
    assert i1 != i3 and not np.array_equal(p1, p3)
