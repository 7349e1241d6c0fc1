"""Spans and counters (``repro.telemetry``) and the engine's use of them.

  * self time is a span's duration less its children's on the same thread;
  * a served run's counts: one ``serve.admit`` per submit, one
    ``serve.dispatch`` / ``serve.fetch`` / ``serve.deliver`` per batch, the
    RNS route's ``rns.*`` once per batch, ``serve.join`` once per batch of
    a complex-result route and never on the others;
  * the latency record starts at the offer, so a request blocked by
    ``max_pending`` waits at least its admission wait;
  * under ``jax.profiler.trace`` the spans land in the host plane, as many
    as the recorder counted;
  * two engines in one process keep their own counters.
"""
import pathlib
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro import telemetry
from repro.launch.engine import ServeEngine

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


class FakeClock:
    """``time.perf_counter`` stand-in that returns the times given."""

    def __init__(self, times):
        self.times = list(times)

    def __call__(self):
        return self.times.pop(0)


@pytest.fixture
def clock(monkeypatch):
    def install(times):
        monkeypatch.setattr(telemetry, "perf_counter", FakeClock(times))
    return install


def _cplx(rng, n):
    return (rng.standard_normal(n)
            + 1j * rng.standard_normal(n)).astype(np.complex64)


# ---------------------------------------------------------------------------
# The recorder
# ---------------------------------------------------------------------------

def test_nested_spans_self_time(clock):
    rec = telemetry.Recorder()
    # outer 0..10 holds a 2..5 and b 6..9; b holds c 7..8
    clock([0.0, 2.0, 5.0, 6.0, 7.0, 8.0, 9.0, 10.0])
    with rec.span("outer"):
        with rec.span("a"):
            pass
        with rec.span("b"):
            with rec.span("c"):
                pass
    spans = rec.snapshot()["spans"]
    assert spans["outer"] == (1, 10.0, 4.0)
    assert spans["a"] == (1, 3.0, 3.0)
    assert spans["b"] == (1, 3.0, 2.0)
    assert spans["c"] == (1, 1.0, 1.0)


def test_current_recorder_reaches_module_spans(clock):
    rec, other = telemetry.Recorder(), telemetry.Recorder()
    clock([0.0, 1.0, 1.0, 3.0, 5.0, 6.0, 7.0, 8.0])
    with telemetry.span("unowned"):         # no recorder current: dropped
        pass
    with rec.current():
        with other.span("outer"):           # owned spans nest across owners
            with telemetry.span("inner"):
                pass
    with telemetry.span("after"):
        pass
    assert rec.snapshot()["spans"] == {"inner": (1, 2.0, 2.0)}
    assert other.snapshot()["spans"] == {"outer": (1, 5.0, 3.0)}


def test_span_records_when_its_block_raises():
    rec = telemetry.Recorder()
    with pytest.raises(KeyError):
        with rec.span("boom"):
            raise KeyError("x")
    assert rec.snapshot()["spans"]["boom"][0] == 1
    with rec.span("next"):                  # the thread's stack is balanced
        pass
    count, total, own = rec.snapshot()["spans"]["next"]
    assert count == 1 and own == pytest.approx(total)


def test_counters_add_events_and_seconds():
    rec = telemetry.Recorder()
    assert rec.snapshot() == {"spans": {}, "counters": {}}
    rec.count("queue_wait", 0.3, 3)
    rec.count("queue_wait", 0.1)
    assert rec.snapshot()["counters"]["queue_wait"] == (4, pytest.approx(0.4))


def test_recorder_under_contending_threads():
    """More threads than cores, a short switch interval: no lost update,
    and a snapshot taken while they record does not fail."""
    rec = telemetry.Recorder()
    threads, per = 32, 400
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per):
                with rec.span("t"):
                    rec.count("c", 1.0)
        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        while any(t.is_alive() for t in pool):
            snap = rec.snapshot()
            assert snap["spans"].get("t", (0,))[0] <= threads * per
        for t in pool:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(old)
    snap = rec.snapshot()
    assert snap["spans"]["t"][0] == threads * per
    assert snap["counters"]["c"] == (threads * per, threads * per * 1.0)


def test_importing_telemetry_does_not_import_jax():
    code = ("import sys, repro.telemetry; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60,
                   env={"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin"})


def test_core_does_not_import_launch():
    core = SRC / "repro" / "core"
    bad = [str(p.relative_to(SRC)) for p in core.rglob("*.py")
           if "repro.launch" in p.read_text()]
    assert bad == []


# ---------------------------------------------------------------------------
# The engine's spans and counters
# ---------------------------------------------------------------------------

BATCH_SPANS = ("serve.dispatch", "serve.stack", "serve.h2d",
               "serve.launch", "serve.fetch", "serve.sync", "serve.d2h",
               "serve.join", "serve.deliver")


@pytest.mark.parametrize("requests,max_batch", [(10, 4), (8, 8), (5, 1)])
def test_served_run_counts(rng, requests, max_batch):
    engine = ServeEngine(max_batch=max_batch, max_pending=64)
    engine.register("fft", 64)
    engine.warmup()
    for rid in range(requests):
        engine.submit("fft", 64, _cplx(rng, 64), rid=rid)
    stats = engine.run(requests)
    batches = stats["batches"]
    assert batches == -(-requests // max_batch)
    spans = stats["spans"]
    assert spans["serve.admit"]["count"] == requests
    for name in BATCH_SPANS:
        assert spans[name]["count"] == batches, name
    # one pop per batch, then an empty one that waits and resolves the last
    assert spans["serve.batch"]["count"] == batches + 1
    assert spans["serve.wait"]["count"] == 1
    for name, s in spans.items():
        assert 0 <= s["self_ms"] <= s["total_ms"] + 1e-9, name
    dispatch = spans["serve.dispatch"]
    inner = sum(spans[n]["total_ms"]
                for n in ("serve.stack", "serve.h2d", "serve.launch"))
    assert dispatch["self_ms"] == pytest.approx(
        dispatch["total_ms"] - inner, abs=1e-6)
    assert "serve.backpressure" not in spans and "serve.verify" not in spans
    assert stats["queue_wait_ms"] >= 0 and stats["admission_wait_ms"] >= 0
    assert stats["compute_seconds"] == pytest.approx(
        sum(spans[n]["total_ms"] for n in
            ("serve.dispatch", "serve.fetch", "serve.deliver")) / 1e3)
    assert 0 < stats["compute_seconds"] <= stats["seconds"]


def test_rns_route_spans_once_per_batch():
    engine = ServeEngine(max_batch=2, max_pending=8, modulus_bits=60)
    bound = engine.register("polymul-mod", 64)
    assert bound.route == "polymul-mod-rns"
    rng = np.random.default_rng(0)
    for rid in range(3):
        engine.submit("polymul-mod", 64, bound.random_payload(rng), rid=rid)
    stats = engine.run(3)
    spans = stats["spans"]
    for name in ("serve.dispatch", "serve.launch", "rns.split",
                 "rns.kernel", "rns.crt", "serve.fetch", "serve.deliver"):
        assert spans[name]["count"] == 2, name
    # the RNS route stays on the host around its kernel: no transfer spans
    for name in ("serve.h2d", "serve.sync", "serve.d2h"):
        assert name not in spans
    launch = spans["serve.launch"]
    rns = sum(spans[n]["total_ms"] for n in ("rns.split", "rns.kernel",
                                             "rns.crt"))
    assert rns <= launch["total_ms"]
    assert launch["self_ms"] == pytest.approx(launch["total_ms"] - rns,
                                              abs=1e-6)


def test_join_span_only_on_complex_results(rng):
    """``serve.join`` (the host's view of float32 words as complex64) is
    recorded once per batch of a complex-result route, and not for the
    float32 (polymul-real) or object (RNS polymul-mod) routes."""
    engine = ServeEngine(max_batch=2, max_pending=8, modulus_bits=60)
    served = 0
    for op in ("fft", "polymul-real", "polymul-mod"):    # one batch each
        bound = engine.register(op, 64)
        for _ in range(2):
            engine.submit(op, 64, bound.random_payload(rng), rid=served)
            served += 1
        spans = engine.run(served)["spans"]
        assert spans["serve.join"]["count"] == 1, op
        assert spans["serve.fetch"]["count"] == served // 2, op
    assert bound.route == "polymul-mod-rns"
    assert spans["serve.d2h"]["count"] == 2     # the RNS route has none
    fetch = spans["serve.fetch"]
    inner = sum(spans[n]["total_ms"]
                for n in ("serve.sync", "serve.d2h", "serve.join"))
    assert fetch["self_ms"] == pytest.approx(fetch["total_ms"] - inner,
                                             abs=1e-6)


def test_queue_wait_counts_time_before_the_pop(rng):
    engine = ServeEngine(max_batch=4, max_pending=64)
    engine.register("fft", 64)
    engine.warmup()
    for rid in range(4):
        engine.submit("fft", 64, _cplx(rng, 64), rid=rid)
    time.sleep(0.1)
    stats = engine.run(4)
    assert stats["queue_wait_ms"] >= 100.0
    assert stats["admission_wait_ms"] < 100.0
    assert stats["latency_ms"]["p50"] >= stats["queue_wait_ms"] - 1e-6


def test_blocked_request_latency_covers_its_admission_wait(rng):
    engine = ServeEngine(max_batch=1, max_pending=1)
    engine.register("fft", 64)
    engine.warmup()
    engine.submit("fft", 64, _cplx(rng, 64), rid=0)
    blocked = threading.Thread(
        target=engine.submit, args=("fft", 64, _cplx(rng, 64)),
        kwargs={"rid": 1})
    blocked.start()
    time.sleep(0.2)                      # rid 1 waits for queue space
    stats = engine.run(2)
    blocked.join(timeout=30)
    assert not blocked.is_alive()
    spans = stats["spans"]
    assert spans["serve.backpressure"]["count"] == 1
    assert spans["serve.admit"]["count"] == 2
    wait_ms = spans["serve.backpressure"]["total_ms"]
    assert wait_ms >= 150.0
    assert stats["admission_wait_ms"] == pytest.approx(wait_ms / 2)
    # latency runs from the offer: the blocked request's includes its wait
    assert stats["latency_ms"]["max"] >= wait_ms
    # the wait is the admit span's child, not its self time
    admit = spans["serve.admit"]
    assert admit["self_ms"] == pytest.approx(admit["total_ms"] - wait_ms,
                                             abs=1e-6)


@pytest.mark.parametrize("concurrent", [False, True])
def test_two_engines_keep_their_own_counters(rng, concurrent):
    engines = [ServeEngine(max_batch=2, max_pending=16) for _ in range(2)]
    sizes = (4, 6)
    for engine, size in zip(engines, sizes):
        engine.register("fft", 32)
        engine.warmup()
        for rid in range(size):
            engine.submit("fft", 32, _cplx(rng, 32), rid=rid)
    if concurrent:
        out = [None, None]

        def serve(i):
            out[i] = engines[i].run(sizes[i])
        pool = [threading.Thread(target=serve, args=(i,)) for i in (0, 1)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in pool)
    else:
        out = [engine.run(size) for engine, size in zip(engines, sizes)]
    for stats, size in zip(out, sizes):
        assert stats["spans"]["serve.admit"]["count"] == size
        assert stats["spans"]["serve.dispatch"]["count"] == size // 2
        assert stats["spans"]["serve.fetch"]["count"] == size // 2


def test_spans_land_in_the_profiler_host_plane(tmp_path, rng):
    import jax
    from jax.profiler import ProfileData
    engine = ServeEngine(max_batch=4, max_pending=64)
    engine.register("fft", 64)
    engine.warmup()
    with jax.profiler.trace(str(tmp_path)):
        for rid in range(10):
            engine.submit("fft", 64, _cplx(rng, 64), rid=rid)
        engine.run(10)
    found = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert len(found) == 1
    seen: dict[str, int] = {}
    for plane in ProfileData.from_file(str(found[0])).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    seen[ev.name] = seen.get(ev.name, 0) + 1
    counted = {name: agg[0] for name, agg
               in engine.telemetry.snapshot()["spans"].items()}
    assert seen == counted
    assert seen["serve.admit"] == 10 and seen["serve.dispatch"] == 3
