"""The program's spans and counters as ``chipbench/spans.py`` reads them:
the window's difference of two recorder readings, the quantities read from
it, the idle attribution on synthetic and chip-recorded traces, and whole
windows on the CPU with and without an engine that records."""
import gzip
import pathlib

import pytest

from chipbench import harness, spans, trace
from chipbench.trace import CLOSE, OPEN

DATA = pathlib.Path(__file__).resolve().parent / "data"
PAPER, HE = "paper-n16384-closed", "he128-n8192-closed"
# each quantity and the cells whose route records its span or counter
CELLS = {"admit_us_per_request": (PAPER, HE), "queue_wait_ms": (PAPER, HE),
         "h2d_ms_per_batch": (PAPER,), "d2h_ms_per_batch": (PAPER,),
         "rns_split_ms_per_batch": (HE,), "crt_ms_per_batch": (HE,)}


def test_delta_keeps_what_happened_in_the_window():
    before = {"spans": {"a": (2, 1.0, 0.5), "idle": (1, 3.0, 3.0)},
              "counters": {"q": (4, 0.4)}}
    after = {"spans": {"a": (5, 2.5, 1.0), "idle": (1, 3.0, 3.0),
                       "b": (1, 0.25, 0.25)},
             "counters": {"q": (10, 1.0)}}
    d = spans.delta(before, after)
    assert d["spans"] == {"a": {"count": 3, "total_s": 1.5, "self_s": 0.5},
                          "b": {"count": 1, "total_s": 0.25,
                                "self_s": 0.25}}
    assert d["counters"] == {"q": {"count": 6,
                                   "total_s": pytest.approx(0.6)}}
    assert spans.delta(None, after) is None
    assert spans.delta(before, None) is None


RECORDED = {"spans": {
    "serve.admit": {"count": 200, "total_s": 0.01, "self_s": 0.004},
    "serve.h2d": {"count": 4, "total_s": 0.002, "self_s": 0.002},
    "serve.d2h": {"count": 5, "total_s": 0.02, "self_s": 0.02},
    "rns.split": {"count": 2, "total_s": 0.3, "self_s": 0.3},
    "rns.crt": {"count": 2, "total_s": 0.9, "self_s": 0.9}},
    "counters": {"queue_wait": {"count": 100, "total_s": 7.5}}}


@pytest.mark.parametrize("name,want", [
    ("admit_us_per_request", 20.0),     # self time, not total
    ("queue_wait_ms", 75.0),
    ("h2d_ms_per_batch", 0.5),
    ("d2h_ms_per_batch", 4.0),
    ("rns_split_ms_per_batch", 150.0),
    ("crt_ms_per_batch", 450.0)])
def test_quantity_from_a_synthetic_reading(name, want):
    assert spans.quantities(RECORDED)[name] == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_quantity_is_none_where_the_program_records_nothing(name):
    assert spans.quantities(None)[name] is None
    assert spans.quantities({"spans": {}, "counters": {}})[name] is None


def test_innermost_names_each_instant_by_its_shortest_span():
    program = [(10, 50, "serve.dispatch"), (20, 30, "serve.h2d"),
               (30, 40, "serve.launch"), (60, 70, "serve.admit"),
               (65, 90, "serve.batch"), (95, 200, "late")]
    segs = spans.innermost(program, 0, 100)
    assert segs == [(0, 10, "host:other"), (10, 20, "serve.dispatch"),
                    (20, 30, "serve.h2d"), (30, 40, "serve.launch"),
                    (40, 50, "serve.dispatch"), (50, 60, "host:other"),
                    (60, 70, "serve.admit"), (70, 90, "serve.batch"),
                    (90, 95, "host:other"), (95, 100, "late")]
    assert spans.innermost([], 0, 5) == [(0, 5, "host:other")]


def test_idle_by_span_on_a_synthetic_trace():
    devices = {0: [(10, 20, "fusion"), (50, 60, "kernel")],
               1: [(0, 100, "other chip")]}
    host = [(8, 8, OPEN), (100, 100, CLOSE), (30, 50, "chipbench.stage")]
    program = [(0, 40, "serve.fetch"), (30, 35, "serve.d2h"),
               (55, 95, "serve.admit"), (70, 80, "serve.backpressure")]
    idle = spans.idle_by_span(devices, host, program)
    # idle gaps of device 0: 8..10, 20..50, 60..100
    assert idle == {"serve.admit": pytest.approx(25e-9),
                    "serve.fetch": pytest.approx(17e-9),
                    "host:other": pytest.approx(15e-9),
                    "serve.backpressure": pytest.approx(10e-9),
                    "serve.d2h": pytest.approx(5e-9)}
    assert sum(idle.values()) == pytest.approx(72e-9)
    top = spans.idle_by_span(devices, host, program, top=2)
    assert list(top) == ["serve.admit", "serve.fetch", "(rest)"]
    assert top["(rest)"] == pytest.approx(30e-9)
    with pytest.raises(ValueError):
        spans.idle_by_span(devices, host[2:], program)


def _unzip(tmp_path, name):
    path = tmp_path / name.removesuffix(".gz")
    path.write_bytes(gzip.decompress((DATA / name).read_bytes()))
    return path


def test_recorded_trace_without_program_spans(tmp_path):
    """The first recorded trace predates the program's spans: the idle
    attribution puts all of device 0's idle time under host:other, and
    ``read_all`` agrees with ``trace.read_events``."""
    path = _unzip(tmp_path, "paper.xplane.pb.gz")
    devices, host, program = spans.read_all(path)
    assert (devices, host) == trace.read_events(path)
    assert program == []
    idle = spans.idle_by_span(devices, host, program)
    r = trace.reduce(devices, host, chips=1)
    assert list(idle) == ["host:other"]
    assert idle["host:other"] == pytest.approx(r["window_s"] - r["busy_s"],
                                               rel=1e-9)


def test_recorded_trace_with_program_spans(tmp_path):
    """A 0.3 s window of paper-n16384-closed on one TPU v5e with the
    program's spans (gzipped): the reduction finds the ``serve.*`` spans,
    thirteen 64-row batches, and the idle attribution divides all of
    device 0's idle time, within 1 ns a gap."""
    path = _unzip(tmp_path, "paper-spans.xplane.pb.gz")
    devices, host, program = spans.read_all(path)
    assert (devices, host) == trace.read_events(path)
    r = trace.reduce(devices, host, chips=1)
    # as the reduction read it on the chip when the trace was recorded
    assert r["busy_s"] == pytest.approx(0.001637607, rel=1e-9)
    assert r["window_s"] == pytest.approx(0.311679178, rel=1e-9)
    counts: dict[str, int] = {}
    for _, _, name in program:
        counts[name] = counts.get(name, 0) + 1
    assert counts.pop("serve.admit") == 13 * 64
    assert counts == {name: 13 for name in (
        "serve.batch", "serve.dispatch", "serve.stack", "serve.h2d",
        "serve.launch", "serve.fetch", "serve.sync", "serve.d2h",
        "serve.deliver")}
    lo, hi = spans.window(host)
    dev0 = trace.clip(trace.union((s, e) for s, e, _ in devices[0]), lo, hi)
    idle_gaps = trace.gaps(dev0, lo, hi)
    idle_ns = sum(e - s for s, e in idle_gaps)
    by_span = spans.idle_by_span(devices, host, program, top=len(counts) + 2)
    assert "(rest)" not in by_span
    assert abs(1e9 * sum(by_span.values()) - idle_ns) <= len(idle_gaps)
    assert list(by_span)[0] == "serve.d2h"
    assert by_span["host:other"] < 0.25 * idle_ns / 1e9


def _window(root, name, trace_on, seed):
    # the trace directory is named by cell and seed: tests that may run at
    # once use seeds of their own
    cell = harness.Cell.load(name, root=root,
                             bench_file=root / "BENCHMARK.json")
    return spans.run_window(cell, seed=seed, seconds=0.5, trace_on=trace_on,
                            t_start=0.0, require_tpu=False)


@pytest.mark.parametrize("name,seed", [(PAPER, 2**31 + 5), (HE, 2**31 + 9)])
def test_traced_window_reads_the_programs_spans(tiny_root, name, seed):
    res = _window(tiny_root, name, True, seed)
    assert res["correct"]
    for quantity, cells in CELLS.items():
        got = res["quantities"][quantity]
        assert (got is not None) == (name in cells), quantity
    recorded = res["recorded"]["spans"]
    completed = res["metrics"]["throughput"] * 0.5
    assert recorded["serve.admit"]["count"] >= completed * 0.9
    # the engine's span around execute against the benchmark's
    assert abs(recorded["serve.dispatch"]["count"] - res["stages"]) <= 1
    assert res["trace_bytes"] > 0
    # on the CPU no op runs on a TPU: the whole window is device-0 idle,
    # and the attribution divides all of it
    idle = res["idle_by_span"]
    window_s = sum(idle.values())
    assert res["metrics"]["device_idle_share"] == pytest.approx(100.0)
    named = sum(v for k, v in idle.items()
                if k.startswith(spans.PROGRAM_PREFIXES))
    assert named > 0.5 * window_s


def test_untraced_window_reads_the_programs_counters(tiny_root):
    res = _window(tiny_root, PAPER, False, 2**31 + 11)
    assert res["correct"]
    assert "idle_by_span" not in res and "trace_bytes" not in res
    assert res["quantities"]["queue_wait_ms"] >= 0
    assert res["recorded"]["counters"]["queue_wait"]["count"] > 0


def test_window_with_an_engine_that_records_nothing(tiny_root, monkeypatch):
    """An engine without a recorder to read: every quantity is None, the
    rest of the object is whole (the trace still holds the spans)."""
    monkeypatch.setattr(spans, "recorder", lambda engine: None)
    res = _window(tiny_root, PAPER, True, 2**31 + 7)
    assert res["correct"]
    assert res["recorded"] is None
    assert set(res["quantities"].values()) == {None}
    assert res["metrics"]["stage_ms_per_batch"] > 0
    assert res["idle_by_span"]
