"""The reduction from a profiler trace to busy time, ops and idle gaps."""
import gzip
import pathlib

import pytest

from chipbench import trace

DATA = pathlib.Path(__file__).parent / "data"
OPEN, CLOSE = trace.OPEN, trace.CLOSE


def test_union_clip_and_gaps():
    u = trace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert trace.clip(u, 1, 6) == [(1, 3), (5, 6)]
    assert trace.gaps(u, -1, 10) == [(-1, 0), (3, 5), (8, 10)]


def test_reduce_on_a_synthetic_trace():
    devices = {0: [(10, 20, "fusion"), (15, 30, "kernel"), (50, 60, "kernel"),
                   (0, 5, "before")],
               1: [(10, 40, "kernel")]}
    spans = [(8, 8, OPEN), (100, 100, CLOSE),
             (30, 50, "chipbench.stage"), (60, 90, "chipbench.fetch"),
             (60, 70, "chipbench.stage")]
    r = trace.reduce(devices, spans, chips=2)
    assert r["window_s"] == pytest.approx(92e-9)
    # device 0 busy 10..30 and 50..60 = 30 ns; device 1: 30 ns
    assert r["busy_s"] == pytest.approx(30e-9)
    ops = dict(r["device_ops"])
    assert ops["kernel"] == pytest.approx((15 + 10 + 30) / 2 * 1e-9)
    assert "before" not in ops
    gaps = r["idle_gaps"]
    assert gaps[0] == ["chipbench.fetch", pytest.approx(40e-9)]
    assert gaps[1] == ["chipbench.stage", pytest.approx(20e-9)]
    assert gaps[2] == ["host:other", pytest.approx(2e-9)]


def test_reduce_without_window_markers_fails():
    with pytest.raises(ValueError):
        trace.reduce({0: [(0, 1, "x")]}, [], chips=1)


def test_reduction_of_a_trace_recorded_on_the_chip(tmp_path):
    """A 0.3 s window of paper-n16384-closed on one TPU v5e, recorded with
    ``record_trace.py`` (gzipped): the device plane and the window markers
    are found, the busy time lies inside the window, and the ops and gaps
    are named."""
    path = tmp_path / "paper.xplane.pb"
    path.write_bytes(gzip.decompress((DATA / "paper.xplane.pb.gz")
                                     .read_bytes()))
    devices, spans = trace.read_events(path)
    assert set(devices) == {0}
    r = trace.reduce(devices, spans, chips=1)
    assert 0 < r["busy_s"] < r["window_s"] < 5
    # as the reduction read it on the chip when the trace was recorded
    assert r["busy_s"] == pytest.approx(0.001456597, rel=1e-9)
    assert r["window_s"] == pytest.approx(0.303387877, rel=1e-9)
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
    names = [name for name, _ in r["device_ops"]]
    assert "%polymul_real_planes.1" in names and "%fft_planes.1" in names
    assert r["idle_gaps"]
    labels = {name for name, _ in r["idle_gaps"]}
    assert labels <= {"chipbench.stage", "chipbench.fetch", "host:other"}
