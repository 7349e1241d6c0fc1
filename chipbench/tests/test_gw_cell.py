"""A whole CPU run of ``gw-n524288-closed`` at a tiny size, with the
planner's VMEM ceiling forced below the row so that the engine binds the
HBM-resident four-step, as it does at n = 2^19 on the chip."""
import pytest

from chipbench import harness
from chipbench.tests import conftest

CELL = "gw-n524288-closed"


@pytest.fixture
def gw_root(tmp_path, monkeypatch):
    monkeypatch.setitem(conftest.TINY, CELL, (
        {"n": 4096, "pool": 8, "check_sample": 4},
        {"max_batch": 2, "max_pending": 4},
        {"outstanding": 4}))
    from repro.core.fft import planner
    monkeypatch.setattr(planner, "_MAX_LOCAL_N", 256)
    monkeypatch.setattr(planner, "_MAX_LOCAL_N_REAL", 256)
    return conftest.make_root(tmp_path, [CELL])


def test_gw_cell_runs_on_the_hbm_tier_and_is_correct(gw_root):
    cell = harness.Cell.load(CELL, root=gw_root,
                             bench_file=gw_root / "BENCHMARK.json")
    res = harness.run_cell(cell, seed=2**31 + 15, seconds=1.5, trace=False,
                           t_start=0.0, require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) >= {"throughput", "latency_p50_ms",
                                   "setup_s"}
    check = res["checks"]["polymul-real_rel_err"]
    assert check["value"] < check["limit"]


def test_gw_cell_binds_the_hbm_route(gw_root):
    cell = harness.Cell.load(CELL, root=gw_root,
                             bench_file=gw_root / "BENCHMARK.json")
    session = harness.Session(cell, 2**31 + 16, t_start=0.0)
    session.compiles.close()
    bound = session.bounds["polymul-real"]
    assert bound.route == "polymul-real-hbm"
    assert bound.plan.tier == "hbm" and bound.plan.inner_n == 256


def _read(name, trace, min_s=1e-3):
    rec = harness.RunRecord(
        t_start=0.0, t_open=1.0, t_close=2.0,
        done=[harness.Request(i, "polymul-real", 0, 1.0, None, t_done=1.5)
              for i in range(4)],
        stages=[], max_batch=2, chips=1,
        min_s_per_op={"polymul-real": min_s}, trace=trace)
    return harness.load_module(
        harness.HERE / "metrics" / f"{name}.py").read(rec)


def test_outer_metrics_read_the_named_device_ops():
    trace = {"busy_s": 0.5, "window_s": 2.0, "device_ops": [
        ["%fft_planes.2", 0.2], ["%fft_planes.3", 0.1],
        ["%hbm_outer_fwd.1", 0.08], ["%hbm_outer_inv.1", 0.04],
        ["%copy", 0.08]]}
    # busy 0.5 s, of which 0.3 s in the inner row kernel
    assert _read("outer_pass_share", trace) == pytest.approx(40.0)
    # 4 requests x 1 ms of 12n bytes; the outer kernels' least is 24n a
    # product: 8 ms over their 0.12 s
    assert _read("hbm_outer_roofline", trace) == pytest.approx(
        100 * 8e-3 / 0.12)


def test_outer_metrics_read_nothing_without_the_tier():
    trace = {"busy_s": 0.5, "window_s": 2.0, "device_ops": [
        ["%polymul_real_planes.1", 0.4], ["%copy", 0.1]]}
    assert _read("outer_pass_share", trace) is None
    assert _read("hbm_outer_roofline", trace) is None
    assert _read("outer_pass_share", None) is None
