"""Window and percentile arithmetic of the metric readers on a fake clock."""
import numpy as np
import pytest

from chipbench import harness, stats


def _metric(name):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py").read


def _record(trace=None):
    # window [10, 14] on the fake clock; 100 requests completed in it, each
    # offered at 10 + i/25 and done 0.1 + i/1000 s later
    done = [harness.Request(i, "fft", i % 7, 10 + i / 25, None,
                            t_done=10 + i / 25 + 0.1 + i / 1000)
            for i in range(100)]
    stages = [harness.Stage(9.5, 9.6, 64, "fft")] + [
        harness.Stage(10 + k * 0.5, 10 + k * 0.5 + 0.02, 32 + k, "fft")
        for k in range(8)] + [harness.Stage(14.5, 14.6, 1, "fft")]
    return harness.RunRecord(t_start=2.0, t_open=10.0, t_close=14.0,
                             done=done, stages=stages, max_batch=64,
                             chips=2, min_s_per_op={"fft": 1e-4},
                             trace=trace)


def test_throughput_is_completions_over_the_window():
    assert _metric("throughput")(_record()) == pytest.approx(100 / 4.0)


def test_latency_percentiles_count_from_the_offer():
    rec = _record()
    lat = [100 + i for i in range(100)]       # ms
    assert rec.latencies_ms() == pytest.approx(lat)
    assert _metric("latency_p50_ms")(rec) == pytest.approx(149.5)
    assert _metric("latency_p95_ms")(rec) == pytest.approx(
        np.percentile(lat, 95))
    assert stats.percentile([], 50) is None


def test_setup_runs_from_process_start_to_the_window():
    assert _metric("setup_s")(_record()) == pytest.approx(8.0)


def test_stage_and_fill_count_only_batches_in_the_window():
    rec = _record()
    assert _metric("stage_ms_per_batch")(rec) == pytest.approx(20.0)
    assert _metric("batch_fill")(rec) == pytest.approx(
        100 * sum(range(32, 40)) / (8 * 64))


def test_trace_metrics_need_a_trace():
    assert _metric("device_idle_share")(_record()) is None
    assert _metric("route_roofline")(_record()) is None
    rec = _record({"busy_s": 1.0, "window_s": 4.0})
    assert _metric("device_idle_share")(rec) == pytest.approx(75.0)
    # 100 requests x 1e-4 s over 2 chips = 5 ms of 1 s busy
    assert _metric("route_roofline")(rec) == pytest.approx(0.5)


def test_reservoir_is_uniform_and_seeded():
    import random
    counts = np.zeros(50)
    for seed in range(400):
        r = harness.Reservoir(5, random.Random(seed))
        for i in range(50):
            r.offer(lambda i=i: i)
        counts[r.items] += 1
        r2 = harness.Reservoir(5, random.Random(seed))
        for i in range(50):
            r2.offer(lambda i=i: i)
        assert r.items == r2.items
    assert counts.sum() == 2000
    assert counts.min() > 15 and counts.max() < 70


def test_spread_is_quartile_distance_over_median():
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)
