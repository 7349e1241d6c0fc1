"""The knee sweep's rule: a point is sustained when its backlog did not
grow, a rate when most of its seeds are, and the knee is the highest rate
at and below which every rate is."""
from chipbench import harness, knee


def _offered(rate, seconds, latency_s, growth_s=0.0):
    """Arrivals at ``rate`` over ``seconds``, each served ``latency_s``
    after it was due plus ``growth_s`` for every second of the window."""
    count = int(rate * seconds)
    return [harness.Request(i, "polymul-real", 0, i / rate, None,
                            t_done=i / rate + latency_s
                            + growth_s * i / rate)
            for i in range(count)]


def test_a_steady_point_is_sustained():
    p = knee.summarize(1000.0, 0.0, 10.0, _offered(1000, 10, 0.005))
    assert p["sustained"] and p["backlog_at_close"] <= 5
    assert abs(p["p50_ms"] - 5.0) < 1e-6


def test_a_growing_backlog_is_not_sustained():
    p = knee.summarize(1000.0, 0.0, 10.0,
                       _offered(1000, 10, 0.005, growth_s=0.1))
    assert not p["sustained"]
    assert p["last_fifth_p50_ms"] > 2 * p["first_fifth_p50_ms"]
    assert p["backlog_at_close"] > 100


def test_most_seeds_decide_a_rate_and_the_first_failing_rate_ends_it():
    def pt(rate, ok):
        return {"rate_per_s": rate, "sustained": ok}
    points = ([pt(4000, True), pt(4000, False), pt(4000, True)]
              + [pt(5000, True)] * 3
              + [pt(6000, False), pt(6000, False), pt(6000, True)]
              + [pt(7000, True)] * 3)
    assert knee.knee(points) == 5000
    assert knee.knee([pt(4000, False), pt(4000, True)]) is None


def test_a_point_runs_the_normal_window(tiny_root):
    cell = harness.Cell.load("polyreal-n16384-open", root=tiny_root,
                             bench_file=tiny_root / "BENCHMARK.json")
    p = knee.point(cell, 50.0, 2**31 + 9, 1.0, require_tpu=False)
    assert p["compiles"] == 0 and p["seed"] == 2**31 + 9
    assert p["served_per_s"] > 0 and p["p50_ms"] > 0
