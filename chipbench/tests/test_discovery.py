"""Cells, configurations, traffic kinds and metrics are found by name: a
new one is new files and a BENCHMARK.json row, never an edit."""
import json

import pytest

from chipbench import harness


def _add_cell(root, name, *, loop="closed", metrics_rows=None):
    wl = json.loads((root / "workloads" / "paper-n16384-closed.json")
                    .read_text())
    wl.update(traffic=f"{loop}-throwaway", loop=loop)
    (root / "workloads" / f"{name}.json").write_text(json.dumps(wl))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": name, "config": wl["config"],
                               "traffic": wl["traffic"], "chips": 1,
                               "why": "throwaway"})
    for row in metrics_rows or []:
        bench["per_layer"].append(row)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_a_new_workload_file_is_found_by_name(tiny_root):
    _add_cell(tiny_root, "throwaway-cell")
    cell = harness.Cell.load("throwaway-cell", root=tiny_root,
                             bench_file=tiny_root / "BENCHMARK.json")
    assert cell.config["n"] == 256
    assert cell.module("traffic", "closed").batch_sizes(cell.workload, 4) \
        == [4]
    assert cell.metric_names(False) == ["throughput", "latency_p50_ms",
                                        "latency_p95_ms", "setup_s"]


def test_a_cell_missing_from_benchmark_json_is_refused(tiny_root):
    wl = (tiny_root / "workloads" / "paper-n16384-closed.json").read_text()
    (tiny_root / "workloads" / "unlisted.json").write_text(wl)
    with pytest.raises(harness.BenchError):
        harness.Cell.load("unlisted", root=tiny_root,
                          bench_file=tiny_root / "BENCHMARK.json")


@pytest.mark.parametrize("name", ["../x", "a b", "", "x/y"])
def test_bad_names_are_refused(tiny_root, name):
    with pytest.raises(harness.BenchError):
        harness.Cell.load(name, root=tiny_root,
                          bench_file=tiny_root / "BENCHMARK.json")


def test_new_traffic_kind_and_metric_are_new_files(tiny_root):
    """A throwaway loop (the closed loop under another name) and a
    throwaway per-layer metric drive a whole run without an edit."""
    src = (tiny_root / "traffic" / "closed.py").read_text()
    (tiny_root / "traffic" / "throwaway.py").write_text(src)
    (tiny_root / "metrics" / "rows_per_stage.py").write_text(
        "def read(run):\n"
        "    s = run.stages_in_window()\n"
        "    return sum(x.rows for x in s) / len(s) if s else None\n")
    (tiny_root / "metrics" / "nothing_to_read.py").write_text(
        "def read(run):\n    return None\n")
    cell = "throwaway-loop"
    _add_cell(tiny_root, cell, loop="throwaway", metrics_rows=[
        {"name": "rows_per_stage", "unit": "rows", "better": "higher",
         "source": "program_counter", "layer": "engine batch formation",
         "moves": "throughput", "workloads": [cell]},
        {"name": "nothing_to_read", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "engine staging",
         "moves": "throughput"}])
    loaded = harness.Cell.load(cell, root=tiny_root,
                               bench_file=tiny_root / "BENCHMARK.json")
    res = harness.run_cell(loaded, seed=3, seconds=0.5, trace=True,
                           t_start=0.0, require_tpu=False)
    assert res["correct"]
    assert res["metrics"]["rows_per_stage"]["value"] == 4
    assert "nothing_to_read" not in res["metrics"]
    # listed for other cells only
    assert "stage_ms_per_batch" not in res["metrics"]
