"""Helpers for the benchmark's own tests (run: python -m pytest chipbench/tests).

``tiny_cell`` copies the benchmark's traffic, ops and metrics files into a
temporary root beside a shrunken copy of one cell's configuration and
workload, so a whole run can be driven on the CPU in seconds.
"""
import copy
import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "chipbench"
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# per-cell shrinkage: configuration fields, engine fields, workload fields
TINY = {
    "paper-n16384-closed": ({"n": 256, "pool": 16, "check_sample": 8},
                            {"max_batch": 4, "max_pending": 16},
                            {"outstanding": 16}),
    "polyreal-n16384-open": ({"n": 256, "pool": 16, "check_sample": 8},
                             {"max_batch": 4, "max_pending": 16},
                             {"rate_per_s": 100}),
    "he128-n8192-closed": ({"n": 64, "pool": 4, "check_sample": 3},
                           {"max_batch": 2, "max_pending": 4},
                           {"outstanding": 4}),
}


def make_root(tmp: pathlib.Path, cells=None) -> pathlib.Path:
    """A benchmark root under ``tmp`` with the named cells shrunk."""
    for d in ("traffic", "ops", "metrics"):
        shutil.copytree(BENCH / d, tmp / d)
    (tmp / "configs").mkdir()
    (tmp / "workloads").mkdir()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name in cells or TINY:
        cfg_over, eng_over, wl_over = TINY[name]
        wl = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
        cfg = json.loads((BENCH / "configs" / f"{wl['config']}.json")
                         .read_text())
        cfg.update(copy.deepcopy(cfg_over))
        cfg["engine"].update(eng_over)
        wl.update(wl_over)
        (tmp / "workloads" / f"{name}.json").write_text(json.dumps(wl))
        (tmp / "configs" / f"{wl['config']}.json").write_text(json.dumps(cfg))
        if name not in {w["name"] for w in bench["workloads"]}:
            # a cell kept as files for a later PR, not yet in BENCHMARK.json
            bench["workloads"].append({"name": name, "config": wl["config"],
                                       "traffic": wl["traffic"],
                                       "chips": cfg["chips"], "why": "-"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
