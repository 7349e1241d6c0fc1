"""Run a cell's control: the plain reference of each op computed one step
below the precision (or without the exactness) the configuration states,
on the payloads a run with each seed draws, put in place of the window's
sampled results and judged by the run's own decision (``harness.check``'s
comparison and ``harness.verdict``).

    python chipbench/control.py --workload <cell> --seeds 1,2,3

Each seed prints one JSON line: ``correct`` as a run would report it, and
``{<op>_<check>: {value, limit}}``. The control must come out not correct
on every seed; the exit code is 1 where it does not. The benchmark's own
runs never run this.
"""
import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np
    from chipbench import harness
    harness.setup_process()
    cell = harness.Cell.load(args.workload)
    print(json.dumps({"device": {k: v for k, v in harness.device_info(
        cell, require_tpu=False).items() if k != "peaks"}}), flush=True)
    cfg = cell.config
    size = min(int(cfg["check_sample"]),
               int(cfg.get("pool", harness.DEFAULT_POOL)))
    wrong = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        # the pools a run with this seed draws (its first seed stream)
        ops, pools = harness.make_pools(
            cell, np.random.SeedSequence(seed).spawn(4)[0])
        rows = {op: list(zip(range(size), mod.control(pools[op],
                                                      list(range(size)), cfg)))
                for op, mod in ops.items()}
        checks = harness.compare_rows(cfg, ops, pools, rows)
        correct = harness.verdict(checks, 0)
        wrong += correct
        print(json.dumps({"seed": seed, "correct": correct, **checks,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
