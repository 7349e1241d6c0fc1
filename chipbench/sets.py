"""Run one cell several times, one process per run, and report the spread.

    python chipbench/sets.py --workload <cell> --seeds 1,2,3 --seconds 30 \
        [--trace 1] [--repeat 2] [--out <file.jsonl>]

Each run is ``run.py`` in a child process (this parent never touches JAX,
so the child holds the chip). Every result line is appended to ``--out``;
at the end each metric's median and spread (the distance between the first
and third quartile as a share of the median) is printed per set, with the
checks' largest value. ``--repeat 2`` runs the seeds twice, as two sets.
"""
import argparse
import json
import pathlib
import statistics
import subprocess
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))
from chipbench.stats import spread  # noqa: E402  (numpy only, never JAX)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--repeat", type=int, default=1)
    ap.add_argument("--out")
    ap.add_argument("--timeout", type=float, default=1200)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    sets = []
    for rep in range(args.repeat):
        rows = []
        for seed in seeds:
            t0 = time.perf_counter()
            cmd = [sys.executable, str(HERE / "run.py"), "--workload",
                   args.workload, "--seed", str(seed), "--seconds",
                   str(args.seconds), "--trace", str(args.trace)]
            proc = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                                  text=True, timeout=args.timeout)
            wall = time.perf_counter() - t0
            last = proc.stdout.strip().splitlines()[-1:] or [""]
            try:
                res = json.loads(last[0])
            except json.JSONDecodeError:
                res = None
            row = {"set": rep, "seed": seed, "rc": proc.returncode,
                   "wall_s": wall, "result": res}
            if res is None:
                row["stderr"] = proc.stderr[-3000:]
            rows.append(row)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
            brief = ({k: round(v["value"], 4)
                      for k, v in res["metrics"].items()} if res else None)
            checks = ({k: v["value"] for k, v in res["checks"].items()}
                      if res else None)
            print(json.dumps({"set": rep, "seed": seed, "rc": proc.returncode,
                              "wall_s": round(wall, 1),
                              "correct": res and res["correct"],
                              "metrics": brief, "checks": checks,
                              "compiles": res and len(
                                  res["notes"]["compiles_in_window"]),
                              "err": None if res else proc.stderr[-1500:]}),
                  flush=True)
        sets.append(rows)
    for rep, rows in enumerate(sets):
        ok = [r["result"] for r in rows if r["result"]]
        names = sorted({m for r in ok for m in r["metrics"]})
        summary = {}
        for m in names:
            vals = [r["metrics"][m]["value"] for r in ok if m in r["metrics"]]
            summary[m] = {"median": statistics.median(vals),
                          "spread": spread(vals) if len(vals) > 1 else None,
                          "n": len(vals)}
        worst = {}
        for r in ok:
            for k, v in r["checks"].items():
                if v["value"] is not None:
                    worst[k] = max(worst.get(k, v["value"]), v["value"])
        print(json.dumps({"set": rep, "summary": summary,
                          "worst_checks": worst,
                          "all_correct": all(r["correct"] for r in ok)
                          and len(ok) == len(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
