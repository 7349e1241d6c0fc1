"""Chip benchmark of the serving engine: one run of one cell.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up binds the cell's buckets on ``ServeEngine``, draws the payload pools
from ``--seed`` and warms every batch shape the traffic will use; then the
window opens, the traffic runs for ``--seconds`` (to the first batch
completion after them), the engine drains, and a sample of the window's
results is compared with the plain reference of ``ops/<op>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones from a profiler trace of the window),
``device``, with a trace ``breakdown``, then ``notes`` and, last,
``checks``: each number compared with its limit, also the last lines of
standard error. The run fails, and prints no such line, when JAX finds no
TPU or too few chips, when the chip's kind has no row in ``peaks.json``,
when ``REPRO_FFT_BACKEND`` is set, or when the cell's files are missing.

JAX's persistent compilation cache lives at ``<checkout>/.jax_cache``
(``repro.launch.cache``), so only a checkout's first run of a cell
compiles. A compile inside the window is printed to standard error and
kept in the run's file under ``chipbench/out/``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    if os.environ.get("REPRO_FFT_BACKEND"):
        print("chipbench: REPRO_FFT_BACKEND is set; it swaps the serving "
              "path for the jnp reference", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness
    harness.setup_process()
    try:
        cell = harness.Cell.load(args.workload)
        result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                                  trace=bool(args.trace), t_start=T_START)
    except harness.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    harness.OUT_DIR.mkdir(exist_ok=True)
    run_file = harness.OUT_DIR / f"{args.workload}-{args.seed}-t{args.trace}.json"
    run_file.write_text(json.dumps(result, indent=1) + "\n")
    compiles = result["notes"]["compiles_in_window"]
    if compiles:
        print(f"chipbench: {len(compiles)} compile events inside the window: "
              f"{sorted(set(compiles))}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
