"""Find an open-loop cell's knee: the highest offered rate whose backlog
does not grow over the window, judged on several seeds per rate.

    python chipbench/knee.py --workload <cell> --rates 4000,5000,6000 \
        --seeds 1,2,3 --seconds 10 [--write] [--out <file.jsonl>]

Each (rate, seed) point is one process (this parent never touches JAX):
the cell's own set-up, then one normal window (``harness.measure``) with
the workload's ``rate_per_s`` set to the rate. A point is sustained when

- the requests completed in the window are at least 97% of the offered rate
  times the window's length,
- at the close no more than a tenth of a second of arrivals is unserved, and
- the median latency of the last fifth of the window's arrivals is at most
  twice that of the first fifth.

A rate is sustained when more than half of its seeds are, so that one host
stall does not decide it; the sweep stops at the first rate that is not.
The knee is the highest rate at and below which every rate swept is
sustained. ``--write`` sets the workload's ``rate_per_s`` to 0.8x the knee,
to 10 req/s. Every point prints one JSON line, the last line the knee.
"""
import argparse
import json
import pathlib
import subprocess
import sys
import time

T_START = time.perf_counter()
HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
from chipbench.stats import percentile  # noqa: E402  (numpy only, never JAX)

SERVED_SHARE = 0.97         # completions / (rate x window), at least
BACKLOG_S = 0.1             # arrivals unserved at the close, in seconds
FIFTH_RATIO = 2.0           # last fifth's p50 / first fifth's, at most
SHARE_OF_KNEE = 0.8


def summarize(rate: float, t_open: float, t_close: float, offered) -> dict:
    """One point's reading from the requests its window offered (all of
    them completed: the engine drains after the close)."""
    window = t_close - t_open
    arrived = sorted(offered, key=lambda r: r.seq)
    served = sum(1 for r in arrived if r.t_done <= t_close)
    backlog = len(arrived) - served
    lat = [1e3 * (r.t_done - r.t_offer) for r in arrived]
    fifth = max(1, len(lat) // 5)
    first, last = percentile(lat[:fifth], 50), percentile(lat[-fifth:], 50)
    ok = (served >= SERVED_SHARE * rate * window
          and backlog <= BACKLOG_S * rate
          and last is not None and last <= FIFTH_RATIO * first)
    return {"rate_per_s": rate, "served_per_s": served / window,
            "backlog_at_close": backlog, "p50_ms": percentile(lat, 50),
            "p95_ms": percentile(lat, 95), "first_fifth_p50_ms": first,
            "last_fifth_p50_ms": last, "sustained": bool(ok)}


def rate_sustained(points: list[dict]) -> bool:
    return 2 * sum(p["sustained"] for p in points) > len(points)


def knee(points: list[dict]) -> float | None:
    """The highest rate at and below which every rate swept is sustained."""
    best = None
    for rate in sorted({p["rate_per_s"] for p in points}):
        if not rate_sustained([p for p in points if p["rate_per_s"] == rate]):
            break
        best = rate
    return best


def point(cell, rate: float, seed: int, seconds: float, *,
          require_tpu: bool = True) -> dict:
    """Set up the cell at ``rate`` and read one window (in this process)."""
    from chipbench import harness
    cell.workload["rate_per_s"] = rate
    harness.device_info(cell, require_tpu=require_tpu)
    session = harness.Session(cell, seed, t_start=T_START)
    harness.measure(session, seconds)
    out = summarize(rate, session.t_open, session.t_close, session.offered)
    out.update(seed=seed, setup_s=session.t_open - T_START,
               mean_batch=(sum(s.rows for s in session.stages)
                           / max(1, len(session.stages))),
               compiles=len(session.compiles.between(session.t_open,
                                                     session.t_close)))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates")
    ap.add_argument("--seeds")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--write", action="store_true")
    ap.add_argument("--out")
    ap.add_argument("--timeout", type=float, default=600)
    ap.add_argument("--point", nargs=2, metavar=("RATE", "SEED"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.point:
        from chipbench import harness
        harness.setup_process()
        cell = harness.Cell.load(args.workload)
        rate, seed = float(args.point[0]), int(args.point[1])
        print(json.dumps(point(cell, rate, seed, args.seconds)), flush=True)
        return 0
    if not (args.rates and args.seeds):
        ap.error("--rates and --seeds are required")
    points = []
    for rate in sorted(float(r) for r in args.rates.split(",")):
        here = []
        for seed in (int(s) for s in args.seeds.split(",")):
            cmd = [sys.executable, str(pathlib.Path(__file__).resolve()),
                   "--workload", args.workload, "--seconds",
                   str(args.seconds), "--point", str(rate), str(seed)]
            proc = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                                  text=True, timeout=args.timeout)
            last = (proc.stdout.strip().splitlines() or [""])[-1]
            try:
                row = json.loads(last)
            except json.JSONDecodeError:
                print(json.dumps({"rate_per_s": rate, "seed": seed,
                                  "rc": proc.returncode,
                                  "err": proc.stderr[-2000:]}), flush=True)
                return 1
            here.append(row)
            print(json.dumps(row), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
        points += here
        if not rate_sustained(here):
            break
    k = knee(points)
    rate = None if k is None else int(round(SHARE_OF_KNEE * k / 10) * 10)
    print(json.dumps({"knee_per_s": k, "rate_per_s": rate}), flush=True)
    if k is None:
        return 1
    if args.write:
        path = HERE / "workloads" / f"{args.workload}.json"
        wl = json.loads(path.read_text())
        wl["rate_per_s"] = rate
        path.write_text(json.dumps(wl, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
