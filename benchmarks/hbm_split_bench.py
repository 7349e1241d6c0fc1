"""Choose the HBM-resident tier's split n = n1 x n2 on a TPU.

    python benchmarks/hbm_split_bench.py [--n 524288] [--batch 16] \
        [--inner 8192,16384,32768,65536] [--runs 20] [--out <file.json>]

For each inner length n2 the real product ``core.fft.hbm.polymul_real_hbm``
runs on a seeded standard-normal batch already on the device: two warm-ups,
then ``--runs`` timed calls (launch to ``block_until_ready``, host clock),
then five calls under the profiler, whose device ops are summed per pass by
name: ``hbm_outer_fwd``, ``fft_planes`` (the inner rows, forward and
inverse), ``hbm_outer_inv``, and everything else (the paired product and
the copies). Each split's result is compared with numpy's float64
``irfft(rfft(a) * rfft(b))`` (worst row of max abs error / max abs
reference). The last line of output is one JSON object with every split and
the device; the script refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import tempfile
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from chipbench import trace as trace_mod  # noqa: E402
from repro.core.fft import hbm  # noqa: E402

PASSES = ("hbm_outer_fwd", "fft_planes", "hbm_outer_inv")


def _device_ms(fn, a, b, calls: int) -> dict:
    """Device milliseconds per call by pass, from a profiler trace."""
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(calls):
            jax.block_until_ready(fn(a, b))
        jax.profiler.stop_trace()
        devices, _ = trace_mod.read_events(trace_mod.find_xplane(d))
    by = dict.fromkeys(PASSES + ("other",), 0.0)
    for s, e, name in devices.get(0, []):
        op = name.split(" = ", 1)[0].lstrip("%").split(".", 1)[0]
        by[op if op in PASSES else "other"] += (e - s) / 1e6
    busy = sum(e - s for s, e in trace_mod.union(
        (s, e) for s, e, _ in devices.get(0, []))) / 1e6
    out = {k: v / calls for k, v in by.items()}
    out["busy"] = busy / calls
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=1 << 19)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--inner", default="8192,16384,32768,65536")
    ap.add_argument("--runs", type=int, default=20)
    ap.add_argument("--out")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"hbm_split_bench: no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    rng = np.random.default_rng(2**31 + 19)
    a = rng.standard_normal((args.batch, args.n), dtype=np.float32)
    b = rng.standard_normal((args.batch, args.n), dtype=np.float32)
    want = np.fft.irfft(np.fft.rfft(a.astype(np.float64))
                        * np.fft.rfft(b.astype(np.float64)), args.n)
    da, db = jax.device_put(a), jax.device_put(b)
    rows = []
    for inner in (int(x) for x in args.inner.split(",")):
        def fn(x, y, inner=inner):
            return hbm.polymul_real_hbm(x, y, inner_n=inner)
        t0 = time.perf_counter()
        got = np.asarray(jax.block_until_ready(fn(da, db)))
        compile_s = time.perf_counter() - t0
        jax.block_until_ready(fn(da, db))
        times = []
        for _ in range(args.runs):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(da, db))
            times.append(1e3 * (time.perf_counter() - t0))
        err = float((np.abs(got - want).max(-1)
                     / np.abs(want).max(-1)).max())
        row = {"n1": args.n // inner, "n2": inner, "compile_s": compile_s,
               "call_ms_median": statistics.median(times),
               "call_ms": times, "device_ms": _device_ms(fn, da, db, 5),
               "rel_err": err}
        rows.append(row)
        print(json.dumps({k: v for k, v in row.items() if k != "call_ms"}),
              flush=True)
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices())},
              "n": args.n, "batch": args.batch, "splits": rows}
    if args.out:
        pathlib.Path(args.out).write_text(json.dumps(result, indent=1))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
