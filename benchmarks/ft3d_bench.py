"""Time the one-device 3-D FFT and the NPB FT solve on a TPU, pass by pass.

    python benchmarks/ft3d_bench.py [--shape 256,256,512] [--niter 20] \
        [--runs 5] [--out <file.json>]

On a seeded grid of ``--shape`` (NZ, NY, NX; NPB FT Class B by default)
already on the device, each case runs twice to compile and warm, then
``--runs`` timed calls (launch to ``block_until_ready``, host clock), then
two calls under the profiler, whose device ops are summed by kernel name:

  * ``x``: the NX-point rows through ``kernels.fft.fft_planes``;
  * ``y``, ``z``: ``core.fft.hbm.axis_dft`` over the (NZ, NY, NX) and
    (1, NZ, NY*NX) views;
  * ``fft3``: ``core.fft.ft3d.fft3_planes``, all three passes;
  * ``xla_fftn``: ``jnp.fft.fftn`` of the complex64 grid, XLA's own 3-D
    transform, the baseline;
  * ``nas_ft``: one whole ``ft3d.nas_ft`` job (1 + niter transforms).

``fft3`` and ``xla_fftn`` are compared with numpy's float64 ``fftn`` (max
abs error / max abs reference). The last line of output is one JSON
object; the script refuses to run without a TPU.
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
import jax.numpy as jnp  # noqa: E402

from chipbench import trace as trace_mod  # noqa: E402
from repro.core.fft import ft3d, hbm  # noqa: E402
from repro.kernels import fft as kfft  # noqa: E402


def _device_ms(fn, args, calls: int) -> dict:
    """Device milliseconds per call: busy, and by op name."""
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(calls):
            jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        devices, _ = trace_mod.read_events(trace_mod.find_xplane(d))
    by: dict[str, float] = {}
    for s, e, name in devices.get(0, []):
        op = name.split(" = ", 1)[0].lstrip("%").split(".", 1)[0]
        by[op] = by.get(op, 0.0) + (e - s) / 1e6 / calls
    busy = sum(e - s for s, e in trace_mod.union(
        (s, e) for s, e, _ in devices.get(0, []))) / 1e6 / calls
    top = dict(sorted(by.items(), key=lambda kv: -kv[1])[:8])
    return {"busy": busy, "ops": top}


def _case(name: str, fn, args, runs: int) -> tuple[dict, object]:
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - t0))
    row = {"case": name, "compile_s": compile_s,
           "call_ms_median": statistics.median(times), "call_ms": times,
           "device_ms": _device_ms(fn, args, 2)}
    print(json.dumps(row), flush=True)
    return row, out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--shape", default="256,256,512")
    ap.add_argument("--niter", type=int, default=20)
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--out")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"ft3d_bench: no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    shape = tuple(int(x) for x in args.shape.split(","))
    nz, ny, nx = shape
    n = nz * ny * nx
    rng = np.random.default_rng(2**31 + 23)
    u0 = (rng.random(n, dtype=np.float32)
          + 1j * rng.random(n, dtype=np.float32)).astype(np.complex64)
    xr = jax.device_put(u0.real.reshape(1, n).copy())
    xi = jax.device_put(u0.imag.reshape(1, n).copy())
    grid = jax.device_put(u0.reshape(shape))
    rows = []

    def rec(*a):
        rows.append(a[0])
        return a[1]
    rec(*_case("x", lambda a, b: kfft.fft_planes(
        a.reshape(nz * ny, nx), b.reshape(nz * ny, nx)), (xr, xi),
        args.runs))
    rec(*_case("y", lambda a, b: hbm.axis_dft(
        a.reshape(nz, ny, nx), b.reshape(nz, ny, nx)), (xr, xi), args.runs))
    rec(*_case("z", lambda a, b: hbm.axis_dft(
        a.reshape(1, nz, ny * nx), b.reshape(1, nz, ny * nx)), (xr, xi),
        args.runs))
    want = np.fft.fftn(u0.astype(np.complex128).reshape(shape)).reshape(-1)
    scale = float(np.abs(want).max())
    yr, yi = rec(*_case("fft3", jax.jit(
        lambda a, b: ft3d.fft3_planes(a, b, shape)), (xr, xi), args.runs))
    got = np.asarray(yr)[0] + 1j * np.asarray(yi)[0]
    rows[-1]["rel_err"] = float(np.abs(got - want).max()) / scale
    y = rec(*_case("xla_fftn", jax.jit(jnp.fft.fftn), (grid,), args.runs))
    rows[-1]["rel_err"] = float(
        np.abs(np.asarray(y).reshape(-1) - want).max()) / scale
    rec(*_case("nas_ft", lambda g: ft3d.nas_ft(
        g.reshape(1, n), shape=shape, niter=args.niter, alpha=1e-6),
        (grid,), max(2, args.runs // 2)))
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices())},
              "shape": shape, "niter": args.niter,
              "cases": [{k: v for k, v in r.items() if k != "call_ms"}
                        for r in rows]}
    if args.out:
        pathlib.Path(args.out).write_text(json.dumps(result, indent=1))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
