"""How a batch of complex results should come back from a TPU to the host.

    python benchmarks/result_wire_bench.py [--n 16384] [--batch 64] [--runs 10]

The Fourier kernels compute float32 planes ``(re, im)``. Three ways to hand
a ``(batch, m)`` complex64 result to the host, each timed end to end on the
real kernel's output:

- ``complex64``: the route joins the planes on the device
  (``kernels.ops.join``) and the host copies the complex64 array
  (``np.asarray``);
- ``words``: the route interleaves the planes on the device into one
  float32 ``(batch, 2m)`` array (``launch.ops._as_words``, what the serving
  routes do); the host copies it and views it as complex64 (no copy);
- ``planes``: the route returns both planes; the host copies each and
  writes them into a new complex64 array through its float32 view.

Each run times ``sync`` (launch to ``block_until_ready``: kernel plus the
join or interleave), ``d2h`` (``np.asarray``) and ``join`` (the host's view
or join). Variants alternate, ``--runs`` runs each after two warm-ups, for
the fft (``m = n``) and the rfft half spectrum (``m = n/2 + 1``). The last
line of output is one JSON object with every run and the device it ran on;
the script refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels import ops as kops  # noqa: E402
from repro.launch import ops as op_registry  # noqa: E402

PLANES = {"fft": lambda x: kops.fft_planes(x, backend="pallas"),
          "rfft": lambda x: kops.rfft_planes(x, backend="pallas")}


def _host_words(out):
    return out.view(np.complex64)


def _host_planes(out):
    yr, yi = out
    res = np.empty(yr.shape, np.complex64)
    words = res.view(np.float32).reshape(*yr.shape, 2)
    words[..., 0] = yr
    words[..., 1] = yi
    return res


def variants(planes):
    """name -> (jitted device fn, host fn after ``np.asarray``)."""
    return {
        "complex64": (jax.jit(lambda x: kops.join(*planes(x))), None),
        "words": (op_registry._as_words(planes)["fn"], _host_words),
        "planes": (jax.jit(planes), _host_planes),
    }


def _to_host(out):
    if isinstance(out, tuple):
        return tuple(np.asarray(o) for o in out)
    return np.asarray(out)


def time_one(fn, host, x) -> dict:
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(x))
    t1 = time.perf_counter()
    arr = _to_host(out)
    t2 = time.perf_counter()
    res = arr if host is None else host(arr)
    t3 = time.perf_counter()
    assert res.dtype == np.complex64
    return {"sync_ms": (t1 - t0) * 1e3, "d2h_ms": (t2 - t1) * 1e3,
            "join_ms": (t3 - t2) * 1e3, "total_ms": (t3 - t0) * 1e3}, res


def bench(name, planes, x, runs: int) -> dict:
    vs = variants(planes)
    results = {v: [] for v in vs}
    ref = None
    for v, (fn, host) in vs.items():          # compile + warm
        for _ in range(2):
            _, res = time_one(fn, host, x)
        if ref is None:
            ref = res
        # the same float32 numbers, whichever way they came back
        assert np.array_equal(res, ref), f"{name}/{v} differs"
    for _ in range(runs):
        for v, (fn, host) in vs.items():
            results[v].append(time_one(fn, host, x)[0])
    return results


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=16384)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--runs", type=int, default=10)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"result_wire_bench: needs a TPU, found {dev.platform}")
    rng = np.random.default_rng(0)
    shape = (args.batch, args.n)
    xc = jnp.asarray((rng.standard_normal(shape)
                      + 1j * rng.standard_normal(shape)).astype(np.complex64))
    xr = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    report = {"device": {"platform": dev.platform,
                         "device_kind": dev.device_kind,
                         "count": jax.device_count()},
              "n": args.n, "batch": args.batch, "runs": {}}
    for name, x in (("fft", xc), ("rfft", xr)):
        res = bench(name, PLANES[name], x, args.runs)
        report["runs"][name] = res
        for v, rows in res.items():
            med = {k: statistics.median(r[k] for r in rows) for k in rows[0]}
            print(f"{name:5s} {v:9s} " + " ".join(
                f"{k}={med[k]:.3f}" for k in med), flush=True)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
