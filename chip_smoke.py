"""Smoke run of the serving engine on a TPU: the quickest proof the system
still starts on the chip and gives right answers there.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the four-step tiers on four chips

One chip: one ``ServeEngine`` (the engine ``repro.launch.serve --service
engine`` runs) serves fft, rfft, polymul, polymul-real and polymul-mod at
n = 16384, the paper's largest sweep length, with a 64-row batch; a second
engine serves polymul-mod on the RNS route at the HomomorphicEncryption.org
128-bit point (N = 8192, log Q = 218: Q is 8 limb primes, and the route
works in Q's own basis). Every bucket warms up
(compile time is reported as set-up), serves full batches, and has served
results checked by the registry's numpy or big-int oracle; every bucket's
compiled program must hold a Pallas kernel (``tpu_custom_call``).

``--four-chips`` runs only the four-step tiers: an engine with four model
shards serves polymul-real and polymul-mod at n = 65536, checks results
against the oracles, and shows that four devices hold each result.

The last line of standard output is one JSON object naming the device. The
script fails, and prints no such line, when JAX finds no TPU, when
``REPRO_FFT_BACKEND`` would swap the serving path for the jnp reference, or
when any phase fails.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

N = 16384
RNS_N, RNS_BITS = 8192, 218
DIST_N = 1 << 16
SEED = 0


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _kernel_text(bound, batch: int) -> str:
    """Compiled text of the bucket's program at the warm batch."""
    import jax
    import jax.numpy as jnp
    if bound.rns is not None:        # host CRT around one Pallas launch
        from repro.kernels.ntt import rns_ntt_polymul
        basis = bound.rns.own_basis or bound.rns    # what rns_polymul runs
        spec = jax.ShapeDtypeStruct((basis.k, batch, bound.n), jnp.uint32)
        return rns_ntt_polymul.lower(spec, spec, basis).compile().as_text()
    zeros = bound.spec.warmup_payload(bound, batch)
    return jax.jit(bound.fn).lower(*zeros).compile().as_text()


def _serve(engine, buckets, per_bucket: int, rng, *, kernel_check: bool):
    """Register, warm up, serve ``per_bucket`` requests per bucket in full
    batches, and check ``checks`` results per bucket against the oracle.
    Returns one report dict per bucket."""
    bounds = {key: engine.register(*key) for key in buckets}
    compile_s = {}
    for key, bound in bounds.items():
        t0 = time.perf_counter()
        bound.warmup(engine.max_batch)
        compile_s[key] = time.perf_counter() - t0
    kept = {key: [] for key in buckets}
    t_gen = time.perf_counter()
    for i in range(per_bucket):
        for key, bound in bounds.items():
            payload = bound.random_payload(rng)
            rid = engine.submit(*key, payload)
            if i % max(1, per_bucket // 4) == 0:
                kept[key].append((rid, payload))
    _log(f"generated {per_bucket * len(buckets)} requests in "
         f"{time.perf_counter() - t_gen:.1f} s")
    stats = engine.run(per_bucket * len(buckets))
    reports = []
    for key, bound in bounds.items():
        errs = [bound.verify(p, engine.results[rid]) for rid, p in kept[key]]
        b = stats["buckets"][f"{key[0]}/n={key[1]}"]
        has_kernel = None
        if kernel_check:
            has_kernel = "tpu_custom_call" in _kernel_text(bound,
                                                           engine.max_batch)
        reports.append({
            "op": key[0], "n": key[1], "route": bound.route,
            "plan": bound.plan.describe(), "served": b["served"],
            "batches": b["batches"], "checked": len(errs),
            "worst_rel_err": ("exact" if bound.payload_dtype in
                              (np.uint32, object) else max(errs)),
            "compile_s": compile_s[key], "tpu_custom_call": has_kernel,
            "observed_s_per_req": b["observed_s_per_req"],
        })
    return reports


def _print(reports) -> None:
    for r in reports:
        _log(" ".join(f"{k}={v}" for k, v in r.items()))


def one_chip(rng) -> int:
    from repro.launch.engine import ServeEngine
    ops = ("fft", "rfft", "polymul", "polymul-real", "polymul-mod")
    engine = ServeEngine(max_batch=64, max_pending=4096)
    reports = _serve(engine, [(op, N) for op in ops], 128, rng,
                     kernel_check=True)
    rns = ServeEngine(max_batch=64, max_pending=4096,
                      modulus_bits=RNS_BITS)
    reports += _serve(rns, [("polymul-mod", RNS_N)], 64, rng,
                      kernel_check=True)
    bound_rns = rns.bound("polymul-mod", RNS_N).rns
    limbs = (bound_rns.own_basis or bound_rns).k
    _log(f"rns route: N={RNS_N} log Q={RNS_BITS} limbs={limbs}")
    _print(reports)
    missing = [r["op"] for r in reports if not r["tpu_custom_call"]]
    if missing:
        raise SystemExit(f"no Pallas kernel in the compiled program of: "
                         f"{missing}")
    return len(reports)


def four_chips(rng) -> int:
    import jax
    from repro.launch.engine import ServeEngine
    if len(jax.devices()) < 4:
        raise SystemExit(f"--four-chips needs 4 devices, JAX sees "
                         f"{len(jax.devices())}")
    engine = ServeEngine(max_batch=16, max_pending=4096, model_shards=4)
    buckets = [("polymul-real", DIST_N), ("polymul-mod", DIST_N)]
    reports = _serve(engine, buckets, 32, rng, kernel_check=False)
    for key in buckets:
        bound = engine.bound(*key)
        payloads = [bound.random_payload(rng) for _ in range(engine.max_batch)]
        out = jax.block_until_ready(bound.execute(payloads))
        devices = sorted(d.id for d in out.sharding.device_set)
        _log(f"{key[0]}/n={key[1]} route={bound.route} result sharding "
             f"device_set={devices}")
        if len(devices) != 4:
            raise SystemExit(f"{key[0]} result sits on {devices}, not on "
                             f"4 devices")
        bound.verify(payloads[0], bound.to_numpy(out)[0])
    _print(reports)
    return len(reports)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-step tiers, on four chips")
    args = ap.parse_args(argv)
    if os.environ.get("REPRO_FFT_BACKEND"):
        raise SystemExit("REPRO_FFT_BACKEND is set: it swaps the serving "
                         "path for the jnp reference; unset it")
    from repro.launch.cache import setup_compile_cache
    cache_dir = setup_compile_cache()
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"JAX backend is {backend!r}, not 'tpu': no "
                         f"accelerator, nothing to run")
    dev = jax.devices()[0]
    _log(f"device_kind={dev.device_kind} devices={len(jax.devices())} "
         f"compile cache={cache_dir}")
    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    n = four_chips(rng) if args.four_chips else one_chip(rng)
    _log(f"{n} buckets served and checked in "
         f"{time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
