"""Host cost of a ring product mod Q on the RNS route, per working basis.

    python benchmarks/rns_host_bench.py [--n 8192] [--batch 16] [--bits 210]
                                        [--runs 3] [--seed 0]

``rns_polymul`` spends nearly all of a batch on the host: ``to_rns`` of
both operands (one big-integer ``%`` per limb) and ``crt_to_modulus``
(Garner digits, then object-dtype recombination). This times both halves
at a (batch, n) shape, by default the he128 cell's (16 x 8192, 210-bit Q),
on the two bases ``core/ntt/rns.py`` describes:

- ``extended``: the full limb set with M > 2 n Q^2 and the centered lift;
- ``own``: Q's own primes (``RNSParams.own_basis``), no lift.

The per-limb products come from the numpy NTT reference (not timed), and
the script checks that both bases give the same coefficients mod Q. It
runs on the host alone and needs no accelerator; it is a pre-check of the
route's host cost, not a yardstick. The last line of output is one JSON
object with every run.
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

from repro.core.ntt.ref import negacyclic_polymul  # noqa: E402
from repro.core.ntt.rns import (RNSParams, crt_to_modulus,  # noqa: E402
                                random_poly, to_rns)


def _ms(fn):
    t0 = time.perf_counter()
    out = fn()
    return (time.perf_counter() - t0) * 1e3, out


def run_basis(a, b, basis: RNSParams) -> tuple[dict, np.ndarray]:
    """Split both operands, multiply per limb (untimed), reconstruct."""
    split_ms, (ar, br) = _ms(lambda: (to_rns(a, basis), to_rns(b, basis)))
    prods = np.stack([negacyclic_polymul(ar[i], br[i], p)
                      for i, p in enumerate(basis.limbs)])
    crt_ms, out = _ms(lambda: crt_to_modulus(prods, basis))
    return {"split_ms": split_ms, "crt_ms": crt_ms}, out


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=8192)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--bits", type=int, default=210)
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    rns = RNSParams.make(args.n, modulus_bits=args.bits)
    bases = {"extended": rns}
    if rns.own_basis is not None:
        bases["own"] = rns.own_basis
    rng = np.random.default_rng(args.seed)
    a, b = (np.stack([random_poly(rng, args.n, rns.modulus)
                      for _ in range(args.batch)]) for _ in range(2))
    runs = {name: [] for name in bases}
    equal = True
    for _ in range(args.runs):
        outs = {}
        for name, basis in bases.items():
            t, outs[name] = run_basis(a, b, basis)
            runs[name].append(t)
            print(f"{name:8s} k={basis.k:2d} split {t['split_ms']:9.1f} ms"
                  f"  crt {t['crt_ms']:9.1f} ms", flush=True)
        if "own" in outs:
            equal &= bool((outs["own"] == outs["extended"]).all())
    print(f"own == extended: {equal}")
    summary = {
        "n": args.n, "batch": args.batch,
        "q_bits": rns.modulus.bit_length(),
        "limbs": {name: basis.k for name, basis in bases.items()},
        "equal": equal, "runs": runs,
        "median_ms": {name: {k: statistics.median(r[k] for r in rs)
                             for k in ("split_ms", "crt_ms")}
                      for name, rs in runs.items()},
    }
    print(json.dumps(summary))
    if not equal:
        raise SystemExit("own basis and extended basis differ")
    return summary


if __name__ == "__main__":
    main()
