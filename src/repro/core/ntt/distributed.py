"""Distributed four-step NTT across a mesh axis via shard_map — exact.

The modular counterpart of ``core.fft.distributed``: the transform length is
sharded n = n1·n2 over a mesh axis (default ``data`` — the exact tier's
sequence dimension rides the data axis, leaving ``model`` for the float
stack), with n1 = D devices:

  x viewed as M[j1, j2] (row-major, j = j1·n2 + j2), k = k1 + k2·n1
  1. all-to-all transpose: each device owns all j1 for a j2 slice
  2. local NTT_{n1} along j1 — root w^{n2} (per-shard roots via
     ``NTTParams.subparams``; q ≡ 1 mod 2n covers every sub-length)
  3. twiddle multiply by w^{j2·k1} (local, Montgomery form)
  4. all-to-all transpose: each device owns all j2 for a k1 row
  5. local NTT_{n2} along j2 — root w^{n1}
  X[k1 + k2·n1] = Z[k1, k2]; one more transpose restores natural order.

``ordered=False`` leaves the result in Z-order (k1-sharded, i.e. the
device-strided decimation X[idx::n1] lives on device idx): for polymul the
pointwise product is order-agnostic and the inverse transform consumes
Z-order directly, saving 2 all-to-alls per transform — the collective-level
analogue of the paper's §5 DFT·IDFT permutation cancellation, same as the
float path.

The local sub-transforms are a plain-jnp radix-2 Stockham sweep built on the
Pallas kernel's own Montgomery primitives (``kernels.ntt._mont_mul`` and
friends). Arithmetic mod q is exact, so distributed == local is exact
``==``, never allclose, whatever butterfly order each side runs.
Every all-to-all goes through ``dist.collectives.all_to_all``, so traced
traffic lands in the byte ledger; ``four_step_collective_stats`` is the
closed form tests pin against that ledger.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.ntt.ref import NTTParams
from repro.dist import collectives, sharding
from repro.dist.compat import shard_map
from repro.kernels import ntt as kntt

__all__ = [
    "four_step_collective_stats", "make_sharded_ntt",
    "make_sharded_ntt_polymul", "ntt_distributed",
]


def _mont_u32(params: NTTParams, values: np.ndarray) -> np.ndarray:
    return params.to_montgomery(values).astype(np.uint32)


def _master_table(params: NTTParams, base: int) -> jnp.ndarray:
    """(1, n) uint32 Montgomery-form powers of ``base``."""
    pw = params.powers(base)
    return jnp.asarray(params.to_montgomery(pw).astype(np.uint32)[None, :])


def _stockham(x, w, *, n: int, q: int, qinv: int):
    """Radix-2 Stockham sweeps of (B, n) residues, natural order in and out.

    Stage L views the row as (B, L, r) and uses the strided slice
    W[::n/(2L)][:L] of the master Montgomery table ``w`` — XLA slices with
    any stride, so this runs outside any kernel.
    """
    b = x.shape[0]
    y = x.reshape(b, 1, n)
    L, r = 1, n
    while r > 1:
        half = r // 2
        e, o = y[:, :, :half], y[:, :, half:]
        stride = n // (2 * L)
        tw = jax.lax.slice_in_dim(w, 0, L * stride, stride, axis=1)
        t = kntt._mont_mul(o, tw[:, :, None], q, qinv)
        y = jnp.concatenate([kntt._add_mod(e, t, q), kntt._sub_mod(e, t, q)],
                            axis=1)
        L, r = 2 * L, half
    return y.reshape(b, n)


def _local_ntt(x: jax.Array, sub: NTTParams, *, inverse: bool) -> jax.Array:
    """Unscaled last-axis transform of (..., m) with the sub-length roots."""
    w = _master_table(sub, sub.w_inv if inverse else sub.w)
    shp = x.shape
    y = _stockham(x.reshape(-1, shp[-1]), w, n=shp[-1], q=sub.q,
                  qinv=sub.qinv)
    return y.reshape(shp)


def _local_ntt_axis2(x: jax.Array, sub: NTTParams, *,
                     inverse: bool) -> jax.Array:
    """Same, along axis -2 (the j1/k1 axis of the (n1, n2/D) block)."""
    return jnp.swapaxes(
        _local_ntt(jnp.swapaxes(x, -1, -2), sub, inverse=inverse), -1, -2)


@functools.lru_cache(maxsize=32)
def _step3_twiddle(params: NTTParams, n1: int, inverse: bool) -> np.ndarray:
    """Full (n1, n2) Montgomery table of w^{±j2·k1}; devices dynamic-slice
    their j2 columns at trace time. Cached as NUMPY (caching jnp values
    across traces would leak tracers out of shard_map)."""
    n, n2 = params.n, params.n // n1
    pw = params.powers(params.w_inv if inverse else params.w)
    idx = np.outer(np.arange(n1), np.arange(n2)) % n
    return _mont_u32(params, pw[idx])


@functools.lru_cache(maxsize=32)
def _edge_table(params: NTTParams, kind: str) -> np.ndarray:
    """(1, n) Montgomery tables sliced by device for twist/untwist/scale:
    ``twist``  — psi^j (negacyclic input twist),
    ``untwist`` — psi^{-j} · n^{-1} (negacyclic output untwist + scale),
    ``scale``  — n^{-1} broadcast (cyclic inverse scale).
    untwist/scale values come from ``kernels.ntt.untwist_table`` — the one
    definition the local kernel and RNS limb tables also use."""
    if kind == "twist":
        vals = params.powers(params.psi)
    elif kind in ("untwist", "scale"):
        vals = kntt.untwist_table(params, negacyclic=(kind == "untwist"))
    else:
        raise ValueError(kind)
    return _mont_u32(params, vals)[None, :]


def _device_slice(table: np.ndarray, idx, width: int, axis: int) -> jax.Array:
    return jax.lax.dynamic_slice_in_dim(jnp.asarray(table), idx * width,
                                        width, axis=axis)


def ntt_distributed(x: jax.Array, params: NTTParams, *,
                    axis_name: str = "data", n_devices: int,
                    inverse: bool = False, ordered: bool = True,
                    scale: bool = True, _in_zorder: bool = False
                    ) -> jax.Array:
    """Exact NTT of (..., n) residues with the last axis sharded over
    ``axis_name``; must be called INSIDE shard_map (``x`` is the local
    (..., n/D) uint32 block). ``scale=False`` on the inverse skips the
    n^{-1} multiply so a caller can fold it into its own output pass."""
    D = n_devices
    *lead, n_loc = x.shape
    n = n_loc * D
    assert n == params.n, f"n={n} != params.n={params.n}"
    n1, n2 = D, n_loc
    p1 = params.subparams(n1)
    p2 = params.subparams(n2)
    idx = jax.lax.axis_index(axis_name)
    x = x.astype(jnp.uint32)
    la = len(lead)

    if not inverse:
        # Device idx holds row j1 = idx of M: (..., 1, n2).
        m = x.reshape(*lead, 1, n2)
        # Step 1: transpose -> all j1 for a j2 slice: (..., n1, n2/D).
        m = collectives.all_to_all(m, axis_name, split_axis=la + 1,
                                   concat_axis=la, tiled=True)
        y = _local_ntt_axis2(m, p1, inverse=False)           # NTT over j1
        tw = _device_slice(_step3_twiddle(params, n1, False), idx,
                           n2 // D, axis=1)                  # (n1, n2/D)
        y = kntt._mont_mul(y, tw, params.q, params.qinv)
        # Step 4: transpose -> all j2 for k1 row idx: (..., 1, n2).
        y = collectives.all_to_all(y, axis_name, split_axis=la,
                                   concat_axis=la + 1, tiled=True)
        z = _local_ntt(y.reshape(*lead, n2), p2, inverse=False)
        if not ordered:
            return z                         # Z-order: device idx = X[idx::n1]
        # Natural order: device d gets X[d*n_loc:(d+1)*n_loc] = Z[:, k2 slice].
        z = z.reshape(*lead, 1, n2)
        z = collectives.all_to_all(z, axis_name, split_axis=la + 1,
                                   concat_axis=la, tiled=True)  # (n1, n2/D)
        z = jnp.swapaxes(z, -1, -2)          # (..., n2/D, n1): [k2_loc, k1]
        return z.reshape(*lead, n_loc)

    # Inverse: same pipeline with inverse roots; intt = NTT_{w^-1} / n.
    if not _in_zorder:
        # Natural chunk -> Z-order row: (..., n2/D, n1) view, transpose away.
        z = x.reshape(*lead, n2 // D, n1)
        z = jnp.swapaxes(z, -1, -2)                          # (n1, n2/D)
        z = collectives.all_to_all(z, axis_name, split_axis=la,
                                   concat_axis=la + 1, tiled=True)
        z = z.reshape(*lead, n2)             # Z[k1=idx, all k2]
    else:
        z = x
    w = _local_ntt(z, p2, inverse=True)                      # over k2
    w = w.reshape(*lead, 1, n2)
    w = collectives.all_to_all(w, axis_name, split_axis=la + 1,
                               concat_axis=la, tiled=True)   # (n1, n2/D)
    tw = _device_slice(_step3_twiddle(params, n1, True), idx,
                       n2 // D, axis=1)
    w = kntt._mont_mul(w, tw, params.q, params.qinv)
    m = _local_ntt_axis2(w, p1, inverse=True)                # over k1
    m = collectives.all_to_all(m, axis_name, split_axis=la,
                               concat_axis=la + 1, tiled=True)  # (..., 1, n2)
    out = m.reshape(*lead, n_loc)            # x[j1=idx, all j2]
    if scale:
        n_inv_mont = params.n_inv * (1 << 32) % params.q
        out = kntt._mont_mul(out, jnp.uint32(n_inv_mont), params.q,
                             params.qinv)
    return out


def _seq_spec(batch_axes: Sequence[str], axis_name: str) -> P:
    return P(tuple(batch_axes) if batch_axes else None, axis_name)


def make_sharded_ntt(mesh: jax.sharding.Mesh, params: NTTParams, *,
                     axis_name: str = "data", batch_axes: Sequence[str] = (),
                     inverse: bool = False, ordered: bool = True):
    """jit-able distributed NTT over ``mesh``: (B, n) residues -> (B, n).

    The sequence axis is sharded over ``axis_name``; the returned callable
    re-asserts that placement through ``dist.sharding.constrain`` (a no-op
    outside a mesh context) before entering shard_map.
    """
    D = mesh.shape[axis_name]
    spec = _seq_spec(batch_axes, axis_name)
    fn = functools.partial(ntt_distributed, params=params,
                           axis_name=axis_name, n_devices=D,
                           inverse=inverse, ordered=ordered)
    mapped = shard_map(fn, mesh=mesh, in_specs=(spec,), out_specs=spec,
                       check_vma=False)

    def wrapped(x):
        x = sharding.constrain(x, *((None,) * (x.ndim - 1)), axis_name)
        return mapped(x)
    return wrapped


def make_sharded_ntt_polymul(mesh: jax.sharding.Mesh, params: NTTParams, *,
                             negacyclic: bool = True,
                             axis_name: str = "data",
                             batch_axes: Sequence[str] = ()):
    """Distributed exact polymul mod (x^n ± 1, q): both forward transforms
    stay in Z-order, the pointwise modmul is local, the inverse consumes
    Z-order, and the psi^{-j}·n^{-1} untwist rides the output multiply —
    6 all-to-alls instead of 9 (same cancellation as the float path)."""
    D = mesh.shape[axis_name]
    spec = _seq_spec(batch_axes, axis_name)
    n_loc = params.n // D
    q, qinv = params.q, params.qinv

    def local_fn(a, b):
        idx = jax.lax.axis_index(axis_name)
        a = a.astype(jnp.uint32)
        b = b.astype(jnp.uint32)
        if negacyclic:
            tw = _device_slice(_edge_table(params, "twist"), idx, n_loc,
                               axis=1)[0]
            a = kntt._mont_mul(a, tw, q, qinv)
            b = kntt._mont_mul(b, tw, q, qinv)
        fa = ntt_distributed(a, params, axis_name=axis_name, n_devices=D,
                             ordered=False)
        fb = ntt_distributed(b, params, axis_name=axis_name, n_devices=D,
                             ordered=False)
        r2_mont = jnp.uint32(params.r2)
        prod = kntt._mont_mul(kntt._mont_mul(fa, r2_mont, q, qinv), fb,
                              q, qinv)
        c = ntt_distributed(prod, params, axis_name=axis_name, n_devices=D,
                            inverse=True, _in_zorder=True, scale=False)
        un = _device_slice(
            _edge_table(params, "untwist" if negacyclic else "scale"), idx,
            n_loc, axis=1)[0]
        return kntt._mont_mul(c, un, q, qinv)

    mapped = shard_map(local_fn, mesh=mesh, in_specs=(spec, spec),
                       out_specs=spec, check_vma=False)

    def wrapped(a, b):
        a = sharding.constrain(a, *((None,) * (a.ndim - 1)), axis_name)
        b = sharding.constrain(b, *((None,) * (b.ndim - 1)), axis_name)
        return mapped(a, b)
    return wrapped


def four_step_collective_stats(n: int, batch: int, n_devices: int, *,
                               op: str = "ntt", ordered: bool = True,
                               itemsize: int = 4) -> dict:
    """Closed-form all-to-all traffic of one traced call, in the byte
    ledger's unit (input-block bytes per device per collective). Pinned
    against the live ledger in tests/test_dist_system.py."""
    counts = {
        ("ntt", True): 3, ("ntt", False): 2,
        ("intt", True): 3, ("intt", False): 2,
        ("polymul", True): 6, ("polymul", False): 6,
    }
    count = counts[(op, ordered)]
    per_call = batch * (n // n_devices) * itemsize
    return {"count": count, "bytes": count * per_call,
            "bytes_per_call": per_call}
