"""Distributed four-step (Bailey) FFT across a mesh axis via shard_map.

FourierPIM §7 leaves "multi-crossbar FFT" as future work: a transform whose
sequence exceeds one array. This module is that extension on the TPU mesh:
the sequence dimension is sharded across the ``model`` axis and the transform
is computed as

  n = n1 * n2,  x viewed as M[j1, j2] (row-major, j = j1*n2 + j2)
  1. all-to-all transpose so each device owns all j1 for a j2 slice
  2. local FFT_{n1} along j1                        -> Y[k1, j2]
  3. twiddle multiply by omega_n^{j2 k1}            (local)
  4. all-to-all transpose so each device owns all j2 for a k1 slice
  5. local FFT_{n2} along j2                        -> Z[k1, k2]
  X[k1 + k2*n1] = Z[k1, k2]

With ``ordered=False`` the result stays in Z-order (k1-sharded): for
convolution/polymul the pointwise product is order-agnostic as long as both
operands share the order, and the inverse transform undoes it — saving one
all-to-all per transform in each direction. This mirrors the paper's
cancellation of the FFT/IFFT input permutations across DFT.IDFT (§5), lifted
to the collective level.

Step-3 twiddles are built from integer exponents reduced mod n (exact at any
n) with the angles evaluated in float64 host-side and rounded ONCE to
complex64. An earlier revision computed ``k1 * j2`` and the device phase in
float32 inside the trace, which accumulates several float32 roundings per
twiddle (pinned at ~4e-7 vs ~4e-8 for this path by the regression test in
tests/test_dist_real.py; the end-to-end n=2^20 pin is in
tests/test_distributed_fft.py).

Real-Hermitian tier (the serving tier for real coefficients): the packed
transform Z = FFT(a + i b) runs four-step in Z-order and the Eq.-(10)
Hermitian split happens PER SHARD, before any ordering collective. The
conjugate bin n-k of a Z-order bin k = idx + D*k2 lives at k1' = (D - idx)
mod D — a single known peer — so one ppermute to the mirror device routes
every conjugate partner, and only the packed half-spectrum (half the
complex width) ever crosses the interconnect in the ordering all-to-all.
``four_step_collective_stats`` is the byte-ledger closed form; the real
tier's total traffic is 3.5/6 ~ 0.58x the complex path's (gated <= 0.6 in
benchmarks/run.py --smoke).

All collectives go through ``repro.dist.collectives`` (all_to_all/ppermute)
inside ``shard_map``, so the dry-run HLO shows real collective ops AND the
moved bytes land in the `dist.collectives` ledger the roofline accounting
reads.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.dist import batching, collectives
from repro.dist.compat import shard_map
from repro.kernels import ops as kops


def _local_fft(x: jax.Array, *, inverse: bool, backend: str | None) -> jax.Array:
    return kops.fft(x, inverse=inverse, backend=backend)


def check_four_step_shape(n: int, n_devices: int, *, real: bool = False) -> None:
    """Validate that the four-step decomposition is well formed for (n, D).

    The transposes split the local j2/k2 axis into D tiles and the step-3
    twiddle block is (D, n2/D) wide, so D^2 must divide n (2*D^2 for the
    real tier, whose ordering all-to-all moves n/(2D)-wide half-spectrum
    tiles). A non-dividing shape used to fall through to ``n2 // D``
    truncation / opaque all_to_all shape errors deep inside the trace;
    rejecting here keeps the failure loud and attributable.
    """
    D = n_devices
    if D < 1:
        raise ValueError(f"n_devices={D} must be >= 1")
    need = 2 * D * D if real else D * D
    if n % need or n < need:
        tier = "real four-step" if real else "four-step"
        raise ValueError(
            f"{tier} FFT needs {'2*' if real else ''}D^2 | n so every "
            f"all-to-all tile and twiddle slice is whole: got n={n}, "
            f"D={D} (n % {need} = {n % need})")


@functools.lru_cache(maxsize=64)
def _twiddle_tables(n: int, n1: int, width: int, inverse: bool
                    ) -> tuple[np.ndarray, np.ndarray]:
    """Host-side step-3 twiddle tables, exact-integer exponents, fp64 angles.

    ``local[k1, j2] = w_n^{+-k1*j2}`` for the local j2 slice (j2 < width)
    and ``offset[m] = w_n^{+-m*width}`` for m = k1*idx < n1^2 — the global
    j2 offset of device idx enters as ``local * offset[k1*idx]``, so every
    factor is exp of an exponent reduced mod n in int64 (never a float
    product) evaluated in float64 and rounded once to complex64. Cached as
    NUMPY: jnp values cached across traces would leak tracers.
    """
    sign = 1.0 if inverse else -1.0
    k1 = np.arange(n1, dtype=np.int64)[:, None]
    j2 = np.arange(width, dtype=np.int64)[None, :]
    local = np.exp(sign * 2j * np.pi * ((k1 * j2) % n) / n)
    m = np.arange(n1 * n1, dtype=np.int64)
    offset = np.exp(sign * 2j * np.pi * ((m * width) % n) / n)
    return local.astype(np.complex64), offset.astype(np.complex64)


def _twiddle(n: int, n1: int, width: int, idx: jax.Array,
             inverse: bool) -> jax.Array:
    """w_n^{+-k1*j2} for device ``idx``'s global j2 slice; shape (n1, width)."""
    local, offset = _twiddle_tables(n, n1, width, inverse)
    k1 = jnp.arange(n1, dtype=jnp.int32)
    phase = jnp.asarray(offset)[k1 * idx.astype(jnp.int32)]
    return jnp.asarray(local) * phase[:, None]


def fft_distributed(x: jax.Array, *, axis_name: str = "model",
                    n_devices: int, inverse: bool = False,
                    ordered: bool = True, backend: str | None = None,
                    _in_zorder: bool = False) -> jax.Array:
    """FFT of (..., n) with the last axis sharded over ``axis_name``.

    Must be called INSIDE shard_map: ``x`` is the per-device local block
    (..., n / D). n1 = D * ceil-pow2 rows, n2 = n / n1 — we pick n1 = D so
    each all-to-all moves exactly one tile per peer and local FFT lengths
    stay balanced (planner may override by reshaping beforehand).
    """
    D = n_devices
    *lead, n_loc = x.shape
    n = n_loc * D
    check_four_step_shape(n, D)
    n1, n2 = D, n_loc
    idx = jax.lax.axis_index(axis_name)
    x = x.astype(jnp.complex64)

    if not inverse:
        # Local block is M[j1 in my chunk, j2 all] = (n1/D=1 rows of j1 ... )
        # With n1 = D each device holds exactly one j1 row: (..., 1, n2).
        m = x.reshape(*lead, 1, n2)
        # Step 1: transpose -> each device owns all j1 for a j2 slice.
        m = collectives.all_to_all(m, axis_name, split_axis=len(lead) + 1,
                                   concat_axis=len(lead), tiled=True)
        # Now (..., n1, n2/D); axis -2 is full j1.
        y = _local_fft(jnp.swapaxes(m, -1, -2), inverse=False, backend=backend)
        y = jnp.swapaxes(y, -1, -2)  # (..., n1=k1, n2/D)
        # global j2 = idx * (n2/D) + local; the exact-exponent table pair
        # folds the offset in (see _twiddle_tables).
        y = y * _twiddle(n, n1, n2 // D, idx, inverse)
        # Step 4: transpose -> each device owns all j2 for a k1 slice.
        y = collectives.all_to_all(y, axis_name, split_axis=len(lead),
                                   concat_axis=len(lead) + 1, tiled=True)
        # (..., n1/D=1? no: split k1 (axis -2) across D, concat j2: (..., 1, n2))
        z = _local_fft(y.reshape(*lead, n2), inverse=False, backend=backend)
        z = z.reshape(*lead, 1, n2)
        if not ordered:
            return z.reshape(*lead, n_loc)  # Z-order: k1-sharded, k2 local
        # Step 7: Z[k1, k2] -> natural order X[k1 + k2 n1], outer digit k2
        # sharded: transpose once more.
        z = collectives.all_to_all(z, axis_name, split_axis=len(lead) + 1,
                                   concat_axis=len(lead), tiled=True)
        # (..., D, n2/D) rows k1 full? After split of k2-axis: each device has
        # Z[k1 all? ...]. Layout: (..., n1, n2/D) with j2 slice owned.
        z = jnp.swapaxes(z, -1, -2)  # (..., n2/D, n1): [k2_local, k1]
        return z.reshape(*lead, n_loc)
    else:
        # Inverse of the above; input in Z-order if _in_zorder else natural.
        if not _in_zorder:
            # natural X sharded by outer k2 chunk: (..., n2/D, n1) view
            z = x.reshape(*lead, n2 // D, n1)
            z = jnp.swapaxes(z, -1, -2)  # (..., n1, n2/D)
            z = collectives.all_to_all(z, axis_name, split_axis=len(lead),
                                       concat_axis=len(lead) + 1, tiled=True)
            # (..., 1, n2): one k1 row, all k2
            z = z.reshape(*lead, n2)
        else:
            z = x
        # Undo step 5: inverse local FFT over k2.
        y = _local_fft(z, inverse=True, backend=backend)
        y = y.reshape(*lead, 1, n2)
        # Undo step 4.
        y = collectives.all_to_all(y, axis_name, split_axis=len(lead) + 1,
                                   concat_axis=len(lead), tiled=True)
        # (..., n1, n2/D): all k1 for a j2 slice. Undo twiddle (conjugate).
        y = y * _twiddle(n, n1, n2 // D, idx, inverse=True)
        # Undo step 2: inverse local FFT over j1 (axis -2).
        m = _local_fft(jnp.swapaxes(y, -1, -2), inverse=True, backend=backend)
        m = jnp.swapaxes(m, -1, -2)
        # Undo step 1 transpose.
        m = collectives.all_to_all(m, axis_name, split_axis=len(lead),
                                   concat_axis=len(lead) + 1, tiled=True)
        return m.reshape(*lead, n_loc)


# ---------------------------------------------------------------------------
# Real-Hermitian tier: per-shard split, half-width collectives.
# ---------------------------------------------------------------------------

def _mirror_perm(n_devices: int) -> tuple[tuple[int, int], ...]:
    """The conjugate-bin route: Z-order bin k = idx + D*k2 has its mirror
    n-k at k1' = (D - idx) mod D, so every device's partner block lives on
    one peer (devices 0 and D/2 are their own mirror)."""
    return tuple((i, (n_devices - i) % n_devices) for i in range(n_devices))


def _split_even_odd(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    return x[..., 0::2, :], x[..., 1::2, :]


def _interleave_rows(a: jax.Array, b: jax.Array) -> jax.Array:
    *lead, half, w = a.shape
    return jnp.stack([a, b], axis=-2).reshape(*lead, 2 * half, w)


def _require_row_pairs(b: int, what: str) -> None:
    if b % 2:
        raise ValueError(
            f"{what} pairs rows two-for-one (z = row[2j] + i row[2j+1]); "
            f"the local batch must be even, got {b}")


def _zhalf_to_natural(p: jax.Array, axis_name: str, D: int) -> jax.Array:
    """Z-half layout (device idx owns packed bins idx + D*k2) -> natural
    contiguous chunks, via one half-width all-to-all + local transpose."""
    *lead, w = p.shape
    la = len(lead)
    p = p.reshape(*lead, 1, w)
    p = collectives.all_to_all(p, axis_name, split_axis=la + 1,
                               concat_axis=la, tiled=True)   # (..., D, w/D)
    return jnp.swapaxes(p, -1, -2).reshape(*lead, w)


def _natural_to_zhalf(p: jax.Array, axis_name: str, D: int) -> jax.Array:
    *lead, w = p.shape
    la = len(lead)
    p = jnp.swapaxes(p.reshape(*lead, w // D, D), -1, -2)    # (..., D, w/D)
    p = collectives.all_to_all(p, axis_name, split_axis=la,
                               concat_axis=la + 1, tiled=True)
    return p.reshape(*lead, w)


def rfft_distributed(x: jax.Array, *, axis_name: str = "model",
                     n_devices: int, ordered: bool = True,
                     backend: str | None = None) -> jax.Array:
    """Packed half-spectrum FFT of real rows, sequence-sharded.

    Must be called INSIDE shard_map. ``x`` is the local real block
    (..., B, n/D) with B even: rows pair two-for-one (Z = FFT(row[2j] +
    i row[2j+1])) through the Z-order four-step transform, then the
    Hermitian split (Eq. (10)) runs per shard — the conjugate bin comes
    from the mirror peer via one HALF-width ppermute — and the result is
    the packed-Nyquist half-spectrum (kernels/fft.py layout: bin 0 carries
    DC.re + i*Nyquist.re), (..., B, n/(2D)) complex64 per device.

    ``ordered=True`` finishes with the ordering all-to-all at HALF the
    complex width (device d owns packed bins [d*n/(2D), (d+1)*n/(2D)));
    ``ordered=False`` leaves the Z-half layout (bin k on device k mod D)
    for pipelines that stay in frequency space.
    """
    D = n_devices
    *lead, B, n_loc = x.shape
    # real=ordered: the half-width ordering all-to-all (2*D^2 | n) only
    # runs for ordered output; the Z-half layout needs just D^2 | n.
    check_four_step_shape(n_loc * D, D, real=ordered)
    _require_row_pairs(B, "rfft_distributed")
    nh = n_loc // 2
    idx = jax.lax.axis_index(axis_name)
    ev, od = _split_even_odd(x)
    z = ev.astype(jnp.complex64) + 1j * od.astype(jnp.complex64)
    zz = fft_distributed(z, axis_name=axis_name, n_devices=D, ordered=False,
                         backend=backend)               # Z-order (.., B/2, n/D)
    zd, zu = zz[..., :nh], zz[..., nh:]
    # Mirror route: Z_{n-k} for my kept (lower-half) bins lives in the
    # UPPER half of the mirror peer's block — half the block crosses.
    mu = collectives.ppermute(zu, axis_name, _mirror_perm(D))
    flip = jnp.flip(mu, axis=-1)
    # Device 0 wraps: its bin 0 is self-conjugate and its other mirrors sit
    # one slot off the pure reversal (k2' = n2 - k2, not n2 - 1 - k2).
    wrap = jnp.concatenate([zd[..., :1], flip[..., :-1]], axis=-1)
    zm = jnp.where(idx == 0, wrap, flip)
    a = 0.5 * (zd + jnp.conj(zm))
    b = -0.5j * (zd - jnp.conj(zm))
    # Packed-Nyquist bin 0 on device 0: X[0] and X[n/2] are both real and
    # both live here (k2 = 0 and k2 = n2/2 of the idx = 0 block).
    a0 = jnp.real(zd[..., :1]) + 1j * jnp.real(zu[..., :1])
    b0 = jnp.imag(zd[..., :1]) + 1j * jnp.imag(zu[..., :1])
    is0 = idx == 0
    a = jnp.concatenate([jnp.where(is0, a0, a[..., :1]), a[..., 1:]], axis=-1)
    b = jnp.concatenate([jnp.where(is0, b0, b[..., :1]), b[..., 1:]], axis=-1)
    p = _interleave_rows(a, b)                          # (..., B, n/(2D))
    if not ordered:
        return p
    return _zhalf_to_natural(p, axis_name, D)


def irfft_distributed(p: jax.Array, *, axis_name: str = "model",
                      n_devices: int, ordered: bool = True,
                      backend: str | None = None) -> jax.Array:
    """Inverse of ``rfft_distributed``: packed half-spectra (..., B, n/(2D))
    -> real rows (..., B, n/D).

    The full Z-order spectrum is re-mirrored per shard before the inverse
    four-step: the upper-half bins are conj(V_{n-k}) with V = A - iB, so
    ONE half-width ppermute of V routes every mirror and two spectra ride
    one inverse complex transform (Z = A + iB). ``ordered`` describes the
    INPUT layout (natural vs Z-half), matching the forward's output.
    """
    D = n_devices
    *lead, B, w = p.shape
    n_loc = 2 * w
    check_four_step_shape(n_loc * D, D, real=ordered)
    _require_row_pairs(B, "irfft_distributed")
    idx = jax.lax.axis_index(axis_name)
    p = p.astype(jnp.complex64)
    if ordered:
        p = _natural_to_zhalf(p, axis_name, D)
    pa, pb = _split_even_odd(p)
    # Unpack device 0's packed-Nyquist bin 0: A[0] = re, A[n/2] = im.
    is0 = idx == 0
    a_nyq = jnp.imag(pa[..., :1])
    b_nyq = jnp.imag(pb[..., :1])
    a0 = jnp.real(pa[..., :1]).astype(jnp.complex64)
    b0 = jnp.real(pb[..., :1]).astype(jnp.complex64)
    pa = jnp.concatenate([jnp.where(is0, a0, pa[..., :1]), pa[..., 1:]],
                         axis=-1)
    pb = jnp.concatenate([jnp.where(is0, b0, pb[..., :1]), pb[..., 1:]],
                         axis=-1)
    zd = pa + 1j * pb              # Z = A + iB at the kept (lower) bins
    v = pa - 1j * pb               # mirror carrier: Z_upper = conj(V_{n-k})
    vm = collectives.ppermute(v, axis_name, _mirror_perm(D))
    flip = jnp.conj(jnp.flip(vm, axis=-1))
    nyq = (a_nyq + 1j * b_nyq).astype(jnp.complex64)
    wrap = jnp.concatenate([nyq, flip[..., :-1]], axis=-1)
    zu = jnp.where(is0, wrap, flip)
    z = jnp.concatenate([zd, zu], axis=-1)              # Z-order full block
    out = fft_distributed(z, axis_name=axis_name, n_devices=D, inverse=True,
                          _in_zorder=True, backend=backend)
    x = _interleave_rows(jnp.real(out), jnp.imag(out))
    return x.astype(jnp.float32)


def zorder_real_product(zz: jax.Array, mirror, at_k1_zero) -> jax.Array:
    """The paired product spectrum Q = P0 + i P1 of two real products,
    from the Z-order spectra ``zz`` (..., B, w) of their packed rows
    z = a + i b: P = A*B = (Z^2 - conj(Z^2_{n-k})) / 4i, and with
    S = Z0^2 + i Z1^2, W = Z0^2 - i Z1^2, Q = (S - conj(W_{n-k})) / 4i.

    Z-order bin k = k1 + n1*k2 sits in the block of k1 at column k2; its
    mirror n - k sits in the block of (n1 - k1) mod n1, at column
    n2 - 1 - k2, or (n2 - k2) mod n2 where k1 = 0. ``mirror(w)`` returns,
    in ``w``'s layout, each block's mirror block (a ppermute across devices
    here, a row gather in ``core.fft.hbm``); ``at_k1_zero`` broadcasts to
    True over the k1 = 0 block, whose reversal wraps (bin 0 is its own
    mirror). Pairs are rows (2j, 2j+1) of the B axis; the result is
    (..., B/2, w).
    """
    z2 = zz * zz
    e, o = _split_even_odd(z2)
    s = e + 1j * o
    w = e - 1j * o
    flip = jnp.flip(mirror(w), axis=-1)
    wrap = jnp.concatenate([flip[..., -1:], flip[..., :-1]], axis=-1)
    wr = jnp.where(at_k1_zero, wrap, flip)
    return -0.25j * (s - jnp.conj(wr))


def polymul_real_distributed(a: jax.Array, b: jax.Array, *,
                             axis_name: str = "model", n_devices: int,
                             backend: str | None = None) -> jax.Array:
    """Circular product of REAL coefficient rows, sequence-sharded, with
    the paired inverse kept at the collective level.

    Per product, z = a + i b rides ONE Z-order forward transform; the
    product spectrum P = A*B = (Z^2 - conj(Z^2_{n-k})) / 4i needs only the
    mirror of Z^2, and for a PAIR of products the two mirrors travel as one
    block (W = Z0^2 - i Z1^2, so Q = P0 + i P1 = (S - conj(W_{n-k})) / 4i
    with S = Z0^2 + i Z1^2): one ppermute per pair. The shared inverse
    consumes Q in Z-order and lands both real results in natural order —
    1.5 transform-equivalents + half a permute per product, 3.5/6 ~ 0.58x
    the complex path's collective bytes (four_step_collective_stats).
    """
    D = n_devices
    if a.shape != b.shape:
        raise ValueError(f"operand shapes differ: {a.shape} vs {b.shape}")
    *lead, B, n_loc = a.shape
    check_four_step_shape(n_loc * D, D)
    _require_row_pairs(B, "polymul_real_distributed")
    idx = jax.lax.axis_index(axis_name)
    z = a.astype(jnp.complex64) + 1j * b.astype(jnp.complex64)
    zz = fft_distributed(z, axis_name=axis_name, n_devices=D, ordered=False,
                         backend=backend)
    q = zorder_real_product(
        zz, lambda w: collectives.ppermute(w, axis_name, _mirror_perm(D)),
        idx == 0)
    c = fft_distributed(q, axis_name=axis_name, n_devices=D, inverse=True,
                        _in_zorder=True, backend=backend)
    out = _interleave_rows(jnp.real(c), jnp.imag(c))
    return out.astype(jnp.float32)


# ---------------------------------------------------------------------------
# shard_map builders
# ---------------------------------------------------------------------------

def _seq_spec(batch_axes: Sequence[str], axis_name: str) -> P:
    return P(tuple(batch_axes) if batch_axes else None, axis_name)


def _checked_shard_map(fn, mesh, *, axis_name, batch_axes, n_args,
                       n_from, real: bool = False, pad_pairs: bool = False):
    """shard_map ``fn`` over the sequence spec and wrap it with the global
    shape guard — the one place the call-time ``check_four_step_shape``
    lives for every make_sharded_* builder. ``n_from`` maps the first
    argument to the GLOBAL transform length.

    ``pad_pairs=True`` (the row-pairing real tiers) accepts ODD batches:
    the tail row gets a zeros partner appended before the shard_map and the
    extra row is sliced off the result. The Eq.-(10) split/pair algebra is
    linear, so pairing a real row with zeros recovers that row's spectrum /
    product exactly — the pad changes no served value. (The pad is applied
    to the GLOBAL batch; when the batch axis is itself sharded over
    ``batch_axes``, callers must keep the padded batch divisible as usual.)
    """
    D = mesh.shape[axis_name]
    spec = _seq_spec(batch_axes, axis_name)
    mapped = shard_map(fn, mesh=mesh, in_specs=(spec,) * n_args,
                       out_specs=spec, check_vma=False)

    def wrapped(*args):
        check_four_step_shape(n_from(args[0]), D, real=real)
        if pad_pairs and args[0].ndim >= 2 and args[0].shape[-2] % 2:
            b = args[0].shape[-2]
            pads = [(0, 0)] * (args[0].ndim - 2) + [(0, 1), (0, 0)]
            args = tuple(jnp.pad(a, pads) for a in args)
            return mapped(*args)[..., :b, :]
        return mapped(*args)
    return wrapped


def make_sharded_fft(mesh: jax.sharding.Mesh, *, axis_name: str = "model",
                     batch_axes: Sequence[str] = ("data",),
                     inverse: bool = False, ordered: bool = True,
                     backend: str | None = None):
    """Build a jit-able distributed FFT over ``mesh``: (B, n) -> (B, n).

    Batch is sharded over ``batch_axes``; the transform dimension over
    ``axis_name``. Raises ValueError at call time when D^2 does not divide
    the global n (see ``check_four_step_shape``).
    """
    D = mesh.shape[axis_name]
    fn = functools.partial(fft_distributed, axis_name=axis_name, n_devices=D,
                           inverse=inverse, ordered=ordered, backend=backend)
    return _checked_shard_map(fn, mesh, axis_name=axis_name,
                              batch_axes=batch_axes, n_args=1,
                              n_from=lambda x: x.shape[-1])


def make_sharded_polymul(mesh: jax.sharding.Mesh, *, axis_name: str = "model",
                         batch_axes: Sequence[str] = ("data",),
                         backend: str | None = None):
    """Distributed circular polymul: both transforms stay in Z-order, the
    pointwise product is local, and the final inverse restores natural order.
    Saves 2 all-to-alls per call vs. composing ordered transforms."""
    D = mesh.shape[axis_name]

    def local_fn(a, b):
        fa = fft_distributed(a, axis_name=axis_name, n_devices=D,
                             ordered=False, backend=backend)
        fb = fft_distributed(b, axis_name=axis_name, n_devices=D,
                             ordered=False, backend=backend)
        prod = fa * fb
        return fft_distributed(prod, axis_name=axis_name, n_devices=D,
                               inverse=True, _in_zorder=True, backend=backend)

    return _checked_shard_map(local_fn, mesh, axis_name=axis_name,
                              batch_axes=batch_axes, n_args=2,
                              n_from=lambda a: a.shape[-1])


def make_sharded_rfft(mesh: jax.sharding.Mesh, *, axis_name: str = "model",
                      batch_axes: Sequence[str] = ("data",),
                      ordered: bool = True, backend: str | None = None):
    """jit-able distributed rfft: real (B, n) -> packed complex (B, n/2).

    Rows pair two-for-one per device; an ODD global batch is padded with a
    zeros partner internally and sliced off the result (``pad_pairs``), so
    any B >= 1 serves. ``batch_axes`` shards should keep pairs together —
    the default contiguous-block data sharding does.
    """
    D = mesh.shape[axis_name]
    fn = functools.partial(rfft_distributed, axis_name=axis_name,
                           n_devices=D, ordered=ordered, backend=backend)
    return _checked_shard_map(fn, mesh, axis_name=axis_name,
                              batch_axes=batch_axes, n_args=1,
                              n_from=lambda x: x.shape[-1], real=ordered,
                              pad_pairs=True)


def make_sharded_irfft(mesh: jax.sharding.Mesh, *, axis_name: str = "model",
                       batch_axes: Sequence[str] = ("data",),
                       ordered: bool = True, backend: str | None = None):
    """jit-able inverse: packed complex (B, n/2) -> real (B, n); odd
    batches pad a zeros half-spectrum internally (see ``pad_pairs``)."""
    D = mesh.shape[axis_name]
    fn = functools.partial(irfft_distributed, axis_name=axis_name,
                           n_devices=D, ordered=ordered, backend=backend)
    return _checked_shard_map(fn, mesh, axis_name=axis_name,
                              batch_axes=batch_axes, n_args=1,
                              n_from=lambda p: 2 * p.shape[-1], real=ordered,
                              pad_pairs=True)


def make_sharded_polymul_real(mesh: jax.sharding.Mesh, *,
                              axis_name: str = "model",
                              batch_axes: Sequence[str] = ("data",),
                              backend: str | None = None):
    """Distributed real circular polymul with the collective-level paired
    inverse (see ``polymul_real_distributed``). ODD global batches are
    accepted: the tail product pairs with a zeros product internally and
    the pad row is sliced off the result (``pad_pairs``) — the serve tier
    no longer needs an even --batch."""
    D = mesh.shape[axis_name]
    fn = functools.partial(polymul_real_distributed, axis_name=axis_name,
                           n_devices=D, backend=backend)
    return _checked_shard_map(fn, mesh, axis_name=axis_name,
                              batch_axes=batch_axes, n_args=2,
                              n_from=lambda a: a.shape[-1], pad_pairs=True)


# ---------------------------------------------------------------------------
# Collective-traffic closed forms (ledger units)
# ---------------------------------------------------------------------------

def four_step_collective_stats(n: int, batch: int, n_devices: int, *,
                               op: str = "fft", ordered: bool = True,
                               itemsize: int = 8) -> dict:
    """Closed-form collective traffic of one traced call, in the byte
    ledger's unit (local-block bytes per collective, complex64 items).
    Pinned against the live ``dist.collectives`` ledger in
    tests/test_dist_real.py and benchmarks/run.py --smoke.

    ``batch`` counts REAL rows for rfft/irfft (pairs ride one transform)
    and products for polymul ops. The real tier's total is 3.5 block-units
    against the complex path's 6 per product (0.583x — the <= 0.6 gate).
    """
    blk = batch * (n // n_devices) * itemsize          # one full-width call
    if op in ("fft", "ifft"):
        a2a, a2a_bytes, pp, pp_bytes = (3 if ordered else 2), 0, 0, 0
        a2a_bytes = a2a * blk
    elif op == "polymul":
        a2a, a2a_bytes, pp, pp_bytes = 6, 6 * blk, 0, 0
    elif op in ("rfft", "irfft"):
        if batch % 2:
            raise ValueError(f"{op} batch must be even, got {batch}")
        half = blk // 2                                # the packed pair block
        # forward/inverse four-step on B/2 packed rows: 2 calls of `half`;
        # the (un)ordering all-to-all moves B packed half-spectra = `half`.
        a2a = 3 if ordered else 2
        a2a_bytes = a2a * half
        pp, pp_bytes = 1, half // 2                    # half-width mirror
    elif op == "polymul_real":
        if batch % 2:
            raise ValueError(f"polymul_real batch must be even, got {batch}")
        # 2 forward calls at full batch + 2 inverse calls at half batch.
        a2a, a2a_bytes = 4, 2 * blk + 2 * (blk // 2)
        pp, pp_bytes = 1, blk // 2                     # one W block per pair
    else:
        raise ValueError(f"unknown op {op!r}")
    return {"a2a_count": a2a, "a2a_bytes": a2a_bytes,
            "ppermute_count": pp, "ppermute_bytes": pp_bytes,
            "total_bytes": a2a_bytes + pp_bytes}


def batch_plan(mesh: jax.sharding.Mesh, batch: int, *,
               batch_axes: Sequence[str] = ("pod", "data"),
               transforms_per_device: int = 1) -> batching.CrossbarBatchPlan:
    """Map B transforms onto the mesh's batch axes (paper-§6 batching lifted
    to the pod level): per-device share, wave count, and the utilization the
    tail wave / mesh padding costs. ``transforms_per_device`` is how many
    transforms one device runs concurrently (1 for the XLA path)."""
    return batching.plan_crossbar_batch(
        batch, num_arrays=transforms_per_device, mesh=mesh,
        axes=tuple(batch_axes))
