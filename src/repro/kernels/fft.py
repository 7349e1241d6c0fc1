"""Pallas TPU kernels: batched FFT, rfft and irfft, each row resident in VMEM.

This is the TPU adaptation of FourierPIM's in-memory FFT (paper §4):

* The paper keeps the whole transform inside a memristive crossbar so no data
  ever moves to a compute unit. Here the whole transform stays inside VMEM:
  one HBM read of the input block, every stage on VMEM values, one HBM
  write. An XLA/cuFFT-style implementation round-trips HBM once per fused
  stage group; for the memory-bound FFT regime (Fig. 1 of the paper) this
  single-residency property is the win.
* The transform is a four-step DFT whose two small DFTs are matrix products
  on the MXU (docs/fourier.md, "Kernel layout"). A row of length n is viewed
  as an (R, C) matrix x[r, c] = x[r*C + c] with C = 2^ceil(log2(n)/2) >= R,
  so both dimensions are lane/sublane dense at n >= 16384 and no stage ever
  shrinks the lane axis:

      X[k1 + R*k2] = sum_c F_C[k2, c] * T[k1, c] * sum_r F_R[k1, r] x[r, c]

  with F_m the m-point DFT matrix and T[k1, c] = W_n^{k1*c}. The last
  product contracts lanes against lanes (F_C @ W^T), so the spectrum comes
  out as a (C, R) matrix whose row-major order IS natural frequency order:
  the digit reversal every natural-order FFT needs is folded into the matmul,
  with no transpose, gather or lane shuffle anywhere.
* The paper's element-parallel butterfly (§4.2, one butterfly per crossbar
  row) maps to the batch: the grid tiles the batch, and a loop inside the
  kernel runs one row's matrix products at a time, so the VMEM working set
  of the stages does not grow with the block.

Complex data is carried as split real/imag planes (SoA): a memristor row can
concatenate bit-fields at zero cost, a VPU cannot.

Every matrix entry is an entry of the one master table ``twiddle_table``
(computed in float64, rounded once to fp32), gathered on the host and cached
as numpy: the kernels never slice the table with a stride.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def resolve_interpret(interpret: bool | None) -> bool:
    """The one rule for a Pallas entry point's ``interpret`` argument.

    ``None`` (every public default) compiles the kernel when JAX's default
    backend is a TPU and runs the Pallas interpreter anywhere else. An
    explicit bool wins: tests pass ``False`` to compile for a described
    chip from a CPU host.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _log2(n: int) -> int:
    assert n & (n - 1) == 0 and n > 0, f"n={n} must be a power of two"
    return n.bit_length() - 1


def split(n: int) -> tuple[int, int]:
    """(R, C) view of a length-n row: n = R*C, C = 2^ceil(log2(n)/2) >= R.

    Element j of the row sits at (j // C, j % C). The NTT kernels use the
    same view, so one VMEM accounting serves both.
    """
    c = 1 << ((_log2(n) + 1) // 2)
    return n // c, c


def twiddle_table(n: int, *, inverse: bool = False) -> tuple[np.ndarray, np.ndarray]:
    """Master twiddle table W[k] = exp(sign * 2 pi i k / n), k < n, fp32 planes.

    Every DFT matrix and twiddle the kernels use is gathered from this one
    (1, n) pair (``dft_tables``). Computed in float64 then rounded once to
    fp32.
    """
    k = np.arange(n, dtype=np.float64)
    sign = 1.0 if inverse else -1.0
    ang = sign * 2.0 * np.pi * k / n
    return (np.cos(ang).astype(np.float32)[None, :],
            np.sin(ang).astype(np.float32)[None, :])


@functools.lru_cache(maxsize=64)
def dft_tables(n: int, inverse: bool = False) -> tuple[np.ndarray, ...]:
    """Four-step tables for a length-n row as (R, C), real/imag fp32 planes:
    F_R (R, R), F_C (C, C) and T (R, C).

    F_R[k, r] = W[(k*r mod R) * C], F_C[k, c] = W[(k*c mod C) * R] and
    T[k, c] = W[k*c], with W = ``twiddle_table(n, inverse=inverse)``: every
    entry is bit-equal to a master-table entry. Cached as numpy (jnp values
    cached across traces would leak tracers).
    """
    r_, c_ = split(n)
    wr, wi = (t[0] for t in twiddle_table(n, inverse=inverse))
    kr, kc = np.arange(r_), np.arange(c_)
    idx_r = np.outer(kr, kr) % r_ * c_
    idx_c = np.outer(kc, kc) % c_ * r_
    idx_t = np.outer(kr, kc)
    return wr[idx_r], wi[idx_r], wr[idx_c], wi[idx_c], wr[idx_t], wi[idx_t]


@functools.lru_cache(maxsize=64)
def row_tables(n: int, inverse: bool = False, half: bool = False
               ) -> tuple[np.ndarray, ...]:
    """Tables for ``four_step`` (row (R, C) -> spectrum (C, R)); ``half``
    keeps the first C/2 rows of F_C: the half spectrum of a real row."""
    frr, fri, fcr, fci, tr, ti = dft_tables(n, inverse)
    if half:
        h = split(n)[1] // 2
        fcr, fci = fcr[:h], fci[:h]
    return frr, fri, fcr, fci, tr, ti


@functools.lru_cache(maxsize=64)
def spectrum_tables(n: int, inverse: bool = True, half: bool = False
                    ) -> tuple[np.ndarray, ...]:
    """Tables for ``four_step_back`` (spectrum (C, R) -> row (R, C)): F_R,
    F_C (its first C/2 columns with ``half``) and T transposed to (C, R)."""
    frr, fri, fcr, fci, tr, ti = dft_tables(n, inverse)
    if half:
        h = split(n)[1] // 2
        fcr, fci = fcr[:, :h], fci[:, :h]
    return (frr, fri, np.ascontiguousarray(fcr), np.ascontiguousarray(fci),
            np.ascontiguousarray(tr.T), np.ascontiguousarray(ti.T))


@functools.lru_cache(maxsize=64)
def alternating_row(n: int) -> np.ndarray:
    """(1, C) fp32 row of (-1)^c: (-1)^j for every j of an (R, C) row, since
    C is even. Carries the real Nyquist bin X[n/2] = sum_j (-1)^j x_j."""
    return np.where(np.arange(split(n)[1]) % 2, -1.0, 1.0).astype(
        np.float32)[None, :]


# ---------------------------------------------------------------------------
# Per-row math on VMEM values (plain jnp: the same code runs compiled, in the
# interpreter, and outside any kernel)
# ---------------------------------------------------------------------------

_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))          # contract last axes: a @ b.T


def _mm(a, b):
    return jnp.dot(a, b, precision=_HIGHEST,
                   preferred_element_type=jnp.float32)


def _mm_nt(a, b):
    return jax.lax.dot_general(a, b, _NT, precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _cmul(ar, ai, br, bi):
    return ar * br - ai * bi, ar * bi + ai * br


def _cmatmul(mm, ar, ai, br, bi):
    """Complex matrix product of split planes through the real product mm."""
    return mm(ar, br) - mm(ai, bi), mm(ar, bi) + mm(ai, br)


def four_step(xr, xi, tables):
    """DFT of one row: (R, C) planes -> spectrum planes (C', R) in natural
    order (row-major index k2*R + k1), C' = rows of the F_C table.

    ``xi=None`` is a real row: the first product needs two real matmuls
    instead of four.
    """
    frr, fri, fcr, fci, tr, ti = tables
    if xi is None:
        yr, yi = _mm(frr, xr), _mm(fri, xr)
    else:
        yr, yi = _cmatmul(_mm, frr, fri, xr, xi)
    wr, wi = _cmul(yr, yi, tr, ti)
    return _cmatmul(_mm_nt, fcr, fci, wr, wi)


def four_step_back(pr, pi, tables, *, real: bool = False):
    """Unscaled inverse layout of ``four_step``: spectrum planes (C', R) ->
    row planes (R, C). ``real=True`` returns only the real plane (and None):
    the last product then needs two real matmuls instead of four."""
    frr, fri, fcr, fci, tr, ti = tables
    vr, vi = _cmatmul(_mm, fcr, fci, pr, pi)
    wr, wi = _cmul(vr, vi, tr, ti)
    if real:
        return _mm_nt(frr, wr) - _mm_nt(fri, wi), None
    return _cmatmul(_mm_nt, frr, fri, wr, wi)


def _origin(shape):
    """Mask of element [0, 0] of a 2-D plane."""
    return ((jax.lax.broadcasted_iota(jnp.int32, shape, 0) == 0)
            & (jax.lax.broadcasted_iota(jnp.int32, shape, 1) == 0))


def rfft_row(x, tables, alt):
    """Packed half spectrum of one real (R, C) row, as (C/2, R) planes:
    P[k] = X[k] for 1 <= k < n/2, P[0] = X[0].re + i * X[n/2].re.

    Only the first C/2 rows of F_C are applied, so the real transform does
    half the matrix work of the complex one; the Nyquist bin is the
    alternating sum of the row.
    """
    pr, pi = four_step(x, None, tables)
    nyq = jnp.sum(jnp.sum(x * alt, axis=0, keepdims=True), axis=1,
                  keepdims=True)
    return pr, jnp.where(_origin(pi.shape), nyq, pi)


def irfft_row(pr, pi, tables, alt, n: int):
    """Real (R, C) row from its packed half spectrum (C/2, R): Hermitian
    symmetry folds bins n/2 < k < n onto 2*Re of bins 0 < k < n/2."""
    at0 = _origin(pr.shape)
    nyq = pi[0:1, 0:1]
    hr = jnp.where(at0, pr, 2.0 * pr)
    hi = jnp.where(at0, 0.0, 2.0 * pi)
    x, _ = four_step_back(hr, hi, tables, real=True)
    return (x + nyq * alt) * (1.0 / n)


# ---------------------------------------------------------------------------
# VMEM planning: pick the batch block so the working set fits.
# ---------------------------------------------------------------------------

# v5e has 128 MiB of VMEM per core; a kernel's pipelined blocks and stack
# live in a scoped part of it. Plans stay within 16 MiB: at n = 2^18 the
# compiler refuses the float kernels even at one row (tests/test_tpu_compile.py
# compiles every kernel at the planner's ceilings).
VMEM_BUDGET_BYTES = 16 * 1024 * 1024
_TEMP_PLANES = 16                      # live (R, C) values in one row's body


def _ceil(x: int, m: int) -> int:
    return -(-x // m) * m


def _tile_bytes(rows: int, cols: int) -> int:
    """Bytes of a 32-bit (rows, cols) array as VMEM holds it: (8, 128)
    tiles, so short sublane or lane extents are padded."""
    return _ceil(rows, 8) * _ceil(cols, 128) * 4


def _plane_bytes(n: int) -> int:
    """One 32-bit (R, C) row plane in VMEM."""
    return _tile_bytes(*split(n))


def _fixed_bytes(n: int) -> int:
    """Batch-independent VMEM of the heaviest kernel of either family:
    double-buffered constant tables plus one row's stage temporaries.

    Float polymul holds forward and inverse F_R, F_C, T planes; the fused
    NTT polymul holds forward and inverse stage tables (one (R, C) plane per
    stage with distance >= C, one (1, C) row per stage below) plus the twist
    and untwist planes.
    """
    r_, c_ = split(n)
    plane = _plane_bytes(n)
    dft = 2 * 2 * (_tile_bytes(r_, r_) + _tile_bytes(c_, c_) + plane)
    ntt = 2 * (_log2(r_) * plane + _log2(c_) * _tile_bytes(1, c_)) + 2 * plane
    return 2 * max(dft, ntt) + _TEMP_PLANES * plane


def block_vmem_bytes(n: int, blk: int, *, real: bool = False) -> int:
    """VMEM of a kernel launch at batch block ``blk``: the fixed part plus
    the double-buffered input and output blocks — two planes in and two out
    per complex row, one in and one out (two half planes) per real row."""
    planes = 1 if real else 2
    return _fixed_bytes(n) + blk * planes * 2 * 2 * _plane_bytes(n)


def plan_batch_block(n: int, max_block: int = 1024, *,
                     real: bool = False) -> int:
    """Largest power-of-two batch block whose launch fits VMEM_BUDGET_BYTES
    (``block_vmem_bytes``), at least 1.

    ``real=True`` plans real rows (rfft/irfft/real polymul): half the
    planes per row, so both the VMEM-derived block and its cap double
    (paper Eq. (10): area halves, batch doubles). Kernels with more operand
    planes per row (the fused polymuls) plan with half this block.
    """
    if real:
        max_block *= 2
    per_row = block_vmem_bytes(n, 1, real=real) - _fixed_bytes(n)
    blk = (VMEM_BUDGET_BYTES - _fixed_bytes(n)) // per_row
    blk = max(1, min(max_block, blk))
    return 1 << (blk.bit_length() - 1)


def _fit_block(blk: int, batch: int) -> int:
    """Shrink a planned batch block to the actual batch (next power of two
    >= batch) so small batches don't zero-pad to the full VMEM block — the
    planned block is a CAP from the VMEM budget, not a minimum."""
    cap = 1 << max(0, batch - 1).bit_length()
    return min(blk, max(1, cap))


# ---------------------------------------------------------------------------
# Launch plumbing shared by the float and NTT kernels
# ---------------------------------------------------------------------------

def pad_rows(xs, blk: int):
    """Zero-pad each (B, ...) operand to a multiple of ``blk`` rows."""
    pad = (-xs[0].shape[0]) % blk
    if not pad:
        return list(xs)
    return [jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) for x in xs]


def _const_spec(shape):
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


def _rows_spec(shape, blk: int):
    return pl.BlockSpec((blk,) + tuple(shape[1:]),
                        lambda i: (i,) + (0,) * (len(shape) - 1))


def batched_call(kernel, tables, operands, out_shapes, *, blk: int,
                 interpret: bool):
    """One pallas_call over the batch: ``operands`` are (B, R', C') row
    stacks tiled ``blk`` rows per grid step, ``tables`` whole constant
    arrays (the same block every step)."""
    tables = [jnp.asarray(t) for t in tables]
    return pl.pallas_call(
        kernel,
        grid=(operands[0].shape[0] // blk,),
        in_specs=([_const_spec(t.shape) for t in tables]
                  + [_rows_spec(x.shape, blk) for x in operands]),
        out_specs=[_rows_spec(s.shape, blk) for s in out_shapes],
        out_shape=out_shapes,
        interpret=interpret,
    )(*tables, *operands)


def for_each_row(refs, body) -> None:
    """Run ``body(b)`` for every row of the block (the leading axis of the
    first ref): one row's temporaries live at a time."""
    def step(b, carry):
        body(b)
        return carry
    jax.lax.fori_loop(0, refs[0].shape[0], step, 0)


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

def _fft_kernel(*refs, scale: float | None):
    *tabs, xr_ref, xi_ref, or_ref, oi_ref = refs

    def row(b):
        t = [r[...] for r in tabs]
        yr, yi = four_step(xr_ref[b].astype(jnp.float32),
                           xi_ref[b].astype(jnp.float32), t)
        if scale is not None:
            yr, yi = yr * scale, yi * scale
        or_ref[b] = yr.astype(or_ref.dtype)
        oi_ref[b] = yi.astype(oi_ref.dtype)
    for_each_row([xr_ref], row)


def _rfft_kernel(*refs):
    *tabs, alt_ref, x_ref, or_ref, oi_ref = refs

    def row(b):
        pr, pi = rfft_row(x_ref[b].astype(jnp.float32),
                          [r[...] for r in tabs], alt_ref[...])
        or_ref[b] = pr.astype(or_ref.dtype)
        oi_ref[b] = pi.astype(oi_ref.dtype)
    for_each_row([x_ref], row)


def _irfft_kernel(*refs, n: int):
    *tabs, alt_ref, pr_ref, pi_ref, o_ref = refs

    def row(b):
        x = irfft_row(pr_ref[b].astype(jnp.float32),
                      pi_ref[b].astype(jnp.float32),
                      [r[...] for r in tabs], alt_ref[...], n)
        o_ref[b] = x.astype(o_ref.dtype)
    for_each_row([pr_ref], row)


@functools.partial(jax.jit, static_argnames=("interpret", "block_b"))
def rfft_planes(x: jax.Array, *, interpret: bool | None = None,
                block_b: int | None = None) -> tuple[jax.Array, jax.Array]:
    """Packed half-spectrum FFT of real rows: (B, n) -> planes (B, n//2).

    Layout of the half spectrum: P[0] = X[0].re + i X[n/2].re, P[k] = X[k]
    for 0 < k < n/2. Grid/tiling contract matches ``fft_planes`` with the
    real-mode batch block; the batch is zero-padded to the block.
    """
    assert x.ndim == 2, f"expected (batch, n), got {x.shape}"
    b, n = x.shape
    assert n >= 2 and n & (n - 1) == 0, f"n={n} must be a power of two >= 2"
    interpret = resolve_interpret(interpret)
    r_, c_ = split(n)
    blk = block_b or _fit_block(plan_batch_block(n, real=True), b)
    (xp,) = pad_rows([x.reshape(b, r_, c_)], blk)
    out = jax.ShapeDtypeStruct((xp.shape[0], c_ // 2, r_), x.dtype)
    yr, yi = batched_call(
        _rfft_kernel, [*row_tables(n, half=True), alternating_row(n)],
        [xp], [out, out], blk=blk, interpret=interpret)
    return yr[:b].reshape(b, n // 2), yi[:b].reshape(b, n // 2)


@functools.partial(jax.jit, static_argnames=("interpret", "block_b"))
def irfft_planes(xr: jax.Array, xi: jax.Array, *,
                 interpret: bool | None = None,
                 block_b: int | None = None) -> jax.Array:
    """Inverse of ``rfft_planes``: packed planes (B, n//2) -> real (B, n)."""
    assert xr.shape == xi.shape and xr.ndim == 2, (xr.shape, xi.shape)
    b, nh = xr.shape
    n = 2 * nh
    assert n & (n - 1) == 0, f"n={n} must be a power of two"
    interpret = resolve_interpret(interpret)
    r_, c_ = split(n)
    blk = block_b or _fit_block(plan_batch_block(n, real=True), b)
    ops = pad_rows([v.reshape(b, c_ // 2, r_) for v in (xr, xi)], blk)
    out = jax.ShapeDtypeStruct((ops[0].shape[0], r_, c_), xr.dtype)
    (y,) = batched_call(
        functools.partial(_irfft_kernel, n=n),
        [*spectrum_tables(n, half=True), alternating_row(n)],
        ops, [out], blk=blk, interpret=interpret)
    return y[:b].reshape(b, n)


@functools.partial(jax.jit, static_argnames=("inverse", "interpret", "block_b"))
def fft_planes(xr: jax.Array, xi: jax.Array, *, inverse: bool = False,
               interpret: bool | None = None,
               block_b: int | None = None) -> tuple[jax.Array, jax.Array]:
    """Batched FFT on split planes. xr, xi: (B, n). Returns planes (B, n).

    The pallas_call tiles the batch: grid=(B/B_blk,), each program
    transforms its (B_blk, R, C) block entirely in VMEM (one HBM read + one
    HBM write). The inverse uses the conjugate tables and scales by 1/n.
    """
    assert xr.shape == xi.shape and xr.ndim == 2
    b, n = xr.shape
    interpret = resolve_interpret(interpret)
    r_, c_ = split(n)
    blk = block_b or _fit_block(plan_batch_block(n), b)
    ops = pad_rows([v.reshape(b, r_, c_) for v in (xr, xi)], blk)
    out = jax.ShapeDtypeStruct((ops[0].shape[0], c_, r_), xr.dtype)
    yr, yi = batched_call(
        functools.partial(_fft_kernel, scale=1.0 / n if inverse else None),
        row_tables(n, inverse), ops, [out, out], blk=blk,
        interpret=interpret)
    return yr[:b].reshape(b, n), yi[:b].reshape(b, n)
