"""Pallas TPU kernel: fused polynomial multiplication via the convolution
theorem (paper §5), one VMEM residency for the entire FFT -> product -> IFFT.

Paper correspondences:

* Eq. (9): C = IDFT(DFT(a) . DFT(b)) — the kernel computes both forward
  transforms, the pointwise product, and the inverse transform without ever
  leaving VMEM. cuFFT (and an unfused XLA graph) pays 3x the HBM traffic
  (two FFTs, a pointwise pass, an IFFT each round-trip memory); the paper
  makes the same observation about the GPU's element-wise multiply being
  memory-bound (§6, last paragraph) — fusion is the TPU-native counterpart.
* Input-permutation cancellation: the paper skips the FFT/IFFT bit-reversal
  permutations because they cancel across DFT.IDFT. The four-step kernels
  (kernels/fft.py) have no permutation to begin with: the forward transform
  leaves the spectrum as a (C, R) matrix and the inverse consumes exactly
  that layout.
* Real coefficients (paper Eq. (10)): the spectrum of a real row is
  Hermitian, so only its first half is computed and multiplied, and the
  inverse folds the mirror half back as 2*Re — half the matrix work of the
  complex path per product.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fft import (_cmul, _fit_block, _origin, alternating_row,
                               batched_call, for_each_row, four_step,
                               four_step_back, irfft_row, pad_rows,
                               plan_batch_block, resolve_interpret,
                               rfft_row, row_tables, spectrum_tables, split)


def _polymul_complex_kernel(*refs, n: int):
    *tabs, ar_ref, ai_ref, br_ref, bi_ref, cr_ref, ci_ref = refs
    fwd, back = tabs[:6], tabs[6:]

    def row(b):
        tf = [r[...] for r in fwd]
        far, fai = four_step(ar_ref[b].astype(jnp.float32),
                             ai_ref[b].astype(jnp.float32), tf)
        fbr, fbi = four_step(br_ref[b].astype(jnp.float32),
                             bi_ref[b].astype(jnp.float32), tf)
        pr, pi = _cmul(far, fai, fbr, fbi)
        cr, ci = four_step_back(pr, pi, [r[...] for r in back])
        inv = 1.0 / n
        cr_ref[b] = (cr * inv).astype(cr_ref.dtype)
        ci_ref[b] = (ci * inv).astype(ci_ref.dtype)
    for_each_row([ar_ref], row)


def _polymul_real_kernel(*refs, n: int):
    """Real-coefficient polymul: two half-spectrum forward transforms, the
    product of the packed half spectra, one real inverse.

    In the packed layout P[0] holds two real bins (DC and Nyquist), so its
    product is component-wise; every other bin is a complex product.
    """
    *tabs, alt_ref, a_ref, b_ref, c_ref = refs
    fwd, back = tabs[:6], tabs[6:]

    def row(b):
        tf = [r[...] for r in fwd]
        alt = alt_ref[...]
        far, fai = rfft_row(a_ref[b].astype(jnp.float32), tf, alt)
        fbr, fbi = rfft_row(b_ref[b].astype(jnp.float32), tf, alt)
        pr, pi = _cmul(far, fai, fbr, fbi)
        at0 = _origin(pr.shape)
        pr = jnp.where(at0, far * fbr, pr)
        pi = jnp.where(at0, fai * fbi, pi)
        c = irfft_row(pr, pi, [r[...] for r in back], alt, n)
        c_ref[b] = c.astype(c_ref.dtype)
    for_each_row([a_ref], row)


@functools.partial(jax.jit, static_argnames=("interpret", "block_b"))
def polymul_complex_planes(ar, ai, br, bi, *, interpret: bool | None = None,
                           block_b: int | None = None):
    """Circular (mod x^n - 1) product of complex coefficient vectors (B, n)."""
    assert ar.shape == ai.shape == br.shape == bi.shape and ar.ndim == 2
    b, n = ar.shape
    interpret = resolve_interpret(interpret)
    r_, c_ = split(n)
    # 3 transforms and 6 planes per row: half the fft block.
    blk = block_b or _fit_block(max(1, plan_batch_block(n) // 2), b)
    ops = pad_rows([v.reshape(b, r_, c_) for v in (ar, ai, br, bi)], blk)
    out = jax.ShapeDtypeStruct(ops[0].shape, ar.dtype)
    cr, ci = batched_call(
        functools.partial(_polymul_complex_kernel, n=n),
        [*row_tables(n), *spectrum_tables(n)], ops, [out, out], blk=blk,
        interpret=interpret)
    return cr[:b].reshape(b, n), ci[:b].reshape(b, n)


@functools.partial(jax.jit, static_argnames=("interpret", "block_b"))
def polymul_real_planes(a, b, *, interpret: bool | None = None,
                        block_b: int | None = None):
    """Circular product of REAL coefficient vectors (B, n) via the half
    spectrum (paper Eq. (10) / §5): 0.5x the matrix work of
    ``polymul_complex_planes`` per product, with the doubled real-mode batch
    block the halved working set buys."""
    assert a.shape == b.shape and a.ndim == 2
    bsz, n = a.shape
    assert n >= 2, f"n={n} must be a power of two >= 2"
    interpret = resolve_interpret(interpret)
    r_, c_ = split(n)
    blk = block_b or _fit_block(max(1, plan_batch_block(n, real=True) // 2),
                                bsz)
    ops = pad_rows([v.reshape(bsz, r_, c_) for v in (a, b)], blk)
    out = jax.ShapeDtypeStruct(ops[0].shape, a.dtype)
    (c,) = batched_call(
        functools.partial(_polymul_real_kernel, n=n),
        [*row_tables(n, half=True), *spectrum_tables(n, half=True),
         alternating_row(n)], ops, [out], blk=blk, interpret=interpret)
    return c[:bsz].reshape(bsz, n)
