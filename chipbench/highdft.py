"""A transform at ``high`` precision: each f32 matmul in three bf16 passes.

The float cells' control (see ``ops/fft.py``) is a plain four-step DFT,
n = r * c, whose matrix products split every f32 operand into a bf16 high
part and a bf16 low part and drop the low-by-low product: what
``Precision.HIGH`` does on the MXU, written out so that it reads the same on
any backend. The serving kernels state f32 at ``HIGHEST``; ``HIGH`` is the
step below it that a faster kernel would be tempted to take.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _split(n: int) -> tuple[int, int]:
    log2n = n.bit_length() - 1
    if n < 4 or 1 << log2n != n:
        raise ValueError(f"n={n} must be a power of two >= 4")
    r = 1 << (log2n // 2)
    return r, n // r


@functools.lru_cache(maxsize=8)
def _tables(n: int) -> tuple[np.ndarray, ...]:
    """DFT matrices of r and c points and the (r, c) twiddles, computed in
    float64 from exact integer exponents and rounded once to f32."""
    r, c = _split(n)
    fr = np.exp(-2j * np.pi * np.outer(np.arange(r), np.arange(r)) / r)
    fc = np.exp(-2j * np.pi * np.outer(np.arange(c), np.arange(c)) / c)
    tw = np.exp(-2j * np.pi * (np.outer(np.arange(r), np.arange(c)) % n) / n)
    return tuple(np.ascontiguousarray(part, np.float32)
                 for m in (fr, fc, tw) for part in (m.real, m.imag))


def _bf16(x):
    """x rounded to bf16 and kept in f32. ``reduce_precision`` is an op the
    compiler keeps: a plain round trip through ``astype`` may be folded
    away on the TPU (excess precision), which would zero the low part."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _mm3(a, b):
    """f32 matmul from three bf16 passes with f32 accumulation."""
    def parts(x):
        hi = _bf16(x)
        return hi, _bf16(x - hi)

    def dot(x, y):              # exact: products of bf16 values fit in f32
        return jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)

    ah, al = parts(a)
    bh, bl = parts(b)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


def _cmm(ar, ai, br, bi):
    return _mm3(ar, br) - _mm3(ai, bi), _mm3(ar, bi) + _mm3(ai, br)


@functools.partial(jax.jit, static_argnames="n")
def _fft_planes(xr, xi, n: int):
    r, c = _split(n)
    frr, fri, fcr, fci, twr, twi = (jnp.asarray(t) for t in _tables(n))
    b = xr.shape[0]
    mr, mi = xr.reshape(b, r, c), xi.reshape(b, r, c)
    ar, ai = _cmm(frr, fri, mr, mi)                     # over j1 -> k1
    br = ar * twr - ai * twi
    bi = ar * twi + ai * twr
    cr, ci = _cmm(br, bi, fcr, fci)                     # over j2 -> k2
    # X[k1 + r * k2] = C[k1, k2]
    return (cr.transpose(0, 2, 1).reshape(b, n),
            ci.transpose(0, 2, 1).reshape(b, n))


def fft_high(x: np.ndarray) -> np.ndarray:
    """Complex (B, n) -> its DFT (B, n), complex64, at ``high``."""
    x = np.asarray(x, np.complex64)
    yr, yi = _fft_planes(x.real.copy(), x.imag.copy(), n=x.shape[-1])
    return np.asarray(yr) + 1j * np.asarray(yi)


def circular_real_high(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Real circular products of (B, n) rows through three transforms at
    ``high``: IFFT(FFT(a) FFT(b)) = conj(FFT(conj(P))) / n."""
    n = a.shape[-1]
    p = fft_high(a.astype(np.complex64)) * fft_high(b.astype(np.complex64))
    return (np.conj(fft_high(np.conj(p).astype(np.complex64))) / n).real
