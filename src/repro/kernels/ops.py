"""Public jit'd wrappers over the Fourier kernels.

Backend selection
-----------------
``backend='pallas'`` runs the Pallas kernels (compiled on TPU; interpret mode
on CPU — bit-exact dataflow, Python-speed). ``backend='xla'`` runs the same
Stockham dataflow as a plain jnp program (fast on CPU, used by the model
layers and examples in this container). ``backend=None`` auto-selects:
Pallas on TPU, XLA elsewhere. Override with env ``REPRO_FFT_BACKEND``.

All functions accept/return complex arrays (complex64) or real arrays where
documented; shape (..., n) with any leading batch dims, n a power of two.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import fft as _kfft
from repro.kernels import polymul as _kpoly
from repro.kernels import ref as _ref


def _auto_backend() -> str:
    env = os.environ.get("REPRO_FFT_BACKEND")
    if env:
        return env
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _as2d(x):
    n = x.shape[-1]
    return x.reshape(-1, n), x.shape[:-1]


def join(re: jax.Array, im: jax.Array) -> jax.Array:
    """Float32 planes -> complex64, the same numbers bit for bit.

    ``fft``, ``rfft`` and the complex ``polymul`` are their ``*_planes``
    function followed by this join, so a caller that takes the planes
    instead (the serving routes, which return them to the host as float32
    words) holds the complex result's own parts.
    """
    return jax.lax.complex(re, im)


def fft_planes(x: jax.Array, *, inverse: bool = False,
               backend: str | None = None) -> tuple[jax.Array, jax.Array]:
    """``fft`` as float32 planes ``(re, im)``, each (..., n)."""
    backend = backend or _auto_backend()
    if backend == "xla":
        y = _ref.fft_stockham(x, inverse=inverse)
        return jnp.real(y), jnp.imag(y)
    x2, lead = _as2d(x)
    xr = jnp.real(x2).astype(jnp.float32)
    xi = jnp.imag(x2).astype(jnp.float32)
    yr, yi = _kfft.fft_planes(xr, xi, inverse=inverse)
    return yr.reshape(*lead, x.shape[-1]), yi.reshape(*lead, x.shape[-1])


def fft(x: jax.Array, *, inverse: bool = False,
        backend: str | None = None) -> jax.Array:
    """Batched FFT of a complex array (..., n)."""
    return join(*fft_planes(x, inverse=inverse, backend=backend))


def ifft(x: jax.Array, **kw) -> jax.Array:
    return fft(x, inverse=True, **kw)


# ---------------------------------------------------------------------------
# Real-Hermitian fast path (paper Eq. (10)): rfft / irfft / polymul_real.
#
# Public layout is numpy's (..., n/2 + 1) complex half-spectrum so callers
# can diff against np.fft.rfft directly; ``packed=True`` exposes the
# kernel's packed-Nyquist layout (n/2 bins, P[0] = X[0].re + i X[n/2].re)
# without the O(n) repack — the layout that never leaves HBM at full width.
# ---------------------------------------------------------------------------

def _halfspec_planes(yr: jax.Array, yi: jax.Array
                     ) -> tuple[jax.Array, jax.Array]:
    """Packed-Nyquist planes (..., n/2) -> numpy-layout planes (..., n/2+1)."""
    zero = jnp.zeros_like(yr[..., :1])
    re = jnp.concatenate([yr, yi[..., :1]], axis=-1)
    im = jnp.concatenate([zero, yi[..., 1:], zero], axis=-1)
    return re, im


def packed_to_halfspec(yr: jax.Array, yi: jax.Array) -> jax.Array:
    """Packed-Nyquist planes (..., n/2) -> numpy-layout (..., n/2+1).

    Public: the distributed real tier (``core.fft.rfft_distributed``)
    emits the same packed layout as the local kernels, and its callers
    repack with this converter (it is the single layout definition).
    """
    return join(*_halfspec_planes(yr, yi))


def halfspec_to_packed(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Numpy-layout half-spectrum (..., n/2+1) -> packed planes (..., n/2)."""
    nh = x.shape[-1] - 1
    re = jnp.real(x).astype(jnp.float32)
    im = jnp.imag(x).astype(jnp.float32)
    pr = re[..., :nh]
    pi = jnp.concatenate([re[..., nh:], im[..., 1:nh]], axis=-1)
    return pr, pi


def rfft_planes(x: jax.Array, *, backend: str | None = None,
                packed: bool = False) -> tuple[jax.Array, jax.Array]:
    """``rfft`` as float32 planes ``(re, im)``: numpy's (..., n/2+1)
    layout, or the kernel's packed-Nyquist (..., n/2) with ``packed=True``.
    """
    if jnp.iscomplexobj(x):
        raise TypeError(f"rfft needs real input, got {x.dtype}")
    n = x.shape[-1]
    backend = backend or _auto_backend()
    if backend == "xla":
        full = _ref.fft_stockham(x.astype(jnp.complex64))
        half = full[..., :n // 2 + 1]
        if packed:
            return halfspec_to_packed(half)
        return jnp.real(half), jnp.imag(half)
    x2, lead = _as2d(x)
    yr, yi = _kfft.rfft_planes(x2.astype(jnp.float32))
    yr = yr.reshape(*lead, n // 2)
    yi = yi.reshape(*lead, n // 2)
    return (yr, yi) if packed else _halfspec_planes(yr, yi)


def rfft(x: jax.Array, *, backend: str | None = None,
         packed: bool = False):
    """FFT of a real array (..., n): half-spectrum only (Hermitian symmetry).

    Returns complex (..., n/2+1) matching ``np.fft.rfft``, or the packed
    planes ``(re, im)`` of shape (..., n/2) with ``packed=True``. The Pallas
    route runs the half-spectrum kernel: half the matrix work and half the
    HBM traffic of ``fft`` on real input.
    """
    planes = rfft_planes(x, backend=backend, packed=packed)
    return planes if packed else join(*planes)


def irfft(x, *, backend: str | None = None,
          packed: bool = False) -> jax.Array:
    """Inverse of ``rfft``: half-spectrum -> real (..., n).

    ``x`` is complex (..., n/2+1) (numpy layout), or the packed plane pair
    with ``packed=True``. The Pallas route folds the mirror half of the
    spectrum back as 2*Re inside the kernel.
    """
    if packed:
        pr, pi = x
        pr = jnp.asarray(pr, jnp.float32)
        pi = jnp.asarray(pi, jnp.float32)
    else:
        pr, pi = halfspec_to_packed(x)
    n = 2 * pr.shape[-1]
    backend = backend or _auto_backend()
    if backend == "xla":
        half = packed_to_halfspec(pr, pi)
        tail = jnp.conj(jnp.flip(half[..., 1:-1], axis=-1))
        full = jnp.concatenate([half, tail], axis=-1)
        return jnp.real(_ref.fft_stockham(full, inverse=True)).astype(
            jnp.float32)
    p2, lead = _as2d(pr)
    q2, _ = _as2d(pi)
    y = _kfft.irfft_planes(p2, q2)
    return y.reshape(*lead, n)


def polymul_real(a: jax.Array, b: jax.Array, *, mode: str = "linear",
                 backend: str | None = None,
                 block_b: int | None = None) -> jax.Array:
    """Polynomial product of REAL coefficient arrays — the explicit fast
    path (``polymul`` also auto-detects real input, but serving code routes
    here so the selection is visible/testable). Raises on complex input,
    then delegates to ``polymul``'s real branch (one dispatch to keep in
    sync): the fused half-spectrum kernel — half the matrix work of the
    complex path per product.
    """
    if jnp.iscomplexobj(a) or jnp.iscomplexobj(b):
        raise TypeError(f"polymul_real needs real input, got "
                        f"{a.dtype}/{b.dtype}")
    return polymul(a, b, mode=mode, backend=backend, block_b=block_b)


def _padded(a: jax.Array, b: jax.Array, mode: str):
    """Operands of a ``mode`` product and its length: 'linear' zero-pads
    to 2n (paper fn. 4), 'circular' keeps n."""
    assert a.shape == b.shape
    n = a.shape[-1]
    if mode == "linear":
        pads = [(0, 0)] * (a.ndim - 1) + [(0, n)]
        return jnp.pad(a, pads), jnp.pad(b, pads), 2 * n
    if mode != "circular":
        raise ValueError(f"unknown mode {mode!r}")
    return a, b, n


def _xla_product(a: jax.Array, b: jax.Array) -> jax.Array:
    fa = _ref.fft_stockham(a.astype(jnp.complex64))
    fb = _ref.fft_stockham(b.astype(jnp.complex64))
    return _ref.fft_stockham(fa * fb, inverse=True)


def polymul_planes(a: jax.Array, b: jax.Array, *, mode: str = "linear",
                   backend: str | None = None,
                   block_b: int | None = None
                   ) -> tuple[jax.Array, jax.Array]:
    """The complex product of ``polymul`` as float32 planes ``(re, im)``
    (the three-transform path, whatever the operands' dtypes)."""
    a, b, n = _padded(a, b, mode)
    backend = backend or _auto_backend()
    if backend == "xla":
        c = _xla_product(a, b)
        return jnp.real(c), jnp.imag(c)
    a2, lead = _as2d(a)
    b2, _ = _as2d(b)
    cr, ci = _kpoly.polymul_complex_planes(
        jnp.real(a2).astype(jnp.float32), jnp.imag(a2).astype(jnp.float32),
        jnp.real(b2).astype(jnp.float32), jnp.imag(b2).astype(jnp.float32),
        block_b=block_b)
    return cr.reshape(*lead, n), ci.reshape(*lead, n)


def polymul(a: jax.Array, b: jax.Array, *, mode: str = "linear",
            backend: str | None = None,
            block_b: int | None = None) -> jax.Array:
    """Polynomial multiplication via the convolution theorem (paper Eq. (9)).

    mode='circular': product mod x^n - 1 (length n).
    mode='linear'  : full product — inputs zero-padded to 2n (paper fn. 4);
                     returns length 2n (last coefficient structurally 0).

    Real inputs dispatch to the Eq. (10) half-spectrum path; complex inputs
    use the three-transform path.
    """
    if jnp.iscomplexobj(a) or jnp.iscomplexobj(b):
        return join(*polymul_planes(a, b, mode=mode, backend=backend,
                                    block_b=block_b))
    a, b, n = _padded(a, b, mode)
    backend = backend or _auto_backend()
    if backend == "xla":
        return jnp.real(_xla_product(a, b)).astype(jnp.float32)
    a2, lead = _as2d(a)
    b2, _ = _as2d(b)
    c = _kpoly.polymul_real_planes(a2.astype(jnp.float32),
                                   b2.astype(jnp.float32), block_b=block_b)
    return c.reshape(*lead, n)


def hermitian_split(zr, zi):
    """Split Z = FFT(a + i b) of two real rows into their spectra (Eq. (10)).

    A_k = (Z_k + conj(Z_{n-k})) / 2,  B_k = -i (Z_k - conj(Z_{n-k})) / 2,
    on split planes; Z_{n-k} is a flip then a rotate by one so k=0 stays.
    The results are EXACTLY Hermitian in fp32 (each component of A_{n-k} is
    the same float expression as ±A_k's).
    """
    zrr = jnp.roll(jnp.flip(zr, axis=-1), 1, axis=-1)
    zri = jnp.roll(jnp.flip(zi, axis=-1), 1, axis=-1)
    ar = 0.5 * (zrr + zr)
    ai = 0.5 * (-zri + zi)
    br = 0.5 * (zri + zi)
    bi = 0.5 * (zrr - zr)
    return ar, ai, br, bi


def realpack_fft(x: jax.Array, y: jax.Array, *, backend: str | None = None
                 ) -> tuple[jax.Array, jax.Array]:
    """FFTs of two real sequences via one complex FFT (paper Eq. (10))."""
    z = x.astype(jnp.complex64) + 1j * y.astype(jnp.complex64)
    zf = fft(z, backend=backend)
    ar, ai, br, bi = hermitian_split(jnp.real(zf), jnp.imag(zf))
    return ar + 1j * ai, br + 1j * bi


def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


def fft2(x: jax.Array, *, inverse: bool = False,
         backend: str | None = None) -> jax.Array:
    """2-D FFT of (..., H, W) via row + column transforms of the batched
    1-D primitive (separability) — the paper's signal-processing use case
    lifted to images. H, W powers of two."""
    y = fft(x, inverse=inverse, backend=backend)          # along W
    y = jnp.swapaxes(y, -1, -2)
    y = fft(y, inverse=inverse, backend=backend)          # along H
    return jnp.swapaxes(y, -1, -2)


def fft_conv2d(img: jax.Array, kern: jax.Array, *,
               backend: str | None = None) -> jax.Array:
    """'same'-padded 2-D convolution via the convolution theorem.

    img: (..., H, W) real; kern: (kh, kw) real, kh/kw odd. O(HW log HW).
    """
    H, W = img.shape[-2:]
    kh, kw = kern.shape
    Hp = _next_pow2(H + kh)
    Wp = _next_pow2(W + kw)
    pads = [(0, 0)] * (img.ndim - 2) + [(0, Hp - H), (0, Wp - W)]
    xi = jnp.pad(img.astype(jnp.float32), pads)
    ki = jnp.pad(kern.astype(jnp.float32), ((0, Hp - kh), (0, Wp - kw)))
    fx = fft2(xi.astype(jnp.complex64), backend=backend)
    fk = fft2(ki.astype(jnp.complex64), backend=backend)
    full = jnp.real(fft2(fx * fk, inverse=True, backend=backend))
    r0, c0 = kh // 2, kw // 2
    return full[..., r0:r0 + H, c0:c0 + W].astype(img.dtype)


def fft_causal_conv(x: jax.Array, k: jax.Array, *,
                    backend: str | None = None) -> jax.Array:
    """Causal depthwise long convolution via FFT: y[t] = sum_{s<=t} k[s] x[t-s].

    x: (..., T) real signal, k: (..., K) real taps (K <= T). O(T log T) — the
    sub-quadratic primitive the model layers use for Fourier token mixing.
    Internally pads to the next power of two >= T + K to avoid wraparound.
    """
    T = x.shape[-1]
    K = k.shape[-1]
    n = _next_pow2(T + K)
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, n - T)])
    kp = jnp.pad(k, [(0, 0)] * (k.ndim - 1) + [(0, n - K)])
    fa = fft(xp.astype(jnp.complex64), backend=backend)
    fb = fft(kp.astype(jnp.complex64), backend=backend)
    y = ifft(fa * fb, backend=backend)
    return jnp.real(y[..., :T]).astype(x.dtype)
