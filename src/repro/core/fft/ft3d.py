"""Three-dimensional FFT on one device, with no transposes, and the NAS
Parallel Benchmarks FT solve built on it.

A grid of shape (NZ, NY, NX) is held flat in C order (x contiguous) as
float32 planes. ``fft3_planes`` transforms one axis a pass, each pass one
read and one write of the grid in HBM, and none moves an axis:

  * x: the grid's NZ*NY rows of NX points through the VMEM-resident row
    kernel (``kernels.fft.fft_planes``);
  * y: ``hbm.axis_dft`` over the (NZ, NY, NX) view: NY-point columns,
    strided by NX;
  * z: ``hbm.axis_dft`` over the (1, NZ, NY*NX) view: NZ-point columns,
    strided by NY*NX.

``nas_ft`` is NPB FT (NAS-95-020) as docs/fourier.md states it: U1 =
FFT3(u0), then for t = 1..niter the inverse transform of U1 damped by
E^t, and the checksum of 1024 of its points. The loop, the damping and
the gather stay on the device.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fft import hbm
from repro.kernels import fft as kfft

#: column passes of one 3-D transform (y and z); the engine's recorder
#: counts them (``ft.axis_passes``)
AXIS_PASSES = 2
#: points summed into each checksum
CHECKSUM_POINTS = 1024


def fft3_planes(xr: jax.Array, xi: jax.Array, shape: tuple[int, int, int],
                *, inverse: bool = False, interpret: bool | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """3-D DFT of (B, N) float32 planes, each row a grid of ``shape``
    (NZ, NY, NX) in C order. The inverse uses the conjugate tables and
    scales by 1/N (1/NX, 1/NY and 1/NZ, one in each pass)."""
    nz, ny, nx = shape
    b = xr.shape[0]
    assert xr.shape == xi.shape == (b, nz * ny * nx), (xr.shape, shape)
    yr, yi = kfft.fft_planes(xr.reshape(b * nz * ny, nx),
                             xi.reshape(b * nz * ny, nx), inverse=inverse,
                             interpret=interpret)
    for view in ((b * nz, ny, nx), (b, nz, ny * nx)):
        yr, yi = hbm.axis_dft(yr.reshape(view), yi.reshape(view),
                              inverse=inverse, interpret=interpret)
    return yr.reshape(b, -1), yi.reshape(b, -1)


def checksum_index(shape: tuple[int, int, int]) -> np.ndarray:
    """Flat indices of the checksum's points (5j mod NZ, 3j mod NY,
    j mod NX), j = 1..1024."""
    nz, ny, nx = shape
    j = np.arange(1, CHECKSUM_POINTS + 1)
    return ((5 * j % nz) * ny + 3 * j % ny) * nx + j % nx


def _decay(shape: tuple[int, int, int], alpha: float) -> list[np.ndarray]:
    """Per axis, -4 alpha pi^2 k'^2 for the signed frequency
    k' = ((k + m/2) mod m) - m/2: E^t is the outer product of their
    exponentials times t."""
    out = []
    for m in shape:
        k = np.arange(m)
        kbar = (k + m // 2) % m - m // 2
        out.append((-4.0 * alpha * math.pi ** 2 * kbar.astype(np.float64)
                    ** 2).astype(np.float32))
    return out


@functools.partial(jax.jit,
                   static_argnames=("shape", "niter", "alpha", "interpret"))
def nas_ft(u0: jax.Array, *, shape: tuple[int, int, int], niter: int,
           alpha: float, interpret: bool | None = None
           ) -> tuple[jax.Array, jax.Array]:
    """NPB FT on (B, N) complex64 grids: the (B, niter) checksums as
    float32 planes (re, im).

    Each grid's mean m is taken out before the forward transform and
    1024 m added to each checksum. That changes no result (the transform
    is linear and the inverse of a constant is that constant), and the
    DC bin, by far the grid's largest, then costs no float32 rounding:
    with u0 uniform on (0, 1) it holds 2^25 / 2 at Class B, and its
    rounding alone would be several times the error of the rest
    (docs/fourier.md)."""
    ur, ui = jnp.real(u0), jnp.imag(u0)
    mr = jnp.mean(ur, axis=1, keepdims=True)
    mi = jnp.mean(ui, axis=1, keepdims=True)
    vr, vi = fft3_planes(ur - mr, ui - mi, shape, interpret=interpret)
    dz, dy, dx = (jnp.asarray(d) for d in _decay(shape, alpha))
    idx = jnp.asarray(checksum_index(shape))

    def step(t, acc):
        tf = t.astype(jnp.float32)
        e = (jnp.exp(tf * dz)[:, None, None]
             * jnp.exp(tf * dy)[None, :, None]
             * jnp.exp(tf * dx)[None, None, :]).reshape(-1)
        wr, wi = fft3_planes(vr * e, vi * e, shape, inverse=True,
                             interpret=interpret)
        cr, ci = acc
        return (cr.at[:, t - 1].set(jnp.sum(wr[:, idx], axis=1)),
                ci.at[:, t - 1].set(jnp.sum(wi[:, idx], axis=1)))

    zeros = jnp.zeros((u0.shape[0], niter), jnp.float32)
    cr, ci = jax.lax.fori_loop(1, niter + 1, step, (zeros, zeros))
    return cr + CHECKSUM_POINTS * mr, ci + CHECKSUM_POINTS * mi
