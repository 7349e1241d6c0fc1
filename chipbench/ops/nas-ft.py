"""nas-ft: NAS Parallel Benchmarks FT, one whole solve per request.

The request is a complex grid u0 of NZ x NY x NX points, flat in C order
(x contiguous), both planes uniform on (0, 1). The job: U1 = FFT3(u0), then
for t = 1..niter W_t = IFFT3(U1 * E^t) (normalised by 1/N), with
E[kz, ky, kx] = exp(-4 alpha pi^2 (kz'^2 + ky'^2 + kx'^2)) and k' the
signed frequency ((k + m/2) mod m) - m/2 on an axis of m points; the result
is the niter checksums chk_t = sum over j = 1..1024 of
W_t[5j mod NZ, 3j mod NY, j mod NX].

The plain reference is the float64 ``fftn`` and ``ifftn`` of ``scipy.fft``
(numpy's algorithm, on every host core). The comparison is, per job, the
largest checksum error over the steps relative to the largest reference
checksum, worst over the jobs checked: the measure of ``ops/fft.py``.

Its scale is the checksums' own size, about 1024 * |mean(u0)| = 724, set
by the grid's DC term. The checksums' distance from that term, which the
damped modes make, is no steady scale: over 24 seeds of Class B its
largest value over the steps read 2.0 to 14.1, spread as the modulus of a
complex Gaussian sum is (about one job in eighty below 1), and a limit on
the error relative to it would fail sound jobs whose only error is the
rounding of complex64 results (up to 4.3e-5 at 724).

The NPB class is found from n alone: S (64^3, 6 steps), A (256 x 256 x
128, 6), B (512 x 256 x 256, 20), C (512^3, 20).
"""
from __future__ import annotations

import math
import os

import numpy as np

CHECK = "chk_rel_err"
# a job's least HBM passes over the grid as complex64: the forward
# transform reads and writes it once, then each step reads it once
BYTES_PER_POINT = 8
CHECKSUM_POINTS = 1024
# n -> ((NZ, NY, NX), niter)
CLASSES = {
    1 << 18: ((64, 64, 64), 6),
    1 << 23: ((128, 256, 256), 6),
    1 << 25: ((256, 256, 512), 20),
    1 << 27: ((512, 512, 512), 20),
}


def _class(n: int) -> tuple[tuple[int, int, int], int]:
    if n not in CLASSES:
        raise ValueError(f"n={n} is no NPB FT class "
                         f"(known: {sorted(CLASSES)})")
    return CLASSES[n]


def flops(n: int) -> float:
    # one forward and niter inverse 3-D transforms
    return 5.0 * n * math.log2(n) * (1 + _class(n)[1])


def limbs(config: dict) -> int:
    return 2 + int(config["niter"])


def payloads(rng: np.random.Generator, count: int, config: dict):
    n = config["n"]
    pool = np.empty((count, n), np.complex64)
    for row in pool:
        row.real = rng.random(n, dtype=np.float32)
        row.imag = rng.random(n, dtype=np.float32)
    return pool


def request(pool, i: int):
    return pool[i]


def _steps(config: dict):
    """(shape, niter, the grid's damping factor E as a float64 array)."""
    shape = tuple(config["shape"])
    c = -4.0 * float(config["alpha"]) * math.pi ** 2
    e = 1.0
    for axis, m in enumerate(shape):
        k = np.arange(m)
        kbar = ((k + m // 2) % m - m // 2).astype(np.float64)
        e = np.multiply.outer(e, np.exp(c * kbar ** 2)) if axis else \
            np.exp(c * kbar ** 2)
    return shape, int(config["niter"]), e


def _points(shape) -> tuple[np.ndarray, ...]:
    j = np.arange(1, CHECKSUM_POINTS + 1)
    nz, ny, nx = shape
    return 5 * j % nz, 3 * j % ny, j % nx


def _solve(u0: np.ndarray, config: dict, fwd, inv) -> np.ndarray:
    shape, niter, e = _steps(config)
    pts = _points(shape)
    u1 = fwd(u0.reshape(shape))
    out = np.empty(niter, np.complex128)
    for t in range(1, niter + 1):
        out[t - 1] = inv(u1 * e ** t)[pts].sum()
    return out


def reference(pool, idx, config: dict) -> np.ndarray:
    import scipy.fft
    workers = os.cpu_count()
    return np.stack([
        _solve(pool[i].astype(np.complex128), config,
               lambda x: scipy.fft.fftn(x, workers=workers),
               lambda x: scipy.fft.ifftn(x, workers=workers))
        for i in idx])


def _fft3_high(x: np.ndarray) -> np.ndarray:
    """The 3-D DFT with each axis through ``highdft.fft_high``."""
    from chipbench.highdft import fft_high
    for axis in range(x.ndim):
        y = np.moveaxis(x, axis, -1)
        x = np.moveaxis(fft_high(y.reshape(-1, y.shape[-1]))
                        .reshape(y.shape), -1, axis)
    return x


def control(pool, idx, config: dict) -> np.ndarray:
    def inv(x):
        return np.conj(_fft3_high(np.conj(x).astype(np.complex64))) / x.size
    return np.stack([
        _solve(pool[i], config, _fft3_high,
               lambda x: inv(x.astype(np.complex64)))
        for i in idx])


def compare(got, want) -> float:
    got = np.asarray(got, np.complex128)
    err = np.abs(got - want).max(axis=-1) / np.abs(want).max(axis=-1)
    return float(err.max())
