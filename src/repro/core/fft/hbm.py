"""HBM-resident four-step real product on one device: the third float tier.

A row too long for one VMEM-resident kernel (n above the planner's
``_MAX_LOCAL_N``) is viewed as an (n1, n2) matrix, x[j1*n2 + j2] =
M[j1, j2], with the inner length n2 within that ceiling. This is the
four-step of ``core/fft/distributed.py`` on one device, with the device
axis D replaced by the n1 rows of the matrix: the whole (n1, n2) block
already sits in HBM, so where that tier runs an all-to-all this one reads
the block in place, and where it ppermutes a mirror block this one gathers
a row.

Per batch of real products (rows a, b packed as z = a + i b):

  1. ``hbm_outer_fwd``: the column DFTs of length n1 and the twiddle
     w_n^{k1*j2}, one Pallas pass over (n1, L) tiles;
  2. the rows of length n2 through the VMEM-resident four-step kernel
     (``kernels.fft.fft_planes``), leaving each spectrum in Z-order,
     X[k1 + n1*k2] = Z[k1, k2];
  3. the paired product of two real products from their Z-order spectra
     (``distributed.zorder_real_product``), its mirror bins in row
     (n1 - k1) mod n1;
  4. the inverse rows through the same kernel, then ``hbm_outer_inv``:
     the conjugate twiddle and the inverse column DFTs in one Pallas pass,
     which writes both real rows of each pair.

The twiddles are ``distributed._twiddle_tables``' (exact integer exponents,
float64 angles, rounded once); every matrix product runs at
``Precision.HIGHEST``, as in the inner kernels.

Both outer passes run through ``column_pass``, one Pallas pass of column
DFTs over (B, m, L) planes in (m, lanes) tiles. ``axis_dft`` is the same
pass without the twiddle: the DFT along a strided axis of a grid held in
HBM, which the 3-D transforms of ``core.fft.ft3d`` take for their y and z
axes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.fft.distributed import _twiddle_tables, zorder_real_product
from repro.kernels import fft as kfft
from repro.kernels.fft import _cmatmul, _cmul, _mm, resolve_interpret

#: HBM passes each batch makes outside the inner row kernel: the outer
#: forward pass, the paired product and the outer inverse pass. The
#: engine's recorder counts them (``hbm.outer_passes``).
OUTER_PASSES = 3
#: elements of one outer grid step's (n1, L) tile
_TILE_ELEMS = 1 << 16


@functools.lru_cache(maxsize=16)
def dft_matrix(m: int) -> tuple[np.ndarray, np.ndarray]:
    """fp32 planes of the m-point DFT matrix F[k, j] = w_m^{k*j}, gathered
    from ``kfft.twiddle_table(m)``. Its conjugate, the inverse's matrix, is
    exact."""
    wr, wi = (t[0] for t in kfft.twiddle_table(m))
    k = np.arange(m)
    idx = np.outer(k, k) % m
    return wr[idx], wi[idx]


@functools.lru_cache(maxsize=16)
def outer_tables(n: int, n1: int) -> tuple[np.ndarray, ...]:
    """fp32 planes of the n1-point DFT matrix (``dft_matrix``) and of the
    twiddle T[k1, j2] = w_n^{k1*j2}, (n1, n/n1). The inverse pass uses
    their conjugates, which are exact."""
    t = _twiddle_tables(n, n1, n // n1, False)[0]
    return (*dft_matrix(n1), np.ascontiguousarray(t.real),
            np.ascontiguousarray(t.imag))


def _lanes(n1: int, n2: int) -> int:
    return min(n2, max(128, _TILE_ELEMS // n1))


def outer_vmem_bytes(n: int, n1: int) -> int:
    """VMEM of one outer pass's launch: the double-buffered DFT matrix
    planes, twiddle tiles, two input and two output (n1, L) tiles, and
    eight tiles of the kernel's temporaries."""
    tile = kfft._tile_bytes(n1, _lanes(n1, n // n1))
    return 2 * (2 * kfft._tile_bytes(n1, n1) + 6 * tile) + 8 * tile


def axis_vmem_bytes(m: int, width: int) -> int:
    """VMEM of one ``axis_dft`` launch over (B, m, width) planes: the
    double-buffered DFT matrix planes, two input and two output (m, L)
    tiles, and eight tiles of the kernel's temporaries."""
    tile = kfft._tile_bytes(m, _lanes(m, width))
    return 2 * (2 * kfft._tile_bytes(m, m) + 4 * tile) + 8 * tile


def _fwd_kernel(fr_ref, fi_ref, tr_ref, ti_ref, a_ref, b_ref, yr_ref,
                yi_ref):
    yr, yi = _cmatmul(_mm, fr_ref[...], fi_ref[...],
                      a_ref[0].astype(jnp.float32),
                      b_ref[0].astype(jnp.float32))
    yr, yi = _cmul(yr, yi, tr_ref[...], ti_ref[...])
    yr_ref[0] = yr
    yi_ref[0] = yi


def _inv_kernel(fr_ref, fi_ref, tr_ref, ti_ref, zr_ref, zi_ref, o_ref, *,
                scale: float):
    wr, wi = _cmul(zr_ref[0], zi_ref[0], tr_ref[...], -ti_ref[...])
    cr, ci = _cmatmul(_mm, fr_ref[...], -fi_ref[...], wr, wi)
    o_ref[0, 0] = cr * scale
    o_ref[0, 1] = ci * scale


def column_pass(kernel, name: str, mats, tiles, planes, outs,
                interpret: bool):
    """One Pallas pass of column DFTs over (B, m, L) planes in (m, lanes)
    tiles, writing ``outs`` (each (B, ..., m, L)). ``mats`` are whole
    (m, m) constants, the same block every step; ``tiles`` are (m, L)
    tables tiled as the planes are. The grid runs the rows innermost, so
    each table tile is read once a pass. The kernel takes the refs of
    ``mats``, ``tiles``, ``planes`` and ``outs``, in that order."""
    rows, m, width = planes[0].shape
    lanes = _lanes(m, width)
    const = pl.BlockSpec((m, m), lambda j, b: (0, 0))
    tile = pl.BlockSpec((m, lanes), lambda j, b: (0, j))

    def rows_spec(shape):
        mid = len(shape) - 3
        return pl.BlockSpec((1, *shape[1:-1], lanes),
                            lambda j, b: (b,) + (0,) * (mid + 1) + (j,))
    return pl.pallas_call(
        kernel, grid=(width // lanes, rows),
        in_specs=[const] * len(mats) + [tile] * len(tiles)
        + [rows_spec(p.shape) for p in planes],
        out_specs=[rows_spec(o.shape) for o in outs], out_shape=outs,
        interpret=interpret, name=name)(
            *(jnp.asarray(t) for t in (*mats, *tiles)), *planes)


def _outer_call(kernel, name: str, n: int, planes, outs, interpret: bool):
    """The four-step's outer pass: column DFTs of length n1 with the
    twiddle of ``outer_tables``, over (B, n1, n2) planes."""
    fr, fi, tr, ti = outer_tables(n, planes[0].shape[1])
    return column_pass(kernel, name, [fr, fi], [tr, ti], planes, outs,
                       interpret)


def _axis_kernel(fr_ref, fi_ref, xr_ref, xi_ref, yr_ref, yi_ref, *,
                 scale: float | None):
    yr, yi = _cmatmul(_mm, fr_ref[...], fi_ref[...], xr_ref[0], xi_ref[0])
    if scale is not None:
        yr, yi = yr * scale, yi * scale
    yr_ref[0] = yr
    yi_ref[0] = yi


@functools.partial(jax.jit, static_argnames=("inverse", "interpret"))
def axis_dft(xr: jax.Array, xi: jax.Array, *, inverse: bool = False,
             interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """The DFT along axis 1 of (B, m, L) float32 planes, in place of the
    strided axis: a dense m-point DFT matrix times each (m, lanes) tile,
    no transpose. The inverse uses the conjugate matrix and scales by
    1/m. A 3-D grid (NZ, NY, NX) takes its y axis as (NZ, NY, NX) and its
    z axis as (1, NZ, NY*NX) (``core.fft.ft3d``)."""
    assert xr.shape == xi.shape and xr.ndim == 3, (xr.shape, xi.shape)
    fr, fi = dft_matrix(xr.shape[1])
    if inverse:
        fi = -fi
    plane = jax.ShapeDtypeStruct(xr.shape, jnp.float32)
    return column_pass(
        functools.partial(_axis_kernel,
                          scale=1.0 / xr.shape[1] if inverse else None),
        "axis_dft", [fr, fi], [], [xr, xi], [plane, plane],
        resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("inner_n", "interpret"))
def polymul_real_hbm(a: jax.Array, b: jax.Array, *, inner_n: int,
                     interpret: bool | None = None) -> jax.Array:
    """Circular product of real rows (B, n) -> (B, n) float32 through the
    HBM-resident four-step with rows of ``inner_n`` (n1 = n / inner_n).
    An odd batch pairs its last product with a zeros product, which
    changes no result."""
    assert a.shape == b.shape and a.ndim == 2, (a.shape, b.shape)
    bsz, n = a.shape
    n1, n2 = n // inner_n, inner_n
    assert n1 * n2 == n and n1 >= 2, f"cannot split n={n} into rows of {n2}"
    interpret = resolve_interpret(interpret)
    if bsz % 2:
        a, b = kfft.pad_rows([a, b], 2)
    rows = a.shape[0]
    half = rows // 2
    plane = jax.ShapeDtypeStruct((rows, n1, n2), jnp.float32)
    yr, yi = _outer_call(
        _fwd_kernel, "hbm_outer_fwd", n,
        [a.reshape(rows, n1, n2), b.reshape(rows, n1, n2)], [plane, plane],
        interpret)
    zr, zi = kfft.fft_planes(yr.reshape(rows * n1, n2),
                             yi.reshape(rows * n1, n2), interpret=interpret)
    # (n1, B, n2): the k1 blocks lead, as the devices do in the
    # distributed tier; the mirror block of k1 is row (n1 - k1) mod n1
    zz = jax.lax.complex(zr, zi).reshape(rows, n1, n2).swapaxes(0, 1)
    q = zorder_real_product(
        zz, lambda w: jnp.concatenate([w[:1], jnp.flip(w[1:], axis=0)]),
        (jnp.arange(n1) == 0)[:, None, None])
    q = q.swapaxes(0, 1).reshape(half * n1, n2)
    cr, ci = kfft.fft_planes(jnp.real(q), jnp.imag(q), inverse=True,
                             interpret=interpret)
    (out,) = _outer_call(
        functools.partial(_inv_kernel, scale=1.0 / n1), "hbm_outer_inv", n,
        [cr.reshape(half, n1, n2), ci.reshape(half, n1, n2)],
        [jax.ShapeDtypeStruct((half, 2, n1, n2), jnp.float32)], interpret)
    return out.reshape(rows, n)[:bsz]
