"""The HBM-resident four-step real product (``core.fft.hbm``) against a
numpy float64 reference, at small sizes on the CPU, directly and through
the engine's polymul-real route with the VMEM ceiling forced small.

Error measure: ``chipbench/ops/polymul-real.py``'s, each row's largest
absolute error over the row's largest absolute reference value, worst row.

TOL: float32 rounds each pass by ~6e-8 relative; the tier's five passes
(column DFT and twiddle, rows, paired product, inverse rows, inverse
column DFT) read 1.8e-7 to 3.6e-7 on these seeded rows at n = 2^12, every
split. 2e-6 leaves over five times that, and one bf16 pass in the outer
column DFTs (8 significant bits, what ``Precision.DEFAULT`` runs on a TPU)
reads 3.6e-3 to 4.2e-3, three orders above it.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.fft import hbm, planner
from repro.kernels import fft as kfft

TOL = 2e-6
N = 1 << 12
# inner lengths n2 at n = 2^12: a 2^4 x 2^8 split, and two
# unbalanced splits, n1 >> n2 and n1 << n2
SPLITS = [1 << 8, 1 << 4, 1 << 10]


def _reference(a, b):
    a, b = a.astype(np.float64), b.astype(np.float64)
    return np.fft.irfft(np.fft.rfft(a) * np.fft.rfft(b), a.shape[-1])


def _rel_err(got, want):
    got = np.asarray(got, np.float64)
    return float((np.abs(got - want).max(-1)
                  / np.abs(want).max(-1)).max())


def _rows(seed, batch, n=N):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((batch, n), dtype=np.float32),
            rng.standard_normal((batch, n), dtype=np.float32))


@pytest.mark.parametrize("inner_n", SPLITS)
@pytest.mark.parametrize("batch", [4, 3])
def test_hbm_product_matches_float64_reference(inner_n, batch):
    """Every split, even batches and an odd one (its last product paired
    with zeros)."""
    a, b = _rows(2**31 + inner_n + batch, batch)
    got = hbm.polymul_real_hbm(a, b, inner_n=inner_n)
    assert got.shape == (batch, N) and got.dtype == jnp.float32
    assert _rel_err(got, _reference(a, b)) < TOL


@pytest.fixture
def bf16_outer(monkeypatch):
    """The outer column passes' matmuls with bf16 operands (one MXU pass,
    f32 accumulation), the inner kernels unchanged."""
    def mm(x, y):
        return kfft._mm(x.astype(jnp.bfloat16).astype(jnp.float32),
                        y.astype(jnp.bfloat16).astype(jnp.float32))
    jax.clear_caches()
    monkeypatch.setattr(hbm, "_mm", mm)
    yield
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("inner_n", SPLITS)
def test_bf16_outer_passes_fail_the_tolerance(bf16_outer, inner_n):
    a, b = _rows(2**31 + 7, 2)
    err = _rel_err(hbm.polymul_real_hbm(a, b, inner_n=inner_n),
                   _reference(a, b))
    assert err > 100 * TOL


def _outer_call_before_column_pass(kernel, name, n, planes, outs, interpret):
    """The outer pass as it was written before ``hbm.column_pass`` took it
    over (and ``axis_dft`` with it), kept to pin gw's results bit for
    bit."""
    from jax.experimental import pallas as pl
    rows, n1, n2 = planes[0].shape
    lanes = hbm._lanes(n1, n2)
    fr, fi, tr, ti = (jnp.asarray(t) for t in hbm.outer_tables(n, n1))
    const = pl.BlockSpec((n1, n1), lambda j, b: (0, 0))
    tile = pl.BlockSpec((n1, lanes), lambda j, b: (0, j))

    def rows_spec(shape):
        mid = len(shape) - 3
        return pl.BlockSpec((1, *shape[1:-1], lanes),
                            lambda j, b: (b,) + (0,) * (mid + 1) + (j,))
    return pl.pallas_call(
        kernel, grid=(n2 // lanes, rows),
        in_specs=[const, const, tile, tile]
        + [rows_spec(p.shape) for p in planes],
        out_specs=[rows_spec(o.shape) for o in outs], out_shape=outs,
        interpret=interpret, name=name)(fr, fi, tr, ti, *planes)


@pytest.mark.parametrize("inner_n", SPLITS)
def test_shared_column_pass_keeps_gw_bit_for_bit(monkeypatch, inner_n):
    """``polymul_real_hbm`` through the column pass it shares with
    ``axis_dft`` returns the same bits as through its own former pass."""
    a, b = _rows(2**31 + 43, 3)
    jax.clear_caches()
    now = np.asarray(hbm.polymul_real_hbm(a, b, inner_n=inner_n))
    monkeypatch.setattr(hbm, "_outer_call", _outer_call_before_column_pass)
    jax.clear_caches()
    before = np.asarray(hbm.polymul_real_hbm(a, b, inner_n=inner_n))
    monkeypatch.undo()
    jax.clear_caches()
    np.testing.assert_array_equal(now, before)


def test_outer_tables_are_exact_roots():
    fr, fi, tr, ti = hbm.outer_tables(N, 16)
    k = np.arange(16)
    want = np.exp(-2j * np.pi * np.outer(k, k) / 16)
    assert np.abs(fr + 1j * fi - want).max() < 1e-7
    j2 = np.arange(N // 16)
    want_t = np.exp(-2j * np.pi * np.outer(k, j2) / N)
    assert np.abs(tr + 1j * ti - want_t).max() < 1e-7


@pytest.fixture
def small_ceiling(monkeypatch):
    """The VMEM ceiling forced to 2^8, so that n = 2^12 takes the hbm tier
    with rows of 2^8."""
    monkeypatch.setattr(planner, "_MAX_LOCAL_N", 1 << 8)
    monkeypatch.setattr(planner, "_MAX_LOCAL_N_REAL", 1 << 8)


def test_engine_serves_polymul_real_on_the_hbm_tier(small_ceiling):
    from repro.launch.engine import ServeEngine
    engine = ServeEngine(max_batch=4)
    bound = engine.register("polymul-real", N)
    assert bound.route == "polymul-real-hbm"
    assert bound.plan.tier == "hbm" and bound.plan.inner_n == 1 << 8
    engine.warmup()
    rng = np.random.default_rng(2**31 + 41)
    kept = []
    for _ in range(10):
        a = rng.standard_normal(N).astype(np.float32)
        b = rng.standard_normal(N).astype(np.float32)
        kept.append((engine.submit("polymul-real", N, (a, b)), a, b))
    stats = engine.run(len(kept))
    got = np.stack([engine.results[rid] for rid, _, _ in kept])
    want = _reference(np.stack([a for _, a, _ in kept]),
                      np.stack([b for _, _, b in kept]))
    assert _rel_err(got, want) < TOL
    bucket = stats["buckets"][f"polymul-real/n={N}"]
    assert bucket["route"] == "polymul-real-hbm"
    # warmup ran outside the engine's recorder: only served batches count
    assert stats["counters"]["hbm.batches"]["count"] == bucket["batches"]
    assert (stats["counters"]["hbm.outer_passes"]["count"]
            == hbm.OUTER_PASSES * bucket["batches"])


def test_paper_length_keeps_the_fused_vmem_kernel():
    from repro.launch import ops
    bound = ops.get_op("polymul-real").bind(1 << 14)
    assert bound.route == "polymul-real-packed"
    assert bound.plan.tier == "local"
