"""The one-device 3-D FFT (``core.fft.ft3d``) and the NPB FT solve on it,
against numpy float64 references at small grids on the CPU; the ``nas-ft``
op through the engine at NPB Class S; its class table; and the
benchmark's own reference and control (``chipbench/ops/nas-ft.py``).

Error measure, of ``fft3_planes`` and of the checksums alike: per grid,
max abs error / max abs reference (the benchmark's ``compare``), worst
grid.

FFT_TOL: float32 rounds each of the three passes by ~6e-8 relative; they
read 1.4e-7 to 2.0e-7 on these seeded grids. 1e-6 leaves five times that.

CHK_TOL: the checksums are complex64 of ~724 (1024 points of a grid
whose mean is 0.5 + 0.5i), and rounding the float64 reference to complex64
alone reads 3.8e-8 to 4.4e-8 at these grids; the solve reads 3.8e-8 to
7.5e-8. 5e-7 leaves over six times that, and sits seven times below the
benchmark's control (every transform in three bf16 passes, 3.6e-6 to
5.6e-6 at these grids) and far below one bf16 pass in the column DFTs
(2.9e-4 to 3.3e-4).
"""
import json
import pathlib
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import REPO
from repro.core.fft import ft3d, hbm, planner
from repro.kernels import fft as kfft
from repro.launch import ops as op_registry
from repro.launch.ops import OpConfigError, OpContext, get_op

FFT_TOL = 1e-6
CHK_TOL = 5e-7
SHAPES = [(8, 16, 128), (16, 16, 128)]
NITER = 3


def _grids(seed, batch, shape):
    rng = np.random.default_rng(seed)
    n = int(np.prod(shape))
    return (rng.random((batch, n), dtype=np.float32)
            + 1j * rng.random((batch, n), dtype=np.float32)
            ).astype(np.complex64)


def _chk_err(got, want):
    got = np.asarray(got, np.complex128)
    return float((np.abs(got - want).max(-1)
                  / np.abs(want).max(-1)).max())


def _solve(u0, shape, niter=NITER):
    cr, ci = ft3d.nas_ft(jnp.asarray(u0), shape=shape, niter=niter,
                         alpha=op_registry.NAS_FT_ALPHA)
    return np.asarray(cr) + 1j * np.asarray(ci)


def _reference(u0, shape, niter=NITER):
    return np.stack([op_registry.nas_ft_reference(g, shape, niter)
                     for g in u0])


@pytest.mark.parametrize("inverse", [False, True])
@pytest.mark.parametrize("shape", SHAPES)
def test_fft3_planes_matches_numpy(shape, inverse):
    x = _grids(2**31 + 3 + inverse, 2, shape)
    x = x - (0.5 + 0.5j)
    yr, yi = ft3d.fft3_planes(jnp.real(x), jnp.imag(x), shape,
                              inverse=inverse)
    got = np.asarray(yr) + 1j * np.asarray(yi)
    f = np.fft.ifftn if inverse else np.fft.fftn
    want = f(x.astype(np.complex128).reshape(2, *shape),
             axes=(1, 2, 3)).reshape(2, -1)
    assert np.abs(got - want).max() / np.abs(want).max() < FFT_TOL


@pytest.mark.parametrize("shape", SHAPES)
def test_nas_ft_matches_float64_reference(shape):
    u0 = _grids(2**31 + 17, 2, shape)
    got = _solve(u0, shape)
    assert got.shape == (2, NITER)
    assert _chk_err(got, _reference(u0, shape)) < CHK_TOL


@pytest.fixture
def bf16_columns(monkeypatch):
    """The column passes' matmuls with bf16 operands (one MXU pass, f32
    accumulation), the x rows unchanged."""
    def mm(x, y):
        return kfft._mm(x.astype(jnp.bfloat16).astype(jnp.float32),
                        y.astype(jnp.bfloat16).astype(jnp.float32))
    jax.clear_caches()
    monkeypatch.setattr(hbm, "_mm", mm)
    yield
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("shape", SHAPES)
def test_bf16_column_pass_fails_the_tolerance(bf16_columns, shape):
    u0 = _grids(2**31 + 19, 1, shape)
    assert _chk_err(_solve(u0, shape), _reference(u0, shape)) \
        > 100 * CHK_TOL


# ---------------------------------------------------------------------------
# The op: class table, route and the engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,shape,niter", [
    (1 << 18, (64, 64, 64), 6), (1 << 23, (128, 256, 256), 6),
    (1 << 25, (256, 256, 512), 20), (1 << 27, (512, 512, 512), 20)],
    ids=["S", "A", "B", "C"])
def test_class_table_binds_each_npb_class(n, shape, niter):
    _, got_shape, got_niter = op_registry.NAS_FT_CLASSES[n]
    assert (got_shape, got_niter) == (shape, niter)
    plan, route = get_op("nas-ft").route(n)
    assert route == "nas-ft-grid"
    assert plan.tier == "grid" and plan.grid == shape


@pytest.mark.parametrize("n,ctx,match", [
    (1 << 20, OpContext(), "is none of them"),
    (1000, OpContext(), "is none of them"),
    (1 << 25, OpContext(verified=True), "op 'nas-ft' has no ABFT"),
], ids=["unknown-n", "not-a-power", "verified"])
def test_class_table_refuses(n, ctx, match):
    with pytest.raises(OpConfigError, match=match):
        get_op("nas-ft").bind(n, ctx)


@pytest.mark.parametrize("shape,match", [
    ((64, 48, 64), "not a power of two"),
    ((64, 64, 1 << 17), "_MAX_LOCAL_N"),
    ((8192, 8, 128), "over the budget"),
])
def test_plan_grid_refuses(shape, match):
    with pytest.raises(ValueError, match=match):
        planner.plan_grid(shape)


def test_engine_serves_nas_ft_at_class_s():
    """NPB Class S (64^3, 6 steps) through register / submit / run, one job
    a dispatch, checked against the float64 reference; the recorder counts
    1 job, 7 transforms and 14 column passes a job."""
    from repro.launch.engine import ServeEngine
    n = 1 << 18
    engine = ServeEngine(max_batch=1, max_pending=4)
    bound = engine.register("nas-ft", n)
    assert bound.route == "nas-ft-grid"
    engine.warmup()
    rng = np.random.default_rng(2**31 + 23)
    kept = [bound.random_payload(rng) for _ in range(2)]
    rids = [engine.submit("nas-ft", n, p) for p in kept]
    stats = engine.run(len(kept))
    got = np.stack([engine.results[rid] for rid in rids])
    assert got.dtype == np.complex64 and got.shape == (2, 6)
    u0 = np.stack(kept)
    assert _chk_err(got, _reference(u0, (64, 64, 64), 6)) < CHK_TOL
    for p, row in zip(kept, got):
        bound.verify(p, row)
    counters = stats["counters"]
    jobs = stats["buckets"][f"nas-ft/n={n}"]["served"]
    # warmup ran outside the engine's recorder: only served jobs count
    assert counters["ft.jobs"]["count"] == jobs == 2
    assert counters["ft.transforms"]["count"] == 7 * jobs
    assert counters["ft.axis_passes"]["count"] == \
        7 * ft3d.AXIS_PASSES * jobs


# ---------------------------------------------------------------------------
# The benchmark's own reference and control (chipbench/ops/nas-ft.py)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def bench():
    if str(REPO) not in sys.path:
        sys.path.insert(0, str(REPO))
    from chipbench import harness
    mod = harness.load_module(harness.HERE / "ops" / "nas-ft.py")
    cfg = json.loads((harness.HERE / "configs"
                      / "npb-ft-classB-512x256x256.json").read_text())
    return harness, mod, cfg


def _small(cfg, shape, niter):
    return {**cfg, "n": int(np.prod(shape)), "shape": list(shape),
            "niter": niter}


def test_bench_reference_is_the_checksum_sum(bench):
    """At a 4 x 8 x 16 grid, the benchmark's reference equals the
    checksums summed point by point from the DFT's definition, and the
    program's own reference (two independent copies agree)."""
    _, mod, cfg = bench
    shape, niter = (4, 8, 16), 2
    cfg = _small(cfg, shape, niter)
    pool = mod.payloads(np.random.default_rng(2**31 + 29), 1, cfg)
    want = mod.reference(pool, [0], cfg)
    u0 = pool[0].astype(np.complex128).reshape(shape)
    grids = np.meshgrid(*(np.arange(m) for m in shape), indexing="ij")
    kbar = [((g + m // 2) % m - m // 2) for g, m in zip(grids, shape)]
    k2 = sum(k.astype(np.float64) ** 2 for k in kbar)
    # U1[k] = sum_r u0[r] exp(-2 pi i k.r / m), k and r over the grid
    phase = sum(np.multiply.outer(g, g2) / m
                for g, g2, m in zip(grids, grids, shape))
    u1 = np.tensordot(np.exp(-2j * np.pi * phase), u0, axes=3)
    direct = []
    for t in range(1, niter + 1):
        v = u1 * np.exp(-4 * cfg["alpha"] * np.pi ** 2 * t * k2)
        total = 0
        for j in range(1, 1025):
            p = (5 * j % shape[0], 3 * j % shape[1], j % shape[2])
            ph = sum(g * q / m for g, q, m in zip(grids, p, shape))
            total += (v * np.exp(2j * np.pi * ph)).sum() / u0.size
        direct.append(total)
    np.testing.assert_allclose(want[0], direct, rtol=1e-12, atol=1e-9)
    np.testing.assert_allclose(
        want[0], op_registry.nas_ft_reference(pool[0], shape, niter),
        rtol=1e-12, atol=1e-9)


@pytest.mark.parametrize("shape", SHAPES)
def test_bench_control_fails_the_cells_limit(bench, shape):
    """The benchmark's control (each axis transform in three bf16 passes)
    comes out not correct by the cell's own limit and decision, where the
    reference itself and the program's solve come out correct."""
    harness, mod, cfg = bench
    cfg = _small(cfg, shape, NITER)
    pool = mod.payloads(np.random.default_rng(2**31 + 31), 2, cfg)
    ops, pools = {"nas-ft": mod}, {"nas-ft": pool}

    def judge(rows):
        checks = harness.compare_rows(cfg, ops, pools,
                                      {"nas-ft": list(enumerate(rows))})
        return harness.verdict(checks, 0)
    want = mod.reference(pool, [0, 1], cfg)
    assert judge(want.astype(np.complex64))
    assert judge(_solve(pool, shape).astype(np.complex64))
    assert not judge(mod.control(pool, [0, 1], cfg).astype(np.complex64))
