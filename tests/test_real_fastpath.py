"""Real-Hermitian fast path: half-spectrum rfft/irfft kernels, the real
polymul, the planner's real tier, and the serve route.

Contract layers pinned here:
  * kernel parity: ``rfft_planes`` vs ``np.fft.rfft`` at fp32 tolerance,
    ``irfft(rfft(x)) == x`` round-trips, odd/even batch padding edges;
  * the EXACT Hermitian symmetry of ``hermitian_split`` (bitwise ``==``),
    the two-for-one split of ``kops.realpack_fft``;
  * ``polymul_real`` vs the schoolbook circular product up to n = 4096;
  * planner: ``plan(n, b, real=True)`` returns the doubled batch block and
    the real tier; exact+real is rejected;
  * serve: ``--op polymul-real`` actually selects the real route (plan and
    kernel), instead of silently aliasing the complex lambda (regression).
"""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import fft as fft_core
from repro.kernels import fft as kfft
from repro.kernels import ops as kops
from repro.kernels import polymul as kpoly


def _unpack_to_numpy(yr, yi):
    """Packed-Nyquist planes -> np.fft.rfft layout (n/2+1 complex bins)."""
    yr = np.asarray(yr)
    yi = np.asarray(yi)
    zero = np.zeros_like(yr[..., :1])
    re = np.concatenate([yr, yi[..., :1]], axis=-1)
    im = np.concatenate([zero, yi[..., 1:], zero], axis=-1)
    return re + 1j * im


def _circular_schoolbook(a, b):
    """O(n^2)-equivalent circular product oracle (linear convolve + fold)."""
    n = a.shape[-1]
    out = np.empty_like(a)
    for i in range(a.shape[0]):
        full = np.convolve(a[i], b[i])
        out[i] = full[:n]
        out[i, :n - 1] += full[n:]
    return out


# ---------------------------------------------------------------------------
# Kernel tier
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [8, 64, 1024])
@pytest.mark.parametrize("block_b", [4, 1])
@pytest.mark.parametrize("batch", [1, 2, 5])
def test_rfft_kernel_matches_numpy(rng, n, block_b, batch):
    x = rng.standard_normal((batch, n)).astype(np.float32)
    yr, yi = kfft.rfft_planes(jnp.asarray(x), block_b=block_b)
    assert yr.shape == yi.shape == (batch, n // 2)   # half-width planes
    np.testing.assert_allclose(_unpack_to_numpy(yr, yi), np.fft.rfft(x),
                               rtol=1e-4, atol=1e-4 * np.sqrt(n))


@pytest.mark.parametrize("n", [16, 256])
@pytest.mark.parametrize("block_b", [4, 1])
@pytest.mark.parametrize("batch", [1, 3, 4])
def test_irfft_rfft_roundtrip_kernel(rng, n, block_b, batch):
    """irfft(rfft(x)) == x, including odd batches through the block
    padding path."""
    x = rng.standard_normal((batch, n)).astype(np.float32)
    yr, yi = kfft.rfft_planes(jnp.asarray(x), block_b=block_b)
    back = kfft.irfft_planes(yr, yi, block_b=block_b)
    np.testing.assert_allclose(np.asarray(back), x, rtol=1e-4, atol=1e-4 * n)


def test_hermitian_split_exact_symmetry(rng):
    """The split spectra are EXACTLY Hermitian (bitwise), not just close:
    each mirrored component is the same float expression."""
    n = 64
    zr = jnp.asarray(rng.standard_normal((3, n)), jnp.float32)
    zi = jnp.asarray(rng.standard_normal((3, n)), jnp.float32)
    ar, ai, br, bi = (np.asarray(v) for v in kops.hermitian_split(zr, zi))
    for sr, si in ((ar, ai), (br, bi)):
        mirror_r = np.roll(sr[:, ::-1], 1, axis=1)   # S_{n-k}.re
        mirror_i = np.roll(si[:, ::-1], 1, axis=1)
        assert (sr == mirror_r).all()
        assert (si == -mirror_i).all()


def test_real_mode_batch_block_doubles():
    for n in (1024, 4096, 16384):
        assert (kfft.plan_batch_block(n, real=True)
                == 2 * kfft.plan_batch_block(n))


@pytest.mark.parametrize("n", [64, 512, 4096])
def test_polymul_real_kernel_vs_schoolbook(rng, n):
    a = rng.standard_normal((2, n)).astype(np.float32)
    b = rng.standard_normal((2, n)).astype(np.float32)
    c = kpoly.polymul_real_planes(jnp.asarray(a), jnp.asarray(b), block_b=2)
    want = _circular_schoolbook(a.astype(np.float64), b.astype(np.float64))
    np.testing.assert_allclose(np.asarray(c), want, rtol=1e-3, atol=1e-4 * n)


@pytest.mark.parametrize("batch", [1, 2, 3, 5, 8])
def test_polymul_real_batch_padding_edges(rng, batch):
    """Odd batches pair the tail row with zero padding; results must be
    identical to the per-row product."""
    n = 128
    a = rng.standard_normal((batch, n)).astype(np.float32)
    b = rng.standard_normal((batch, n)).astype(np.float32)
    c = kpoly.polymul_real_planes(jnp.asarray(a), jnp.asarray(b), block_b=4)
    want = np.fft.ifft(np.fft.fft(a) * np.fft.fft(b)).real
    np.testing.assert_allclose(np.asarray(c), want, rtol=1e-3, atol=1e-4 * n)


# ---------------------------------------------------------------------------
# Ops tier (public rfft/irfft/polymul_real, both backends)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_ops_rfft_matches_numpy(rng, backend):
    x = rng.standard_normal((2, 3, 128)).astype(np.float32)
    got = np.asarray(kops.rfft(jnp.asarray(x), backend=backend))
    np.testing.assert_allclose(got, np.fft.rfft(x), rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("packed", [False, True])
def test_ops_irfft_roundtrip(rng, backend, packed):
    x = rng.standard_normal((5, 64)).astype(np.float32)
    h = kops.rfft(jnp.asarray(x), backend=backend, packed=packed)
    back = np.asarray(kops.irfft(h, backend=backend, packed=packed))
    np.testing.assert_allclose(back, x, rtol=1e-3, atol=1e-3)


def test_ops_rfft_rejects_complex(rng):
    with pytest.raises(TypeError):
        kops.rfft(jnp.ones((2, 8), jnp.complex64))
    with pytest.raises(TypeError):
        kops.polymul_real(jnp.ones((2, 8), jnp.complex64),
                          jnp.ones((2, 8), jnp.complex64))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_ops_polymul_real_linear(rng, backend):
    n = 32
    a = rng.standard_normal((2, n)).astype(np.float32)
    b = rng.standard_normal((2, n)).astype(np.float32)
    c = np.asarray(kops.polymul_real(jnp.asarray(a), jnp.asarray(b),
                                     mode="linear", backend=backend))
    want = np.zeros((2, 2 * n))
    for i in range(2):
        want[i, :2 * n - 1] = np.convolve(a[i], b[i])
    np.testing.assert_allclose(c, want, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# Property tests
# ---------------------------------------------------------------------------

_n_strategy = st.sampled_from([8, 16, 64, 256])
_seed_strategy = st.integers(min_value=0, max_value=2**31 - 1)


@settings(max_examples=15, deadline=None)
@given(n=_n_strategy, seed=_seed_strategy)
def test_property_irfft_rfft_identity(n, seed):
    r = np.random.default_rng(seed)
    x = r.standard_normal((2, n)).astype(np.float32)
    yr, yi = kfft.rfft_planes(jnp.asarray(x), block_b=2)
    back = np.asarray(kfft.irfft_planes(yr, yi, block_b=2))
    np.testing.assert_allclose(back, x, rtol=1e-4, atol=1e-4 * n)


@settings(max_examples=15, deadline=None)
@given(n=_n_strategy, seed=_seed_strategy)
def test_property_rfft_parity(n, seed):
    r = np.random.default_rng(seed)
    x = r.standard_normal((3, n)).astype(np.float32)
    yr, yi = kfft.rfft_planes(jnp.asarray(x), block_b=4)
    np.testing.assert_allclose(_unpack_to_numpy(yr, yi), np.fft.rfft(x),
                               rtol=1e-3, atol=1e-3 * np.sqrt(n))


@settings(max_examples=10, deadline=None)
@given(n=st.sampled_from([8, 32, 128]), seed=_seed_strategy)
def test_property_polymul_real_vs_schoolbook(n, seed):
    r = np.random.default_rng(seed)
    a = r.standard_normal((2, n)).astype(np.float32)
    b = r.standard_normal((2, n)).astype(np.float32)
    c = np.asarray(kpoly.polymul_real_planes(jnp.asarray(a), jnp.asarray(b),
                                             block_b=2))
    want = _circular_schoolbook(a.astype(np.float64), b.astype(np.float64))
    np.testing.assert_allclose(c, want, rtol=1e-3, atol=1e-3 * n)


# ---------------------------------------------------------------------------
# Planner real tier
# ---------------------------------------------------------------------------

def test_planner_real_tier_doubled_block():
    for n in (1024, 4096):
        pr = fft_core.plan(n, batch=64, real=True)
        pc = fft_core.plan(n, batch=64)
        assert pr.real and not pc.real
        assert pr.tier == "local"
        assert pr.block_b == 2 * pc.block_b
        assert "real-packed" in pr.describe()


def test_planner_real_tier_local_ceiling_matches_complex():
    """The real tier's local-n ceiling equals the complex tier's: both are
    the largest n whose kernels compile for v5e (tests/test_tpu_compile.py
    compiles at the ceiling). At the ceiling the real block fits the VMEM
    budget. (The batch BLOCK doubles; the ceiling does not.)"""
    from repro.core.fft import planner
    from repro.kernels.fft import (VMEM_BUDGET_BYTES, block_vmem_bytes,
                                   plan_batch_block)
    n_edge = planner._MAX_LOCAL_N_REAL
    assert n_edge == planner._MAX_LOCAL_N
    p = fft_core.plan(n_edge, 1, real=True, model_shards=4)
    assert p.tier == "local"
    blk = plan_batch_block(n_edge, real=True)
    assert blk >= 2
    assert block_vmem_bytes(n_edge, blk, real=True) <= VMEM_BUDGET_BYTES
    assert fft_core.plan(2 * n_edge, 1, real=True,
                         model_shards=4).tier == "distributed"
    assert fft_core.plan(2 * planner._MAX_LOCAL_N, 1,
                         model_shards=4).tier == "distributed"


def test_planner_real_tier_above_the_ceiling_on_one_device_is_hbm():
    """Above the VMEM ceiling one device no longer gets a local plan whose
    kernel cannot compile: a real plan is the hbm tier (rows of at most
    the ceiling, at least eight of them), a complex plan raises."""
    from repro.core.fft import planner
    for n in (2 * planner._MAX_LOCAL_N, 1 << 19, 1 << 22):
        p = fft_core.plan(n, 16, real=True)
        assert p.tier == "hbm" and p.real and p.seq_shards == 1
        assert p.inner_n <= planner._MAX_LOCAL_N
        assert n // p.inner_n >= planner._HBM_MIN_ROWS
        assert p.block_b == kfft.plan_batch_block(p.inner_n)
        assert "HBM-resident" in p.describe()
    with pytest.raises(ValueError, match="_MAX_LOCAL_N"):
        fft_core.plan(1 << 19, 16)
    assert fft_core.plan(planner._MAX_LOCAL_N, 1, real=True).tier == "local"


def test_rfft_above_the_ceiling_on_one_device_is_refused():
    from repro.launch.ops import OpConfigError, get_op
    with pytest.raises(OpConfigError, match="polymul-real only"):
        get_op("rfft").bind(1 << 19)


def test_planner_rejects_exact_real_combo():
    with pytest.raises(ValueError):
        fft_core.plan(1024, batch=8, exact=True, real=True)


# ---------------------------------------------------------------------------
# Serve route regression: polymul-real must NOT alias the complex lambda
# ---------------------------------------------------------------------------

def test_serve_polymul_real_route_selected(rng):
    from repro.launch.serve import FFTService
    svc = FFTService(256, 4, "polymul-real")
    # Route + plan: the real tier is actually selected.
    assert svc.route == "polymul-real-packed"
    assert svc.plan is not None and svc.plan.real
    assert svc.plan.block_b == 2 * fft_core.plan(256, 4).block_b
    # The complex endpoint stays complex.
    cplx = FFTService(256, 4, "polymul")
    assert not cplx.plan.real and cplx.route == "polymul"
    # And the real route computes the right thing.
    a = rng.standard_normal((4, 256)).astype(np.float32)
    b = rng.standard_normal((4, 256)).astype(np.float32)
    got = np.asarray(svc._fn(jnp.asarray(a), jnp.asarray(b)))
    want = np.fft.ifft(np.fft.fft(a) * np.fft.fft(b)).real
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-2)
    assert not np.iscomplexobj(got)


def test_serve_rfft_route(rng):
    from repro.launch.serve import FFTService
    svc = FFTService(128, 4, "rfft")
    assert svc.plan.real and svc.route == "rfft-real"
    x = rng.standard_normal((4, 128)).astype(np.float32)
    # the route returns float32 words; ``to_numpy`` views them as complex
    got = svc.bound.to_numpy(svc._fn(jnp.asarray(x)))
    assert got.dtype == np.complex64 and got.shape == (4, 65)
    np.testing.assert_allclose(got, np.fft.rfft(x), rtol=1e-3, atol=1e-3)
