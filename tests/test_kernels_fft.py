"""Pallas FFT / polymul kernels vs. pure-jnp oracles (interpret mode on CPU).

Per-kernel shape x dtype sweeps + hypothesis property tests on the system's
mathematical invariants (linearity, Parseval, convolution theorem, Eq. (10)).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.kernels import fft as kfft
from repro.kernels import ops as kops
from repro.kernels import polymul as kpoly
from repro.kernels import ref


def _rand_complex(rng, shape):
    return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape))


def _planes(x):
    return (jnp.asarray(x.real, jnp.float32), jnp.asarray(x.imag, jnp.float32))


def _join(yr, yi):
    return np.asarray(yr) + 1j * np.asarray(yi)


# ---------------------------------------------------------------------------
# Shape / dtype / batch-block sweep vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [8, 32, 128, 1024])
@pytest.mark.parametrize("block_b", [None, 2])
@pytest.mark.parametrize("batch", [1, 3])
def test_fft_kernel_matches_numpy(rng, n, block_b, batch):
    x = _rand_complex(rng, (batch, n))
    yr, yi = kfft.fft_planes(*_planes(x), block_b=block_b)
    want = np.fft.fft(x)
    np.testing.assert_allclose(_join(yr, yi), want,
                               rtol=1e-4, atol=1e-4 * np.sqrt(n))


@pytest.mark.parametrize("n", [16, 256])
@pytest.mark.parametrize("block_b", [None, 2])
def test_ifft_kernel_roundtrip(rng, n, block_b):
    x = _rand_complex(rng, (4, n))
    yr, yi = kfft.fft_planes(*_planes(x), block_b=block_b)
    zr, zi = kfft.fft_planes(yr, yi, inverse=True, block_b=block_b)
    np.testing.assert_allclose(_join(zr, zi), x, rtol=1e-4, atol=1e-5 * n)


@pytest.mark.parametrize("n", [64])
def test_fft_kernel_bf16(rng, n):
    x = _rand_complex(rng, (2, n))
    xr = jnp.asarray(x.real, jnp.bfloat16)
    xi = jnp.asarray(x.imag, jnp.bfloat16)
    yr, yi = kfft.fft_planes(xr, xi)
    want = np.fft.fft(x)
    got = np.asarray(yr, np.float32) + 1j * np.asarray(yi, np.float32)
    # bf16 storage, fp32 compute: ~2-3 decimal digits
    np.testing.assert_allclose(got, want, rtol=0.15, atol=0.15 * np.sqrt(n))


def test_fft_kernel_nondivisible_batch(rng):
    """Batch not a multiple of the block: wrapper pads and strips."""
    x = _rand_complex(rng, (5, 64))
    yr, yi = kfft.fft_planes(*_planes(x), block_b=4)
    np.testing.assert_allclose(_join(yr, yi), np.fft.fft(x), rtol=1e-4,
                               atol=1e-3)


@pytest.mark.parametrize("oracle", [ref.dft, ref.fft_recursive,
                                    ref.fft_stockham])
def test_oracles_agree(rng, oracle):
    """The three independent references agree with numpy."""
    x = _rand_complex(rng, (2, 64))
    got = np.asarray(oracle(jnp.asarray(x, jnp.complex64)))
    np.testing.assert_allclose(got, np.fft.fft(x), rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# Fused polymul kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [8, 64, 512])
@pytest.mark.parametrize("block_b", [None, 2])
def test_polymul_complex_kernel(rng, n, block_b):
    a = _rand_complex(rng, (3, n))
    b = _rand_complex(rng, (3, n))
    cr, ci = kpoly.polymul_complex_planes(*_planes(a), *_planes(b),
                                          block_b=block_b)
    want = np.fft.ifft(np.fft.fft(a) * np.fft.fft(b))
    np.testing.assert_allclose(_join(cr, ci), want, rtol=1e-3,
                               atol=1e-4 * n)


@pytest.mark.parametrize("n", [8, 64, 512])
def test_polymul_real_kernel(rng, n):
    a = rng.standard_normal((3, n))
    b = rng.standard_normal((3, n))
    c = kpoly.polymul_real_planes(jnp.asarray(a, jnp.float32),
                                  jnp.asarray(b, jnp.float32))
    want = np.fft.ifft(np.fft.fft(a) * np.fft.fft(b)).real
    np.testing.assert_allclose(np.asarray(c), want, rtol=1e-3, atol=1e-4 * n)


def test_polymul_linear_matches_direct_convolution(rng):
    """ops.polymul(mode='linear') == coefficient convolution (paper Eq. 9)."""
    n = 32
    a = rng.standard_normal((2, n))
    b = rng.standard_normal((2, n))
    c = kops.polymul(jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32),
                     mode="linear", backend="pallas")
    want = np.zeros((2, 2 * n))
    for i in range(2):
        want[i, :2 * n - 1] = np.convolve(a[i], b[i])
    np.testing.assert_allclose(np.asarray(c), want, rtol=1e-3, atol=1e-3)


def test_realpack_matches_ref(rng):
    n = 64
    x = rng.standard_normal((2, n))
    y = rng.standard_normal((2, n))
    xk, yk = kops.realpack_fft(jnp.asarray(x, jnp.float32),
                               jnp.asarray(y, jnp.float32), backend="xla")
    np.testing.assert_allclose(np.asarray(xk), np.fft.fft(x), rtol=1e-3,
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(yk), np.fft.fft(y), rtol=1e-3,
                               atol=1e-3)


def test_fft_causal_conv(rng):
    T, K = 100, 17  # deliberately not powers of two
    x = rng.standard_normal((3, T)).astype(np.float32)
    k = rng.standard_normal((3, K)).astype(np.float32)
    y = kops.fft_causal_conv(jnp.asarray(x), jnp.asarray(k), backend="xla")
    want = np.stack([np.convolve(x[i], k[i])[:T] for i in range(3)])
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# Property tests (hypothesis)
# ---------------------------------------------------------------------------

_n_strategy = st.sampled_from([8, 16, 64, 128])
_seed_strategy = st.integers(min_value=0, max_value=2**31 - 1)


@settings(max_examples=20, deadline=None)
@given(n=_n_strategy, seed=_seed_strategy, alpha=st.floats(-3, 3),
       beta=st.floats(-3, 3))
def test_fft_linearity(n, seed, alpha, beta):
    r = np.random.default_rng(seed)
    x = _rand_complex(r, (1, n))
    y = _rand_complex(r, (1, n))
    fx = np.asarray(ref.fft_stockham(jnp.asarray(x, jnp.complex64)))
    fy = np.asarray(ref.fft_stockham(jnp.asarray(y, jnp.complex64)))
    fxy = np.asarray(ref.fft_stockham(jnp.asarray(alpha * x + beta * y,
                                                  jnp.complex64)))
    np.testing.assert_allclose(fxy, alpha * fx + beta * fy, rtol=1e-3,
                               atol=1e-3 * n)


@settings(max_examples=20, deadline=None)
@given(n=_n_strategy, seed=_seed_strategy)
def test_parseval(n, seed):
    r = np.random.default_rng(seed)
    x = _rand_complex(r, (1, n))
    fx = np.asarray(ref.fft_stockham(jnp.asarray(x, jnp.complex64)))
    np.testing.assert_allclose(np.sum(np.abs(fx) ** 2) / n,
                               np.sum(np.abs(x) ** 2), rtol=1e-4)


@settings(max_examples=20, deadline=None)
@given(n=_n_strategy, seed=_seed_strategy)
def test_convolution_theorem_vs_direct(n, seed):
    r = np.random.default_rng(seed)
    a = r.standard_normal((1, n))
    b = r.standard_normal((1, n))
    c = np.asarray(kops.polymul(jnp.asarray(a, jnp.float32),
                                jnp.asarray(b, jnp.float32),
                                mode="circular", backend="xla"))
    want = np.fft.ifft(np.fft.fft(a) * np.fft.fft(b)).real
    np.testing.assert_allclose(c, want, rtol=1e-3, atol=1e-3 * n)


@settings(max_examples=20, deadline=None)
@given(n=_n_strategy, seed=_seed_strategy)
def test_realpack_identity(n, seed):
    """Eq. (10): packing two real FFTs into one complex FFT is exact."""
    r = np.random.default_rng(seed)
    x = r.standard_normal((1, n))
    y = r.standard_normal((1, n))
    xk, yk = ref.realpack_fft_ref(jnp.asarray(x, jnp.float32),
                                  jnp.asarray(y, jnp.float32))
    np.testing.assert_allclose(np.asarray(xk), np.fft.fft(x), rtol=1e-3,
                               atol=1e-3 * n)
    np.testing.assert_allclose(np.asarray(yk), np.fft.fft(y), rtol=1e-3,
                               atol=1e-3 * n)


@settings(max_examples=10, deadline=None)
@given(n=st.sampled_from([8, 32]), seed=_seed_strategy)
def test_pallas_kernel_equals_oracle_property(n, seed):
    """Kernel == oracle on random data (the per-kernel allclose contract)."""
    r = np.random.default_rng(seed)
    x = _rand_complex(r, (2, n))
    yr, yi = kfft.fft_planes(*_planes(x))
    want = np.asarray(ref.dft(jnp.asarray(x, jnp.complex64)))
    np.testing.assert_allclose(_join(yr, yi), want, rtol=1e-3, atol=1e-3 * n)


# ---------------------------------------------------------------------------
# Kernel tables and the interpret rule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [8, 64, 2048, 16384])
def test_dft_tables_are_strided_slices_of_master_table(n):
    """Every four-step table entry is bit-equal to the master table's
    entry the old strided in-kernel slices read: F_R rows are the stride-C
    slice W[::C] indexed by k*r mod R, F_C the stride-R slice, T the
    leading entries of W itself."""
    r_, c_ = kfft.split(n)
    for inverse in (False, True):
        w = kfft.twiddle_table(n, inverse=inverse)
        tabs = kfft.dft_tables(n, inverse)
        for plane, (fr, fc, t) in enumerate(
                [(tabs[0], tabs[2], tabs[4]), (tabs[1], tabs[3], tabs[5])]):
            wp = w[plane][0]
            kr, kc = np.arange(r_), np.arange(c_)
            assert np.array_equal(fr, wp[::c_][np.outer(kr, kr) % r_])
            assert np.array_equal(fc, wp[::r_][np.outer(kc, kc) % c_])
            assert np.array_equal(t, wp[np.outer(kr, kc)])
            assert np.array_equal(fr[1], wp[::c_][:r_])


def _kernel_entry_points():
    from repro.core.ntt import rns
    from repro.kernels import attention, ntt
    return {
        "fft_planes": kfft.fft_planes, "rfft_planes": kfft.rfft_planes,
        "irfft_planes": kfft.irfft_planes,
        "polymul_complex_planes": kpoly.polymul_complex_planes,
        "polymul_real_planes": kpoly.polymul_real_planes,
        "ntt_batched": ntt.ntt_batched, "ntt_polymul": ntt.ntt_polymul,
        "rns_ntt_polymul": ntt.rns_ntt_polymul,
        "rns_polymul": rns.rns_polymul,
        "flash_attention": attention.flash_attention,
    }


@pytest.mark.parametrize("name", sorted(_kernel_entry_points()))
def test_kernel_entry_point_interpret_defaults_to_backend_rule(name):
    """No public Pallas entry point defaults to the interpreter: the
    default is None, resolved from the backend (compiled on a TPU)."""
    import inspect
    fn = _kernel_entry_points()[name]
    assert inspect.signature(fn).parameters["interpret"].default is None


def test_resolve_interpret_rule():
    assert kfft.resolve_interpret(None) == (jax.default_backend() != "tpu")
    assert kfft.resolve_interpret(False) is False
    assert kfft.resolve_interpret(True) is True


# ---------------------------------------------------------------------------
# Planner contract: loud rejection + the exact (NTT) route
# ---------------------------------------------------------------------------

def test_planner_rejects_non_power_of_two():
    """plan() must raise, not silently mis-plan (asserts vanish under -O)."""
    from repro.core import fft as fcore
    for bad in (48, 0, -8, 1536):
        with pytest.raises(ValueError):
            fcore.plan(bad, batch=8)
    with pytest.raises(ValueError):
        fcore.plan(1024, batch=-1)


def test_planner_exact_route():
    from repro.core import fft as fcore
    p = fcore.plan(4096, batch=64, exact=True)
    assert p.exact and p.tier == "local"
    assert "NTT" in p.describe()
    # the float route is unchanged by the new field
    f = fcore.plan(4096, batch=64)
    assert not f.exact


def test_ops_ifft_roundtrip_both_backends(rng):
    """Inverse-transform round-trip through the public ops layer."""
    x = _rand_complex(rng, (3, 128)).astype(np.complex64)
    for backend in ("xla", "pallas"):
        y = kops.fft(jnp.asarray(x), backend=backend)
        z = np.asarray(kops.ifft(y, backend=backend))
        np.testing.assert_allclose(z, x, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# 2-D extension (signal processing application of the paper's primitive)
# ---------------------------------------------------------------------------

def test_fft2_matches_numpy(rng):
    x = _rand_complex(rng, (2, 16, 32))
    got = np.asarray(kops.fft2(jnp.asarray(x, jnp.complex64), backend="xla"))
    np.testing.assert_allclose(got, np.fft.fft2(x), rtol=1e-3, atol=1e-3)
    back = np.asarray(kops.fft2(jnp.asarray(got), inverse=True,
                                backend="xla"))
    np.testing.assert_allclose(back, x, rtol=1e-3, atol=1e-4)


def test_fft_conv2d_matches_direct(rng):
    H, W, kh, kw = 12, 20, 3, 5
    img = rng.standard_normal((2, H, W)).astype(np.float32)
    kern = rng.standard_normal((kh, kw)).astype(np.float32)
    got = np.asarray(kops.fft_conv2d(jnp.asarray(img), jnp.asarray(kern),
                                     backend="xla"))
    # direct 'same' convolution reference
    want = np.zeros_like(img)
    pi = np.pad(img, ((0, 0), (kh // 2, kh // 2), (kw // 2, kw // 2)))
    for r in range(kh):
        for c in range(kw):
            want += kern[r, c] * pi[:, kh - 1 - r:kh - 1 - r + H,
                                    kw - 1 - c:kw - 1 - c + W][..., ::1]
    # convolution flips the kernel relative to correlation
    want2 = np.zeros_like(img)
    for r in range(kh):
        for c in range(kw):
            want2 += kern[r, c] * pi[:, r:r + H, c:c + W]
    close1 = np.allclose(got, want, rtol=1e-3, atol=1e-3)
    close2 = np.allclose(got, want2, rtol=1e-3, atol=1e-3)
    assert close1 or close2
