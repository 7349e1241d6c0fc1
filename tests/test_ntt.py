"""Exact-NTT tier: reference properties, modulus selection, and
Pallas-kernel-vs-reference BIT-EXACT equality (the crypto contract — a
single wrong residue breaks an RLWE pipeline, so every comparison here is
``==``, never allclose)."""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core.ntt import ref
from repro.kernels import ntt as kntt


def _params(n, bits=30):
    return ref.NTTParams.make(n, bits=bits)


def _naive_negacyclic(a, b, q):
    """Independent pure-python O(n^2) oracle (no numpy, no roots)."""
    n = len(a)
    out = [0] * n
    for i in range(n):
        for j in range(n):
            k = i + j
            t = int(a[i]) * int(b[j]) % q
            if k < n:
                out[k] = (out[k] + t) % q
            else:
                out[k - n] = (out[k - n] - t) % q
    return np.array(out, np.uint64)


# ---------------------------------------------------------------------------
# Modulus / root selection
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [256, 1024, 4096])
def test_modulus_selection_rules(n):
    q = ref.choose_modulus(n)
    assert ref.is_prime(q)
    assert q % (2 * n) == 1          # 2n-th roots of unity exist
    assert q < 1 << 31               # single uint32 word, 2q < 2^32
    p = _params(n)
    assert p.q == q
    # w is a primitive n-th root, psi a primitive 2n-th root with psi^2 = w
    assert pow(p.w, n, q) == 1 and pow(p.w, n // 2, q) != 1
    assert p.psi * p.psi % q == p.w
    assert pow(p.psi, n, q) == q - 1          # psi^n = -1: the negacyclic sign
    assert p.n_inv * n % q == 1
    assert (p.qinv * q) % (1 << 32) == (1 << 32) - 1   # -q^-1 mod 2^32


def test_param_validation_raises():
    with pytest.raises(ValueError):
        ref.NTTParams.make(48)                 # non-power-of-two
    with pytest.raises(ValueError):
        ref.NTTParams.make(256, q=257)         # 257 != 1 mod 512
    with pytest.raises(ValueError):
        ref.NTTParams.make(256, q=3 * 2048 + 1)  # 6145 = 5*1229, composite
    with pytest.raises(TypeError):
        ref.ntt(np.ones(256, np.float32), _params(256))   # floats rejected


# ---------------------------------------------------------------------------
# Reference properties (hypothesis, via tests/_hypothesis_fallback.py when
# the real library is absent)
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(n=st.sampled_from([256, 512, 1024]),
       bits=st.sampled_from([20, 24, 30]),
       seed=st.integers(0, 2**31 - 1))
def test_ref_roundtrip_property(n, bits, seed):
    """intt(ntt(x)) == x over random moduli and sizes, exactly."""
    p = _params(n, bits=bits)
    r = np.random.default_rng(seed)
    x = r.integers(0, p.q, size=(2, n))
    assert (ref.intt(ref.ntt(x, p), p) == x.astype(np.uint64)).all()


@settings(max_examples=10, deadline=None)
@given(n=st.sampled_from([64, 128]), seed=st.integers(0, 2**31 - 1))
def test_ref_negacyclic_vs_schoolbook_property(n, seed):
    p = _params(n)
    r = np.random.default_rng(seed)
    a = r.integers(0, p.q, size=n)
    b = r.integers(0, p.q, size=n)
    want = ref.schoolbook_polymul(a, b, p.q, negacyclic=True)
    assert (ref.negacyclic_polymul(a, b, p) == want).all()


@settings(max_examples=10, deadline=None)
@given(n=st.sampled_from([64, 128]), seed=st.integers(0, 2**31 - 1))
def test_ref_cyclic_vs_schoolbook_property(n, seed):
    p = _params(n)
    r = np.random.default_rng(seed)
    a = r.integers(0, p.q, size=n)
    b = r.integers(0, p.q, size=n)
    want = ref.schoolbook_polymul(a, b, p.q, negacyclic=False)
    assert (ref.cyclic_polymul(a, b, p) == want).all()


def test_ref_linearity_mod_q(rng):
    """NTT is F_q-linear: ntt(c1 a + c2 b) == c1 ntt(a) + c2 ntt(b)."""
    n = 256
    p = _params(n)
    q = np.uint64(p.q)
    a = rng.integers(0, p.q, size=n)
    b = rng.integers(0, p.q, size=n)
    c1, c2 = np.uint64(17), np.uint64(3001)
    lhs = ref.ntt((c1 * a.astype(np.uint64) + c2 * b.astype(np.uint64)) % q, p)
    rhs = (c1 * ref.ntt(a, p) + c2 * ref.ntt(b, p)) % q
    assert (lhs == rhs).all()


def test_schoolbook_sign_wraparound():
    """x^(n-1) * x = x^n = -1 mod x^n+1 (+1 in the cyclic ring)."""
    n = 8
    p = _params(n)
    a = np.zeros(n, np.uint64)
    b = np.zeros(n, np.uint64)
    a[n - 1] = 1
    b[1] = 1
    nega = ref.negacyclic_polymul(a, b, p)
    assert nega[0] == p.q - 1 and (nega[1:] == 0).all()
    cyc = ref.cyclic_polymul(a, b, p)
    assert cyc[0] == 1 and (cyc[1:] == 0).all()


def test_naive_oracle_agrees_with_schoolbook(rng):
    n = 32
    p = _params(n)
    a = rng.integers(0, p.q, size=n)
    b = rng.integers(0, p.q, size=n)
    assert (ref.schoolbook_polymul(a, b, p.q, negacyclic=True)
            == _naive_negacyclic(a, b, p.q)).all()


# ---------------------------------------------------------------------------
# Pallas kernel vs reference: bit-exact, n in {256..4096}
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [256, 1024, 4096])
@pytest.mark.parametrize("inverse", [False, True])
def test_kernel_matches_ref_exactly(rng, n, inverse):
    p = _params(n)
    x = rng.integers(0, p.q, size=(3, n)).astype(np.uint32)
    got = np.asarray(kntt.ntt_batched(jnp.asarray(x), p, inverse=inverse))
    want = (ref.intt if inverse else ref.ntt)(x, p)
    assert (got == want.astype(np.uint32)).all()


@pytest.mark.parametrize("n", [256, 1024, 4096])
def test_kernel_roundtrip_exact(rng, n):
    p = _params(n)
    x = rng.integers(0, p.q, size=(2, n)).astype(np.uint32)
    f = kntt.ntt_batched(jnp.asarray(x), p)
    back = np.asarray(kntt.ntt_batched(f, p, inverse=True))
    assert (back == x).all()


@pytest.mark.parametrize("n", [256, 1024, 4096])
@pytest.mark.parametrize("negacyclic", [True, False])
def test_kernel_polymul_matches_ref_exactly(rng, n, negacyclic):
    p = _params(n)
    a = rng.integers(0, p.q, size=(2, n)).astype(np.uint32)
    b = rng.integers(0, p.q, size=(2, n)).astype(np.uint32)
    got = np.asarray(kntt.ntt_polymul(jnp.asarray(a), jnp.asarray(b), p,
                                      negacyclic=negacyclic))
    fn = ref.negacyclic_polymul if negacyclic else ref.cyclic_polymul
    assert (got == fn(a, b, p).astype(np.uint32)).all()


def test_kernel_polymul_matches_schoolbook(rng):
    """End to end vs the O(n^2) oracle — no transform code shared at all."""
    n = 256
    p = _params(n)
    a = rng.integers(0, p.q, size=(2, n)).astype(np.uint32)
    b = rng.integers(0, p.q, size=(2, n)).astype(np.uint32)
    got = np.asarray(kntt.ntt_polymul(jnp.asarray(a), jnp.asarray(b), p))
    want = ref.schoolbook_polymul(a, b, p.q, negacyclic=True)
    assert (got == want.astype(np.uint32)).all()


def test_kernel_nondivisible_batch(rng):
    """Batch not a multiple of the block: wrapper pads and strips."""
    n = 256
    p = _params(n)
    x = rng.integers(0, p.q, size=(5, n)).astype(np.uint32)
    got = np.asarray(kntt.ntt_batched(jnp.asarray(x), p, block_b=4))
    assert (got == ref.ntt(x, p).astype(np.uint32)).all()


def test_kernel_rejects_float_input():
    p = _params(256)
    with pytest.raises(TypeError):
        kntt.ntt_batched(jnp.zeros((2, 256), jnp.float32), p)


def test_kernel_reduces_unreduced_input(rng):
    """Signed / >= q integer coefficients must reduce mod q, matching the
    reference — not wrap through uint32 (regression: the kernel once cast
    without reducing, silently corrupting unreduced RLWE input)."""
    n = 256
    p = _params(n)
    signed = rng.integers(-(p.q - 1), p.q, size=(2, n)).astype(np.int32)
    got = np.asarray(kntt.ntt_batched(jnp.asarray(signed), p))
    assert (got == ref.ntt(signed, p).astype(np.uint32)).all()
    big = (rng.integers(0, p.q, size=(2, n)).astype(np.uint32)
           + np.uint32(p.q))          # in [q, 2q): valid uint32, unreduced
    got_big = np.asarray(kntt.ntt_batched(jnp.asarray(big), p))
    assert (got_big == ref.ntt(big, p).astype(np.uint32)).all()


@settings(max_examples=8, deadline=None)
@given(n=st.sampled_from([64, 256]), seed=st.integers(0, 2**31 - 1))
def test_kernel_equals_ref_property(n, seed):
    p = _params(n)
    r = np.random.default_rng(seed)
    x = r.integers(0, p.q, size=(2, n)).astype(np.uint32)
    got = np.asarray(kntt.ntt_batched(jnp.asarray(x), p))
    assert (got == ref.ntt(x, p).astype(np.uint32)).all()


@pytest.mark.parametrize("n", [8, 256, 4096])
def test_stage_tables_are_strided_slices_of_master_table(n):
    """Stage d's twiddle at positions i < d is the old strided slice
    W[::n/(2d)][:d] of the master Montgomery table, bit for bit; the DIF
    tables carry the same values at positions with bit d set and
    Montgomery one elsewhere."""
    from repro.core.ntt.distributed import _master_table
    from repro.kernels.fft import split
    p = _params(n)
    r_, c_ = split(n)
    n_rows = r_.bit_length() - 1
    one = np.uint32(p.to_montgomery(np.ones(1, np.uint64))[0])
    for base in (p.w, p.w_inv):
        w = np.asarray(_master_table(p, base))[0]
        dit = kntt.stage_tables(p, base, False)
        dif = kntt.stage_tables(p, base, True)
        for s_, d in enumerate(kntt._distances(n)):
            if d >= c_:
                t_dit, t_dif = dit[0][s_].ravel(), dif[0][s_].ravel()
            else:
                t_dit = np.tile(dit[1][s_ - n_rows].ravel(), r_)
                t_dif = np.tile(dif[1][s_ - n_rows].ravel(), r_)
            assert np.array_equal(t_dit[:d], w[::n // (2 * d)][:d])
            upper = (np.arange(n) & d) != 0
            assert np.array_equal(t_dif[upper], t_dit[upper])
            assert (t_dif[~upper] == one).all()
