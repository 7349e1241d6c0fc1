"""RNS/CRT differential tier: the multi-limb exact-polymul contract.

Everything here is ``==``, never allclose — a single wrong residue breaks
an RLWE/FHE pipeline. Three differential layers pin each other:

  big-int schoolbook (pure python, no transforms, no CRT)
    == rns_polymul_reference (numpy NTT per limb + Garner/CRT)
    == rns_polymul (limb-batched Pallas kernel, ONE launch for all limbs)

plus the CRT algebra itself (round-trip identity, limb-permutation
invariance, uint64 Garner == object-dtype oracle), the planner's exact
distributed route, and the first cross-stack differential: float-FFT
polymul vs exact-NTT polymul on small-coefficient inputs.
"""
import dataclasses

import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import fft as fft_core
from repro.core.ntt import ref, rns


def _rns(n, bits):
    return rns.RNSParams.make(n, modulus_bits=bits)


# ---------------------------------------------------------------------------
# Limb selection rules
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [40, 100, 120])
def test_limb_selection_rules(bits):
    n = 256
    r = _rns(n, bits)
    # Every limb is a distinct NTT-friendly prime < 2^30 (hence coprime).
    assert len(set(r.qs)) == r.k
    for q in r.qs:
        assert ref.is_prime(q) and q % (2 * n) == 1 and q < 1 << 30
    # Q >= the requested width; the limb product covers the exact-lift bound.
    assert r.modulus.bit_length() >= bits
    assert r.limb_product > 2 * n * r.modulus ** 2
    # Q >= 2^100 needs >= 4 limbs of <= 30 bits — the acceptance floor.
    if bits >= 100:
        assert r.k >= 4


def test_rns_params_validation():
    with pytest.raises(ValueError):
        rns.RNSParams.make(256)                       # neither Q nor bits
    with pytest.raises(ValueError):
        rns.RNSParams.make(256, modulus=97, modulus_bits=40)   # both
    with pytest.raises(ValueError):
        rns.RNSParams.make(255, modulus_bits=40)      # non-power-of-two n
    with pytest.raises(TypeError):
        rns.to_rns(np.ones(8, np.float32), _rns(8, 40))  # floats rejected


# ---------------------------------------------------------------------------
# CRT algebra (hypothesis, deterministic fallback when the lib is absent)
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(bits=st.sampled_from([40, 70, 100, 120]),
       seed=st.integers(0, 2**31 - 1))
def test_crt_roundtrip_identity_property(bits, seed):
    """to_rns -> Garner/CRT == identity on [0, M), exactly."""
    n = 64
    r = _rns(n, bits)
    rng = np.random.default_rng(seed)
    x = rns.random_poly(rng, n, r.limb_product)   # full CRT range
    back = rns.crt_reconstruct(rns.to_rns(x, r), r)
    assert (back == x).all()


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), perm_seed=st.integers(0, 2**31 - 1))
def test_limb_permutation_invariance_property(seed, perm_seed):
    """CRT reconstruction is invariant under permuting the limb order —
    Garner's mixed-radix digits differ per ordering, the value must not."""
    n = 64
    r = _rns(n, 100)
    rng = np.random.default_rng(seed)
    x = rns.random_poly(rng, n, r.limb_product)
    res = rns.to_rns(x, r)
    perm = np.random.default_rng(perm_seed).permutation(r.k)
    r_perm = dataclasses.replace(r, limbs=tuple(r.limbs[i] for i in perm))
    back = rns.crt_reconstruct(res[perm], r_perm)
    assert (back == x).all()


def test_garner_u64_path_matches_object_oracle(rng):
    """The vectorized uint64 assembly == the python-int path when M < 2^64
    (two 30-bit limbs), and recovers raw uint64 inputs exactly."""
    n = 64
    r = rns.RNSParams.make(n, modulus=65537)      # bound 2^39 -> 2 limbs
    assert r.k == 2 and r.limb_product < 1 << 64
    x = rng.integers(0, r.limb_product, size=(3, n), dtype=np.uint64)
    res = rns.to_rns(x, r)
    u64 = rns.crt_reconstruct_u64(res, r)
    assert (u64 == x).all()
    assert (u64.astype(object) == rns.crt_reconstruct(res, r)).all()
    big = _rns(n, 100)
    with pytest.raises(ValueError):
        rns.crt_reconstruct_u64(rns.to_rns(x, big), big)


def test_centered_lift_recovers_negative_values():
    """crt_to_modulus must treat residue stacks of negative integers as
    negative (centered lift), not as their huge mod-M representatives."""
    n = 8
    r = _rns(n, 60)
    vals = np.array([-5, -1, 0, 1, 7, -(1 << 61), 1 << 61, 3], object)
    out = rns.crt_to_modulus(rns.to_rns(vals, r), r)
    assert (out == np.array([int(v) % r.modulus for v in vals], object)).all()


# ---------------------------------------------------------------------------
# Polymul: schoolbook == reference == fused limb-batched kernel
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(bits=st.sampled_from([60, 100, 120]),
       negacyclic=st.sampled_from([True, False]),
       seed=st.integers(0, 2**31 - 1))
def test_rns_polymul_vs_bigint_schoolbook_property(bits, negacyclic, seed):
    """Kernel product mod Q (up to ~120-bit Q, >= 4 limbs) == the big-int
    O(n^2) oracle — no transforms, no CRT, no numpy shared."""
    n = 64
    r = _rns(n, bits)
    rng = np.random.default_rng(seed)
    a = rns.random_poly(rng, n, r.modulus)
    b = rns.random_poly(rng, n, r.modulus)
    want = rns.schoolbook_polymul_mod(a, b, r.modulus, negacyclic=negacyclic)
    mid = rns.rns_polymul_reference(a, b, r, negacyclic=negacyclic)
    got = rns.rns_polymul(a, b, r, negacyclic=negacyclic)
    assert (mid == want).all()
    assert (got == want).all()


def test_rns_kernel_batched_and_shapes(rng):
    """(B, n) batches through one launch; 1-D convenience shape preserved."""
    n, B = 128, 3
    r = _rns(n, 100)
    a = np.stack([rns.random_poly(rng, n, r.modulus) for _ in range(B)])
    b = np.stack([rns.random_poly(rng, n, r.modulus) for _ in range(B)])
    got = rns.rns_polymul(a, b, r)
    assert got.shape == (B, n)
    for i in range(B):
        want = rns.schoolbook_polymul_mod(a[i], b[i], r.modulus)
        assert (got[i] == want).all()
    one = rns.rns_polymul(a[0], b[0], r)
    assert one.shape == (n,) and (one == got[0]).all()


def test_rns_kernel_single_limb_degenerates_to_ntt_polymul(rng):
    """k == 1 RNS == the plain single-word kernel: same modulus, same
    residues, same launch machinery."""
    from repro.kernels.ntt import ntt_polymul, rns_ntt_polymul
    n = 256
    r = rns.RNSParams.make(n, modulus=17)        # tiny Q: one limb covers it
    assert r.k == 1
    p = r.limbs[0]
    a = rng.integers(0, p.q, size=(2, n)).astype(np.uint32)
    b = rng.integers(0, p.q, size=(2, n)).astype(np.uint32)
    via_rns = np.asarray(rns_ntt_polymul(a[None], b[None], r))[0]
    via_ntt = np.asarray(ntt_polymul(jnp.asarray(a), jnp.asarray(b), p))
    assert (via_rns == via_ntt).all()


@pytest.mark.parametrize("negacyclic", [True, False])
def test_rns_scalar_prefetch_bit_exact(rng, negacyclic):
    """The scalar-prefetch layout (PrefetchScalarGridSpec, per-limb q/qinv/
    r2 resident in SMEM before the body runs — the on-TPU default) is
    bit-identical to the scalar-Ref fallback, and both still match the
    big-int schoolbook oracle. Forced explicitly so interpret mode pins
    BOTH layouts."""
    from repro.kernels.ntt import rns_ntt_polymul
    n, B = 64, 2
    r = _rns(n, 100)
    assert r.k > 1                        # multiple limbs exercise program_id
    ar = np.stack([np.stack([rng.integers(0, p.q, n).astype(np.uint32)
                             for p in r.limbs]) for _ in range(B)], axis=1)
    br = np.stack([np.stack([rng.integers(0, p.q, n).astype(np.uint32)
                             for p in r.limbs]) for _ in range(B)], axis=1)
    fallback = np.asarray(rns_ntt_polymul(
        jnp.asarray(ar), jnp.asarray(br), r, negacyclic=negacyclic,
        scalar_prefetch=False))
    prefetch = np.asarray(rns_ntt_polymul(
        jnp.asarray(ar), jnp.asarray(br), r, negacyclic=negacyclic,
        scalar_prefetch=True))
    assert (fallback == prefetch).all()
    # cross-check one limb against its own single-modulus reference
    from repro.core.ntt.ref import cyclic_polymul, negacyclic_polymul
    fn = negacyclic_polymul if negacyclic else cyclic_polymul
    for li in (0, r.k - 1):
        p = r.limbs[li]
        want = fn(ar[li], br[li], p).astype(np.uint32)
        assert (prefetch[li] == want).all()


# ---------------------------------------------------------------------------
# Q's own basis: products mod Q on Q's primes alone, no lift
# ---------------------------------------------------------------------------

OWN_BITS = [60, 120, 210]


def _foreign_moduli(n):
    """Moduli with a factor outside the limb set: a Mersenne prime, a limb
    prime squared, a limb prime times 3."""
    q0 = next(rns.ntt_limb_primes(n))
    return [(1 << 61) - 1, q0 * q0, 3 * q0]


@pytest.mark.parametrize("bits", OWN_BITS)
def test_own_basis_is_qs_primes(bits):
    """make(modulus_bits=...) builds Q from limb primes: its own basis is
    those limbs, their product is Q, and every access gives one object."""
    r = _rns(64, bits)
    own = r.own_basis
    assert own is not None and own is r.own_basis
    assert own.limb_product == own.modulus == r.modulus
    assert own.n == r.n and own.limbs == r.limbs[:own.k] and own.k < r.k
    assert own.own_basis is own


@pytest.mark.parametrize("negacyclic", [True, False])
@pytest.mark.parametrize("bits", OWN_BITS)
def test_rns_polymul_on_own_basis_matches_oracles(rng, bits, negacyclic):
    """rns_polymul (own basis, kernel) == big-int schoolbook == the
    extended-basis reference with its centered lift."""
    n = 64
    r = _rns(n, bits)
    a = np.stack([rns.random_poly(rng, n, r.modulus) for _ in range(2)])
    b = np.stack([rns.random_poly(rng, n, r.modulus) for _ in range(2)])
    got = rns.rns_polymul(a, b, r, negacyclic=negacyclic)
    for i in range(2):
        want = rns.schoolbook_polymul_mod(a[i], b[i], r.modulus,
                                          negacyclic=negacyclic)
        assert (got[i] == want).all()
        mid = rns.rns_polymul_reference(a[i], b[i], r,
                                        negacyclic=negacyclic)
        assert (mid == want).all()


@pytest.mark.parametrize("which", range(3))
@pytest.mark.parametrize("negacyclic", [True, False])
def test_foreign_factor_keeps_the_extended_basis(rng, which, negacyclic):
    """A Q with a factor outside the limb set has no own basis; the
    extended path (centered lift over M > 2nQ^2) still matches the oracle,
    and the own-basis counter stays silent."""
    from repro import telemetry
    n = 64
    r = rns.RNSParams.make(n, modulus=_foreign_moduli(n)[which])
    assert r.own_basis is None
    a = rns.random_poly(rng, n, r.modulus)
    b = rns.random_poly(rng, n, r.modulus)
    rec = telemetry.Recorder()
    with rec.current():
        got = rns.rns_polymul(a, b, r, negacyclic=negacyclic)
    want = rns.schoolbook_polymul_mod(a, b, r.modulus, negacyclic=negacyclic)
    assert (got == want).all()
    assert "rns.own_basis" not in rec.snapshot()["counters"]
    assert rec.snapshot()["spans"]["rns.crt"][0] == 1


@pytest.mark.parametrize("bits", OWN_BITS)
def test_crt_to_modulus_on_own_basis_is_crt_reconstruct(rng, bits):
    """On a basis whose product is Q, the CRT value is the residue mod Q:
    crt_to_modulus returns crt_reconstruct's value, and it round-trips
    any x in [0, Q)."""
    n = 64
    own = _rns(n, bits).own_basis
    x = np.stack([rns.random_poly(rng, n, own.modulus) for _ in range(3)])
    res = rns.to_rns(x, own)
    got = rns.crt_to_modulus(res, own)
    assert (got == rns.crt_reconstruct(res, own)).all()
    assert (got == x).all()


@pytest.mark.parametrize("bits", OWN_BITS)
def test_own_basis_counts_one_per_batch(rng, bits):
    """Under a Recorder, ``rns.own_basis`` counts each rns_polymul call that
    ran in Q's own basis, next to the spans of the same calls."""
    from repro import telemetry
    n = 64
    r = _rns(n, bits)
    rec = telemetry.Recorder()
    calls = 3
    with rec.current():
        for _ in range(calls):
            a = np.stack([rns.random_poly(rng, n, r.modulus)
                          for _ in range(2)])
            rns.rns_polymul(a, a, r)
    snap = rec.snapshot()
    assert snap["counters"]["rns.own_basis"][0] == calls
    assert snap["spans"]["rns.split"][0] == calls


def test_engine_rns_route_runs_in_own_basis():
    """The served RNS route counts ``rns.own_basis`` once per dispatched
    batch in ``stats()["counters"]``, and its results match the registry's
    extended-basis oracle."""
    from repro.launch.engine import ServeEngine
    engine = ServeEngine(max_batch=2, max_pending=8, modulus_bits=120)
    bound = engine.register("polymul-mod", 64)
    assert bound.route == "polymul-mod-rns"
    assert bound.rns.own_basis is not None
    rng = np.random.default_rng(2**31 + 16)
    payloads = [bound.random_payload(rng) for _ in range(3)]
    for rid, p in enumerate(payloads):
        engine.submit("polymul-mod", 64, p, rid=rid)
    stats = engine.run(3)
    assert (stats["counters"]["rns.own_basis"]["count"]
            == stats["spans"]["serve.dispatch"]["count"] == 2)
    for rid, (a, b) in enumerate(payloads):
        want = rns.rns_polymul_reference(a, b, bound.rns)
        assert (engine.results[rid] == want).all()


# ---------------------------------------------------------------------------
# Cross-stack differential: float FFT vs exact NTT
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [64, 256, 1024, 4096])
def test_float_fft_polymul_agrees_with_exact_ntt(rng, n):
    """The two subsystems pinned against each other for the first time:
    circular float-FFT polymul, rounded to integers, == exact cyclic NTT
    polymul on small-coefficient inputs (peak coefficient ~n·9 << q, and
    far below the fp32 rounding half-unit at these magnitudes)."""
    p = ref.NTTParams.make(n)
    a = rng.integers(0, 4, size=(2, n))
    b = rng.integers(0, 4, size=(2, n))
    fa = jnp.asarray(a, jnp.float32)
    fb = jnp.asarray(b, jnp.float32)
    via_fft = np.asarray(fft_core.polymul(fa, fb, mode="circular"))
    rounded = np.rint(np.real(via_fft)).astype(np.int64)
    via_ntt = ref.cyclic_polymul(a, b, p)
    assert (rounded >= 0).all() and (rounded < p.q).all()
    assert (rounded.astype(np.uint64) == via_ntt).all()


# ---------------------------------------------------------------------------
# Planner: the exact tier now has a distributed route
# ---------------------------------------------------------------------------

def test_planner_routes_exact_distributed():
    small = fft_core.plan(4096, 64, model_shards=8, exact=True)
    assert small.tier == "local" and small.exact
    big = fft_core.plan(1 << 20, 8, model_shards=8, exact=True)
    assert big.tier == "distributed" and big.exact and big.seq_shards == 8
    assert "NTT" in big.describe()
    # without shards the exact tier is local up to its ceiling, and above
    # it raises like the float tiers (no local kernel compiles there)
    solo = fft_core.plan(1 << 16, 8, model_shards=1, exact=True)
    assert solo.tier == "local" and solo.exact
    with pytest.raises(ValueError, match="_MAX_LOCAL_N_EXACT"):
        fft_core.plan(1 << 20, 8, model_shards=1, exact=True)
