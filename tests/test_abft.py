"""ABFT tier: fault model, sim-level injection, integrity checks, and the
check-cost contract (docs/fault_tolerance.md).

* :class:`FaultModel` is seeded + replayable: same (seed, array) -> same
  faults; quarantine remaps to finite spares;
* sim-level faults (dead array, stuck cells, transient flips) land in the
  charge log as zero-cycle ``fault:*`` ledger entries and are DETECTED by
  the matching integrity check — while fault-free runs pass it;
* the modular checks are exact (every single-coefficient corruption is
  caught); the float checks are toleranced residuals that localize the
  corrupted batch row;
* cost contract: ``abft.charge_check`` on a live sim == the closed form
  ``abft.check_cycles`` (counter parity), and the check stays under the
  BENCH gate's share of the closed-form cycles of the unit it verifies
  (``abft.unit_cycles``, the ``core.pim`` closed forms).
"""
import numpy as np
import pytest

from repro.core.ntt import NTTParams, RNSParams
from repro.core.pim import (FOURIERPIM_8, FP32, INT32, FaultModel,
                            SparesExhausted, fft_pim, ntt_pim)
from repro.core.pim.crossbar import CrossbarSim
from repro.ft import abft

CFG = FOURIERPIM_8


def _negacyclic_ref(a, b, q):
    """O(n^2) negacyclic product mod q in exact python ints (the oracle
    the eval-at-psi check is validated against)."""
    n = len(a)
    conv = np.convolve(np.array([int(v) for v in a], object),
                       np.array([int(v) for v in b], object))
    out = [(int(conv[k]) - (int(conv[k + n]) if k + n < len(conv) else 0))
           % q for k in range(n)]
    return out


# ---------------------------------------------------------------------------
# FaultModel: determinism, quarantine, spares
# ---------------------------------------------------------------------------

def test_fault_model_deterministic():
    kw = dict(seed=7, stuck_per_array=2, bitflip_per_gate=1e-6,
              n_arrays=4, spares=2)
    f1 = FaultModel(**kw).for_array(1)
    f2 = FaultModel(**kw).for_array(1)
    assert f1 == f2 and f1.permanent
    assert len(f1.stuck_pos) == 2
    # a different seed draws different stuck cells
    assert FaultModel(**{**kw, "seed": 8}).for_array(1) != f1
    # clean model resolves None everywhere (the zero-overhead fast path)
    assert FaultModel(seed=7, n_arrays=4).for_array(1) is None


def test_fault_model_quarantine_and_spares():
    fm = FaultModel(seed=0, dead_arrays=(0, 1, 2), n_arrays=4, spares=2)
    assert fm.for_array(0).dead
    spare = fm.quarantine(0)
    assert spare >= fm.n_arrays
    assert fm.is_quarantined(0)
    assert fm.for_array(0) is None          # spares are clean
    assert fm.quarantine(0) == spare        # idempotent, no spare burned
    fm.quarantine(1)
    with pytest.raises(SparesExhausted):
        fm.quarantine(2)
    # the spare draws its own (replayable) transient stream
    a = fm.rng_for(0, salt=5).random(3)
    b = fm.rng_for(0, salt=5).random(3)
    np.testing.assert_array_equal(a, b)


def test_fault_model_validation():
    with pytest.raises(ValueError):
        FaultModel(bitflip_per_gate=1.5)
    with pytest.raises(ValueError):
        FaultModel(dead_arrays=(9,), n_arrays=4)
    with pytest.raises(ValueError):
        FaultModel(stuck_per_array=-1)


# ---------------------------------------------------------------------------
# Sim-level injection: ledger entries + detection by the checks
# ---------------------------------------------------------------------------

def test_dead_array_mod_detected_and_costs_nothing(rng):
    n = 1024
    params = NTTParams.make(n)
    a = rng.integers(0, params.q, n).astype(np.uint32)
    b = rng.integers(0, params.q, n).astype(np.uint32)
    clean = ntt_pim.pim_ntt_polymul(a, b, params, CFG, INT32)
    fm = FaultModel(seed=0, dead_arrays=(0,), n_arrays=2, spares=1)
    faulty = ntt_pim.pim_ntt_polymul(a, b, params, CFG, INT32,
                                     faults=fm, array_id=0)
    assert abft.check_polymul_mod(a, b, clean.output, params).ok
    v = abft.check_polymul_mod(a, b, faulty.output, params)
    assert not v and v.failed_rows == (0,) and v.check == "eval-at-psi"
    # ledger: the array names itself, at zero cycles — fault injection
    # never perturbs the cost model
    tags = [t for t, _ in faulty.log if t.startswith("fault:")]
    assert tags and all(t == "fault:dead:a0" for t in tags)
    assert all(c == 0 for t, c in faulty.log if t.startswith("fault:"))
    assert faulty.counters.cycles == clean.counters.cycles


def test_transient_flip_float_detected_by_parseval(rng):
    n = 1024
    x = rng.standard_normal(n) + 1j * rng.standard_normal(n)
    clean = fft_pim.pim_fft(x, CFG, FP32)
    assert abft.check_fft(x, clean.output).ok
    fm = FaultModel(seed=3, bitflip_per_gate=1e-4, n_arrays=1, spares=0)
    faulty = fft_pim.pim_fft(x, CFG, FP32, faults=fm, array_id=0)
    flips = [t for t, _ in faulty.log if t == "fault:flip:a0"]
    assert flips, "pinned seed must fire at least one transient"
    assert not abft.check_fft(x, faulty.output)
    assert faulty.counters.cycles == clean.counters.cycles
    # same model, same seed -> identical corrupted output (replayable)
    again = fft_pim.pim_fft(
        x, CFG, FP32,
        faults=FaultModel(seed=3, bitflip_per_gate=1e-4, n_arrays=1,
                          spares=0), array_id=0)
    np.testing.assert_array_equal(faulty.output, again.output)


def test_stuck_cells_mod_detected(rng):
    n = 2048
    params = NTTParams.make(n)
    a = rng.integers(0, params.q, n).astype(np.uint32)
    b = rng.integers(0, params.q, n).astype(np.uint32)
    fm = FaultModel(seed=11, stuck_per_array=3, n_arrays=1, spares=0)
    faulty = ntt_pim.pim_ntt_polymul(a, b, params, CFG, INT32,
                                     faults=fm, array_id=0)
    assert any(t == "fault:stuck:a0" for t, _ in faulty.log)
    assert not abft.check_polymul_mod(a, b, faulty.output, params)


# ---------------------------------------------------------------------------
# Integrity checks: clean pass, corruption localized
# ---------------------------------------------------------------------------

def test_float_checks_pass_clean_and_localize_row(rng):
    n = 128
    x = (rng.standard_normal((3, n))
         + 1j * rng.standard_normal((3, n))).astype(np.complex64)
    out = np.fft.fft(x).astype(np.complex64)
    assert abft.check_fft(x, out).ok
    bad = out.copy()
    bad[1, 5] *= 3.0
    v = abft.check_fft(x, bad)
    assert not v and v.failed_rows == (1,)

    xr = rng.standard_normal((3, n)).astype(np.float32)
    outr = np.fft.rfft(xr).astype(np.complex64)
    assert abft.check_rfft(xr, outr).ok
    badr = outr.copy()
    badr[2, 7] += 50.0
    v = abft.check_rfft(xr, badr)
    assert not v and v.failed_rows == (2,) and v.check == "parseval-half"

    a = rng.standard_normal((3, n)) + 1j * rng.standard_normal((3, n))
    b = rng.standard_normal((3, n)) + 1j * rng.standard_normal((3, n))
    r = np.fft.ifft(np.fft.fft(a) * np.fft.fft(b))
    assert abft.check_polymul(a, b, r).ok
    rb = r.copy()
    rb[0, 0] += 100.0
    v = abft.check_polymul(a, b, rb)
    assert not v and v.failed_rows == (0,)

    ar, br = a.real, b.real
    rr = np.fft.irfft(np.fft.rfft(ar) * np.fft.rfft(br), n)
    assert abft.check_polymul_real(ar, br, rr).ok
    assert not abft.check_polymul_real(ar, br, rr + 1.0)


def test_polymul_mod_check_catches_every_coefficient(rng):
    """Exactness: ANY single-coefficient corruption moves r(psi) by
    delta * psi^j != 0 mod q — checked for every position at once."""
    n = 64
    params = NTTParams.make(n)
    a = rng.integers(0, params.q, n).astype(np.uint32)
    b = rng.integers(0, params.q, n).astype(np.uint32)
    r = np.array(_negacyclic_ref(a, b, params.q), np.uint32)
    assert abft.check_polymul_mod(a, b, r, params).ok
    batch = np.tile(r, (n, 1))
    batch[np.arange(n), np.arange(n)] = \
        (batch[np.arange(n), np.arange(n)] + 1) % params.q
    v = abft.check_polymul_mod(np.tile(a, (n, 1)), np.tile(b, (n, 1)),
                               batch, params)
    assert v.failed_rows == tuple(range(n))


def test_polymul_rns_check_and_factor_recovery(rng):
    n = 128
    rns = RNSParams.make(n, modulus_bits=60)
    limbs = abft.check_limbs_for(rns)
    prod = 1
    for limb in limbs:
        prod *= limb.q
    assert prod == rns.modulus
    Q = rns.modulus
    a = np.array([int(v) for v in rng.integers(0, 1 << 62, n)],
                 object) % Q
    b = np.array([int(v) for v in rng.integers(0, 1 << 62, n)],
                 object) % Q
    r = np.array(_negacyclic_ref(a, b, Q), object)
    assert abft.check_polymul_rns(a, b, r, rns).ok
    bad = r.copy()
    bad[17] = (bad[17] + 1) % Q
    v = abft.check_polymul_rns(a, b, bad, rns)
    assert not v and v.failed_rows == (0,)


def test_rns_unsupported_modulus_rejected():
    # A Mersenne prime shares no factor with the 30-bit NTT limb primes.
    rns = RNSParams.make(64, modulus=(1 << 61) - 1)
    with pytest.raises(abft.ABFTUnsupportedModulus):
        abft.check_limbs_for(rns)


# ---------------------------------------------------------------------------
# Check cost: counter parity, overhead gate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("workload", sorted(abft.CHECKS))
@pytest.mark.parametrize("n", [1024, 4096])
def test_check_cost_counter_parity(workload, n):
    spec = INT32 if workload == "polymul-mod" else FP32
    sim = CrossbarSim(CFG, spec)
    abft.charge_check(sim, workload, n)
    closed = abft.check_cycles(workload, n, CFG, spec)
    assert sim.ctr.cycles == closed


@pytest.mark.parametrize("workload", sorted(abft.CHECKS))
@pytest.mark.parametrize("n", [1024, 4096])
def test_check_overhead_under_gate(workload, n):
    """The check must stay CHEAP relative to the transform it verifies —
    the same <= 0.25 bound the BENCH abft_overhead_ratio gate enforces."""
    spec = INT32 if workload == "polymul-mod" else FP32
    base = abft.unit_cycles(workload, n, CFG, spec)
    check = abft.check_cycles(workload, n, CFG, spec)
    assert 0 < check <= 0.25 * base, \
        f"{workload}/n={n}: check {check} vs base {base}"
