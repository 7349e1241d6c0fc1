"""Cost-model tier: the auto-tiering planner's numbers and choices
(docs/planner.md).

* closed-form PIM cycle counts == live ``CrossbarSim`` counters, for
  every workload on both tiers (the cost model's "measured twin"
  contract — the same equalities the smoke bench re-asserts per run);
* closed-form collective-byte formulas == live ``dist.collectives``
  ledger traces of the REAL sharded builders (AbstractMesh: a lower()
  trace needs no devices, so the single-CPU suite measures the D=8 tier);
* prune / infeasibility reasons NAME their constraint — the serve layer
  surfaces these messages verbatim, so they are pinned here;
* ``plan(n, batch, workload=...)`` (auto mode) never returns a plan the
  guards ``bind()`` applies would reject — property-tested across the
  (workload, n, batch, D) space;
* ``FFTPlan.cost`` rides along without perturbing plan equality/hash
  (the engine keys buckets on plans).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import cost as cost_lib
from repro.core.cost import (LINK_BW, WORKLOADS, dist_prune_reason,
                             local_prune_reason, pim_dist_infeasible,
                             pim_local_infeasible, workload_cost, xla_cost)
from repro.core.fft import planner
from repro.core.fft.planner import plan
from repro.core.ntt import NTTParams
from repro.core.pim import (FOURIERPIM_8, FP32, INT32, aritpim, fft_pim,
                            ntt_pim, polymul_pim)
from repro.dist import compat

CFG = FOURIERPIM_8


# ---------------------------------------------------------------------------
# Closed forms == simulator counters (local tier)
# ---------------------------------------------------------------------------

def _sim_local_cycles(workload: str, n: int, batch: int,
                      rng: np.random.Generator) -> int:
    if workload == "fft":
        z = rng.standard_normal(n) + 1j * rng.standard_normal(n)
        return fft_pim.pim_fft(z, CFG, FP32).counters.cycles
    if workload == "rfft":
        return fft_pim.pim_rfft(rng.standard_normal(n),
                                rng.standard_normal(n),
                                CFG, FP32).counters.cycles
    if workload == "polymul":
        a = rng.standard_normal(n) + 1j * rng.standard_normal(n)
        b = rng.standard_normal(n) + 1j * rng.standard_normal(n)
        return polymul_pim.pim_polymul(a, b, CFG, FP32).counters.cycles
    if workload == "polymul-real":
        return polymul_pim.pim_polymul_real(
            rng.standard_normal((batch, n)), rng.standard_normal((batch, n)),
            CFG, FP32).counters.cycles
    params = NTTParams.make(n)
    a = rng.integers(0, params.q, n).astype(np.uint32)
    b = rng.integers(0, params.q, n).astype(np.uint32)
    return ntt_pim.pim_ntt_polymul(a, b, params, CFG, INT32).counters.cycles


@pytest.mark.parametrize("workload", WORKLOADS)
def test_local_unit_cycles_match_simulator(workload, rng):
    n, batch = 2048, 4
    want = cost_lib.pim_local_unit_cycles(workload, n, batch=batch)
    assert _sim_local_cycles(workload, n, batch, rng) == want


def test_complex_fallback_candidates_price_the_complex_schedule():
    """A real=False candidate of a real workload runs the complex kernels
    on XLA — its PIM twin must price the complex schedule too, not the
    packed one it isn't running."""
    assert cost_lib._pim_workload("rfft", False) == "fft"
    assert cost_lib._pim_workload("polymul-real", False) == "polymul"
    assert cost_lib._pim_workload("rfft", True) == "rfft"
    n = 2048
    c = cost_lib.pim_cost("rfft", n, 4, tier="local", real=False)
    assert c.pim_cycles == fft_pim.fft_latency_cycles(n, CFG, FP32)


# ---------------------------------------------------------------------------
# Closed forms == simulator counters + byte records (distributed tier)
# ---------------------------------------------------------------------------

def test_dist_unit_cycles_match_distributed_simulators(rng):
    n, D = 8192, 8            # the n1 = D cap: n == D * crossbar_rows
    r = fft_pim.pim_rfft_distributed(rng.standard_normal(n),
                                     rng.standard_normal(n), D, CFG, FP32)
    rfft_meas = max(c.cycles for c in r.shard_counters)
    unpack = fft_pim.realpack_unpack_cycles(CFG, FP32)
    assert rfft_meas == cost_lib.pim_dist_unit_cycles("rfft", n, D)
    assert rfft_meas - unpack == cost_lib.pim_dist_unit_cycles("fft", n, D)
    assert r.a2a_bytes + r.permute_bytes == \
        cost_lib.pim_dist_unit_bytes("rfft", n, D)

    params = NTTParams.make(n)
    x = rng.integers(0, params.q, n).astype(np.uint32)
    nt = ntt_pim.pim_ntt_distributed(x, params, D, CFG, INT32)
    # polymul-mod composes 3 transforms + the pointwise/twist modmuls
    assert 3 * nt.latency_cycles + 4 * aritpim.mod_mul_cycles(INT32) == \
        cost_lib.pim_dist_unit_cycles("polymul-mod", n, D)
    assert 3 * nt.a2a_bytes == \
        cost_lib.pim_dist_unit_bytes("polymul-mod", n, D)
    # the float polymuls compose the measured transform the same way
    assert 3 * (rfft_meas - unpack) + aritpim.complex_mul_cycles(FP32) == \
        cost_lib.pim_dist_unit_cycles("polymul", n, D)


@pytest.mark.parametrize("workload,real", [
    ("fft", False), ("rfft", True), ("rfft", False),
    ("polymul", False), ("polymul-real", True), ("polymul-real", False),
    ("polymul-mod", False)])
def test_xla_collective_bytes_match_live_ledger(workload, real):
    """The byte model the planner charges for the distributed XLA tier ==
    the live ledger of the actual sharded builder, traced at the real
    shard count on an AbstractMesh."""
    from repro.core.fft import distributed as dfft
    from repro.core.ntt import distributed as dntt
    from repro.dist import collectives
    n, batch, D = 1024, 4, 4
    mesh = compat.abstract_mesh((D,), ("model",))
    if workload == "polymul-mod":
        build = dntt.make_sharded_ntt_polymul(
            mesh, NTTParams.make(n), axis_name="model", batch_axes=())
        spec = jax.ShapeDtypeStruct((batch, n), jnp.uint32)
        args = (spec, spec)
    elif workload == "rfft" and real:
        build = dfft.make_sharded_rfft(mesh, batch_axes=())
        args = (jax.ShapeDtypeStruct((batch, n), jnp.float32),)
    elif workload == "polymul-real" and real:
        build = dfft.make_sharded_polymul_real(mesh, batch_axes=())
        spec = jax.ShapeDtypeStruct((batch, n), jnp.float32)
        args = (spec, spec)
    elif workload in ("polymul", "polymul-real"):
        build = dfft.make_sharded_polymul(mesh, batch_axes=())
        spec = jax.ShapeDtypeStruct((batch, n), jnp.complex64)
        args = (spec, spec)
    else:
        build = dfft.make_sharded_fft(mesh, batch_axes=())
        args = (jax.ShapeDtypeStruct((batch, n), jnp.complex64),)
    with collectives.ledger() as led:
        jax.jit(build).lower(*args)
    got = led.bytes_by_kind["all-to-all"] + led.bytes_by_kind["ppermute"]
    assert got == cost_lib._xla_collective_bytes(workload, n, batch, D,
                                                 real=real)


def test_xla_collective_bytes_pad_odd_real_batches():
    """The engine pads odd real batches to the next even size; the byte
    model charges the padded batch, not the impossible odd one."""
    even = cost_lib._xla_collective_bytes("rfft", 1024, 4, 4, real=True)
    assert cost_lib._xla_collective_bytes("rfft", 1024, 3, 4,
                                          real=True) == even


def test_xla_cost_is_roofline_max_plus_collectives():
    local = xla_cost("fft", 4096, 8, tier="local")
    assert local.t_collective_s == 0 and local.collective_bytes == 0
    assert local.total_s == max(local.t_compute_s, local.t_memory_s)
    dist = xla_cost("fft", 4096, 8, tier="distributed", n_devices=8)
    assert dist.t_compute_s == pytest.approx(local.t_compute_s / 8)
    assert dist.t_memory_s == pytest.approx(local.t_memory_s / 8)
    assert dist.t_collective_s == dist.collective_bytes / LINK_BW
    assert dist.total_s == pytest.approx(
        max(dist.t_compute_s, dist.t_memory_s) + dist.t_collective_s)


# ---------------------------------------------------------------------------
# Prune / infeasibility reasons name their constraint
# ---------------------------------------------------------------------------

def test_prune_reasons_name_their_constraint():
    assert local_prune_reason("fft", 1024) is None
    assert "_MAX_LOCAL_N" in local_prune_reason("fft", 2 ** 20)
    assert "_MAX_LOCAL_N_EXACT" in local_prune_reason("polymul-mod",
                                                      2 ** 20)
    assert dist_prune_reason("fft", 4096, 8, real=False) is None
    assert "model_shards > 1" in dist_prune_reason("fft", 1024, 1,
                                                   real=False)
    assert "D^2 | n" in dist_prune_reason("fft", 2 ** 20, 3, real=False)
    # the ordered real tier's stricter tiling has its own name
    assert "2*D^2 | n" in dist_prune_reason("rfft", 2 ** 20, 1024,
                                            real=True)


@pytest.mark.parametrize("workload,n,D,real,name", [
    ("fft", 2 ** 19, 1, False, "only polymul-real"),
    ("polymul-real", 2 ** 19, 1, False, "real packing only"),
    ("polymul-real", 2 ** 16, 1, True, "_MAX_LOCAL_N"),
    ("polymul-real", 2 ** 19, 4, True, "model_shards=4 > 1"),
])
def test_hbm_prune_reasons_name_their_constraint(workload, n, D, real, name):
    reason = cost_lib.hbm_prune_reason(workload, n, D, real=real)
    assert reason.startswith("hbm tier:") and name in reason


def test_hbm_tier_is_offered_above_the_ceiling_on_one_device():
    assert cost_lib.hbm_prune_reason("polymul-real", 2 ** 19, 1,
                                     real=True) is None
    b = workload_cost("polymul-real", 2 ** 19, 16)
    assert [(c["tier"], c["real"]) for c in b["candidates"]] == [("hbm",
                                                                 True)]
    hbm = b["candidates"][0]["backends"]
    assert "HBM-resident" in hbm["pim"]["infeasible"]
    local = xla_cost("polymul-real", 2 ** 19, 16, tier="local", real=True)
    assert hbm["xla"]["t_memory_s"] == pytest.approx(
        cost_lib.HBM_TIER_PASSES * local.t_memory_s)
    p = plan(2 ** 19, 16, workload="polymul-real")
    assert p.tier == "hbm" and p.real and p.seq_shards == 1
    assert p.inner_n == planner.hbm_inner_n(2 ** 19) and p.cost is not None
    # with more devices the sequence is sharded, as before
    assert plan(2 ** 19, 16, workload="polymul-real",
                model_shards=4).tier == "distributed"
    # at the ceiling the fused local kernel stays the choice
    assert plan(2 ** 14, 64, workload="polymul-real").tier == "local"


def test_pim_infeasibility_names_its_constraint():
    assert pim_local_infeasible("fft", 2048) is None
    bad = pim_local_infeasible("fft", 65536)
    assert "valid_config" in bad and "crossbar_cols" in bad
    assert pim_dist_infeasible(8192, 8) is None
    bad = pim_dist_infeasible(8192, 4)
    assert "n1 = D four-step cap" in bad
    assert "model_shards > 1" in pim_dist_infeasible(8192, 1)


def test_auto_plan_error_names_every_pruned_candidate():
    """A workload with no executable candidate fails listing each pruned
    (tier, packing) with the constraint that pruned it — the serve layer
    returns this message verbatim."""
    with pytest.raises(ValueError) as ei:
        plan(2 ** 20, 4, workload="fft", model_shards=3)
    msg = str(ei.value)
    assert "every candidate was pruned" in msg
    assert "_MAX_LOCAL_N" in msg and "D^2 | n" in msg
    with pytest.raises(ValueError) as ei:
        plan(2 ** 20, 4, workload="rfft", real=True, model_shards=1024)
    assert "2*D^2 | n" in str(ei.value)


# ---------------------------------------------------------------------------
# The chooser and the auto planner surface
# ---------------------------------------------------------------------------

def test_workload_cost_breakdown_structure():
    b = workload_cost("polymul-real", 4096, 8, n_devices=8)
    assert b["best"] is not None
    totals = [c["total_s"] for c in b["candidates"]]
    assert totals == sorted(totals)         # cheapest-first, stable ties
    assert b["best"] == b["candidates"][0]
    assert b["constants"]["link_bw"] == LINK_BW
    assert {c["real"] for c in b["candidates"]
            if c["tier"] == "local"} == {True, False}


def test_pim_infeasibility_is_a_backend_verdict_not_a_prune():
    """A shape the crossbar cannot hold still EXECUTES on XLA — PIM
    infeasibility must not remove the candidate, only its PIM score."""
    b = workload_cost("fft", 65536, 8, n_devices=8)
    local = [c for c in b["candidates"] if c["tier"] == "local"]
    assert local, b["pruned"]
    assert "valid_config" in local[0]["backends"]["pim"]["infeasible"]
    assert local[0]["backend_best"] == "xla"


def test_auto_plan_knob_interactions():
    with pytest.raises(ValueError, match="unknown workload"):
        plan(1024, 4, workload="dct")
    with pytest.raises(ValueError, match="exact.*polymul-mod"):
        plan(1024, 4, workload="fft", exact=True)
    with pytest.raises(ValueError, match="real-packed route"):
        plan(1024, 4, workload="polymul", real=True)
    # explicit knobs narrow the candidate space instead of being ignored
    p = plan(4096, 8, workload="fft", model_shards=8,
             force_distributed=True)
    assert p.tier == "distributed" and p.seq_shards == 8
    assert all(c["tier"] == "distributed" for c in p.cost["candidates"])
    p = plan(4096, 8, workload="rfft", real=True)
    assert p.real is True
    p = plan(1024, 4, workload="polymul-mod")
    assert p.exact is True and p.radix == 2


def test_auto_plan_cost_breakdown_rides_without_breaking_equality():
    """FFTPlan.cost is excluded from eq/hash: an auto plan and the
    equivalent explicit plan are the same bucket key to the engine."""
    auto = plan(1024, 4, workload="fft")
    explicit = plan(1024, 4)
    assert auto.cost is not None and explicit.cost is None
    assert auto == explicit and hash(auto) == hash(explicit)
    best = auto.cost["best"]
    assert (best["tier"], best["real"]) == (auto.tier, auto.real)


@settings(max_examples=60, deadline=None)
@given(workload=st.sampled_from(WORKLOADS),
       k=st.integers(6, 19),
       batch=st.integers(1, 16),
       D=st.sampled_from([1, 2, 4, 8, 16]))
def test_auto_plan_is_always_executable(workload, k, batch, D):
    """Property: auto either raises naming the pruning constraints, or
    returns a plan that passes the same guards bind() applies — never a
    plan the kernels reject."""
    n = 2 ** k
    try:
        p = plan(n, batch, workload=workload, model_shards=D)
    except ValueError as e:
        msg = str(e)
        assert "every candidate was pruned" in msg
        assert ("_MAX_LOCAL_N" in msg or "D^2 | n" in msg
                or "model_shards > 1" in msg)
        return
    assert p.exact == (workload == "polymul-mod")
    if p.tier == "local":
        cap = (planner._MAX_LOCAL_N_EXACT if p.exact
               else planner._MAX_LOCAL_N)
        assert n <= cap
        assert p.seq_shards == 1 and p.block_b >= 1
    else:
        from repro.core.fft.distributed import check_four_step_shape
        # the ordered rfft is the only dist route with the 2*D^2 tiling
        check_four_step_shape(n, p.seq_shards,
                              real=p.real and workload == "rfft")
        assert p.seq_shards == D
    best = p.cost["best"]
    assert (best["tier"], best["real"]) == (p.tier, p.real)


def test_engine_auto_mode_binds_serves_and_reports_predictions(rng):
    """End to end: every registry op binds in auto mode, serves, verifies
    against its numpy oracle, and reports predicted-vs-observed cost in
    stats() (the serve-layer surface of the tentpole)."""
    from repro.launch.engine import ServeEngine
    ops = ("fft", "rfft", "polymul", "polymul-real", "polymul-mod")
    engine = ServeEngine(max_batch=4, auto=True)
    for op in ops:
        engine.register(op, 256)
        assert engine.bound(op, 256).plan.cost is not None
    engine.warmup()
    kept = {}
    for op in ops:
        payload = engine.bound(op, 256).random_payload(rng)
        kept[op] = (engine.submit(op, 256, payload), payload)
    stats = engine.run(len(ops))
    assert stats["served"] == len(ops)
    for op, (rid, payload) in kept.items():
        engine.bound(op, 256).verify(payload, engine.results[rid])
    for name, b in stats["buckets"].items():
        assert b["predicted_s_per_req"] is not None, name
        assert b["predicted_tier"] == "local", name
        assert b["predicted_backend"] in ("pim", "xla"), name
        assert b["observed_s_per_req"] > 0, name
