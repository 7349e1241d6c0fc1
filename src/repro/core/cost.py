"""Unified cost model behind the auto-tiering planner (docs/planner.md).

One module owns every number the planner compares so the comparison is
apples-to-apples:

  * **PIM closed forms** (``core.pim.fft_pim`` / ``polymul_pim`` /
    ``ntt_pim``): bit-serial cycle counts asserted equal to the
    ``CrossbarSim`` counters in tests — the cost twin of the paper's
    crossbar schedule.
  * **Collective byte formulas** (``core.fft.distributed`` /
    ``core.ntt.distributed`` ``four_step_collective_stats``): the same
    closed forms pinned against the live ``dist.collectives`` ledger.
  * **Roofline host/XLA estimates**: the v5e constants that
    ``benchmarks/roofline.py`` uses for the dry-run analysis (the
    constants LIVE here now; roofline imports them back, so the serving
    cost model and the training roofline can never drift apart).

``workload_cost(workload, n, batch, ...)`` enumerates every (tier,
packing) candidate that is *executable on the XLA path* (the planner
only ever returns plans the kernels accept), scores each candidate on
both backends (PIM cost twin and XLA roofline), and returns the
predicted-cheapest candidate plus a machine-readable breakdown — every
pruned candidate carries the NAME of the constraint that pruned it
(the ``n1 = D`` four-step cap, ``D^2 | n`` tiling, the VMEM ceiling, the
hbm tier's one device and real product),
so a non-executable request fails with the reason, not a bare error.

Accounting conventions (shared by the smoke bench's "measured" side so
predicted-vs-measured agreement is meaningful, benchmarks/run.py):

  * XLA: ``total = max(t_compute, t_memory) + t_collective`` — roofline
    max of the on-chip terms, plus serialized interconnect time.
    Distributed splits the on-chip work over D devices and charges the
    per-device ledger bytes of ``four_step_collective_stats``.
  * PIM local: steady-state batched throughput (every crossbar runs the
    schedule in parallel, net of scratch area — the paper's §6 model).
  * PIM distributed: one in-flight transform holds one crossbar on each
    of the D shards, so ``num_crossbars * concurrency`` units pipeline;
    inter-shard transpose bytes cross each device's link at
    ``bytes/D / LINK_BW``. Only valid under the ``n1 = D`` cap
    (``n == D * crossbar_rows``) that the closed forms assert.
"""
from __future__ import annotations

import dataclasses
import math

from repro.core.pim import aritpim
from repro.core.pim.device_model import FOURIERPIM_8, PIMConfig
from repro.core.pim.fft_pim import (
    fft_distributed_a2a_bytes,
    fft_distributed_latency_cycles,
    fft_latency_cycles,
    fft_throughput_per_s,
    realpack_unpack_cycles,
    rfft_distributed_a2a_bytes,
    rfft_distributed_latency_cycles,
    rfft_distributed_permute_bytes,
    rfft_latency_cycles,
    rfft_throughput_per_s,
)
from repro.core.pim.ntt_pim import (
    ntt_distributed_a2a_bytes,
    ntt_distributed_latency_cycles,
    ntt_polymul_latency_cycles,
)
from repro.core.pim.polymul_pim import (
    polymul_latency_cycles,
    polymul_real_batch_latency_cycles,
    polymul_throughput_per_s,
)

# Hardware model constants (v5e-class host chip). benchmarks/roofline.py
# imports these back — single source of truth for both the training-side
# dry-run roofline and the serving-side planner.
PEAK_FLOPS = 197e12        # bf16 FLOPs/s per chip
HBM_BW = 819e9             # bytes/s per chip
LINK_BW = 50e9             # bytes/s per ICI link
#: HBM passes of a product on the hbm tier, against the local tier's one:
#: the outer forward pass, the inner forward rows, the paired product, the
#: inner inverse rows and the outer inverse pass (core/fft/hbm.py)
HBM_TIER_PASSES = 5

#: Workloads the chooser understands — exactly the ``OpSpec`` registry
#: names (launch/ops.py), so serve buckets can ask for costs verbatim.
WORKLOADS = ("fft", "rfft", "polymul", "polymul-real", "polymul-mod")

_PIM_CFG = FOURIERPIM_8
_FP = aritpim.FP32
_INT = aritpim.INT32


@dataclasses.dataclass(frozen=True)
class TierCost:
    """Predicted cost of one executable (tier, packing) candidate on one
    backend. ``total_s`` is the comparison key; the component terms and
    raw PIM cycle / collective byte counts ride along so tests can pin
    them against simulator counters and ledger bytes."""
    tier: str               # "local" | "hbm" | "distributed"
    backend: str            # "pim" | "xla"
    real: bool
    exact: bool
    seq_shards: int
    total_s: float
    t_compute_s: float = 0.0
    t_memory_s: float = 0.0
    t_collective_s: float = 0.0
    pim_cycles: int = 0           # per-unit closed-form latency (pim only)
    collective_bytes: int = 0     # per-batch interconnect bytes

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def _require_pow2(n: int) -> None:
    if n <= 0 or n & (n - 1):
        raise ValueError(f"n={n} must be a power of two")


def _word_bits(*, real: bool, exact: bool) -> int:
    if exact:
        return _INT.word_bits
    # Real rows pack pairwise into full complex words; the per-row
    # capacity doubling is carried by the throughput closed forms, so
    # crossbar feasibility is judged at the complex word width.
    return aritpim.complex_word_bits(_FP)


# ---------------------------------------------------------------------------
# Executability constraints — each returns None (ok) or the prune reason.
# The reason strings NAME the constraint; tests pin them.
# ---------------------------------------------------------------------------

def local_prune_reason(workload: str, n: int) -> str | None:
    """XLA local tier: the sequence must stay VMEM-resident."""
    from repro.core.fft import planner
    exact = workload == "polymul-mod"
    cap = planner._MAX_LOCAL_N_EXACT if exact else planner._MAX_LOCAL_N
    if n > cap:
        which = "_MAX_LOCAL_N_EXACT" if exact else "_MAX_LOCAL_N"
        return (f"local tier: n={n} exceeds the VMEM-resident ceiling "
                f"{which}={cap} (sequence no longer fits one kernel)")
    return None


def dist_prune_reason(workload: str, n: int, n_devices: int, *,
                      real: bool) -> str | None:
    """XLA four-step tier: device count and transpose tiling."""
    if n_devices <= 1:
        return ("distributed tier: four-step needs model_shards > 1 "
                f"(have {n_devices})")
    d2 = n_devices * n_devices
    if n % d2:
        return (f"distributed tier: four-step tiling needs D^2 | n "
                f"(transposes + twiddle blocks): n={n}, D^2={d2}")
    if real and workload == "rfft" and n % (2 * d2):
        return (f"distributed real tier: the ordered rfft's half-width "
                f"ordering all-to-all needs 2*D^2 | n: n={n}, "
                f"2*D^2={2 * d2}")
    return None


def hbm_prune_reason(workload: str, n: int, n_devices: int, *,
                     real: bool) -> str | None:
    """XLA hbm tier: one device, a row above the VMEM ceiling, and the
    four-step real product (the only op it implements)."""
    from repro.core.fft import planner
    if workload != "polymul-real":
        return (f"hbm tier: only polymul-real has an HBM-resident "
                f"four-step (got {workload!r})")
    if not real:
        return ("hbm tier: real packing only (products pair two-for-one "
                "through the packed transform)")
    if n <= planner._MAX_LOCAL_N_REAL:
        return (f"hbm tier: n={n} is within the VMEM-resident ceiling "
                f"_MAX_LOCAL_N={planner._MAX_LOCAL_N_REAL} (the local "
                f"kernel makes one HBM pass)")
    if n_devices > 1:
        return (f"hbm tier: one device only; model_shards={n_devices} > 1 "
                f"shards the sequence (distributed tier)")
    return None


def pim_local_infeasible(workload: str, n: int,
                         cfg: PIMConfig = _PIM_CFG) -> str | None:
    """PIM cost twin, local tier: one sequence must fit one crossbar's
    columns (``PIMConfig.valid_config`` — the paper's footnote 7)."""
    real = workload in ("rfft", "polymul-real")
    exact = workload == "polymul-mod"
    word = _word_bits(real=real, exact=exact)
    if not cfg.valid_config(n, word):
        beta = max(1, n // (2 * cfg.crossbar_rows))
        return (f"pim local: 2*beta*word_bits={2 * beta * word} exceeds "
                f"crossbar_cols={cfg.crossbar_cols} (valid_config: "
                f"multi-crossbar FFT is the paper's future work)")
    return None


def pim_dist_infeasible(n: int, n_devices: int,
                        cfg: PIMConfig = _PIM_CFG) -> str | None:
    """PIM cost twin, distributed tier: the closed forms assert the
    ``n1 = D`` four-step cap (each shard's block is exactly one r-config
    crossbar column: n2 = n/D == crossbar_rows)."""
    if n_devices <= 1:
        return "pim distributed: needs model_shards > 1"
    r = cfg.crossbar_rows
    if n != n_devices * r:
        return (f"pim distributed: closed forms need n2 = n/D == "
                f"crossbar_rows={r} (the n1 = D four-step cap): "
                f"n={n}, D={n_devices}, n/D={n // n_devices}")
    return None


# ---------------------------------------------------------------------------
# XLA roofline estimates
# ---------------------------------------------------------------------------

def _xla_local_terms(workload: str, n: int, batch: int, *,
                     real: bool) -> tuple[float, float]:
    """(flops, hbm_bytes) of one local batched call.

    FFT flop model: 5 n log2 n per complex transform (the textbook
    split-radix-free count XLA's Stockham hits within a small constant);
    real packing runs batch/2 packed transforms plus O(n) unpack adds.
    Byte model: the Pallas kernels are VMEM-resident single-pass — each
    operand/result crosses HBM exactly once.
    """
    lg = n.bit_length() - 1
    fft_flops = 5.0 * n * lg
    # repro: noqa[dispatch-ladder]: per-workload closed-form flop/byte FORMULAS (cost-model data, not op dispatch) — executable routes bind through the launch/ops.py registry
    if workload == "fft":
        flops = batch * fft_flops
        nbytes = batch * 2 * n * 8                      # c64 in + out
    elif workload == "rfft":
        if real:
            flops = batch * (fft_flops / 2 + 4.0 * n)   # packed + unpack
            nbytes = batch * (n * 4 + n * 4)            # f32 in, half c64 out
        else:                                           # complex fallback
            flops = batch * fft_flops
            nbytes = batch * (2 * n * 8)
    elif workload == "polymul":
        flops = batch * (3 * fft_flops + 6.0 * n)
        nbytes = batch * 3 * n * 8                      # a, b in + out
    elif workload == "polymul-real":
        if real:   # paired inverse: ~1.5 transform-equivalents/product
            flops = batch * (1.5 * fft_flops + 12.0 * n)
            nbytes = batch * 3 * n * 4                  # f32 a, b, out
        else:      # cast-to-complex fallback: full complex product
            flops = batch * (3 * fft_flops + 6.0 * n)
            nbytes = batch * 3 * n * 8
    elif workload == "polymul-mod":
        # Montgomery butterfly ~ 8 int-op equivalents; 3 transforms +
        # pointwise + negacyclic twists.
        flops = batch * (3 * 8.0 * (n / 2) * lg + 4.0 * 2 * n)
        nbytes = batch * 3 * n * 4                      # u32 a, b, out
    else:
        raise ValueError(f"unknown workload {workload!r}")
    return flops, nbytes


def _xla_collective_bytes(workload: str, n: int, batch: int,
                          n_devices: int, *, real: bool) -> int:
    """Per-device ledger bytes of one distributed call — the
    ``four_step_collective_stats`` closed forms (pinned against the live
    ledger), with the even-batch pad the engine applies to odd real
    batches folded in."""
    if workload == "polymul-mod":
        from repro.core.ntt.distributed import four_step_collective_stats
        return four_step_collective_stats(
            n, batch, n_devices, op="polymul")["bytes"]
    from repro.core.fft.distributed import four_step_collective_stats
    # repro: noqa[dispatch-ladder]: maps workload -> ledger closed-form key (byte-formula selection, not op dispatch); the registry is the only executable dispatch surface
    if workload == "rfft":
        op = "rfft" if real else "fft"
    elif workload == "polymul-real":
        op = "polymul_real" if real else "polymul"
    else:
        op = {"fft": "fft", "polymul": "polymul"}[workload]
    if op in ("rfft", "polymul_real") and batch % 2:
        batch += 1                      # engine pads odd real batches
    return four_step_collective_stats(n, batch, n_devices,
                                      op=op)["total_bytes"]


def xla_cost(workload: str, n: int, batch: int, *, tier: str,
             n_devices: int = 1, real: bool = False,
             verified: bool = False) -> TierCost:
    exact = workload == "polymul-mod"
    flops, nbytes = _xla_local_terms(workload, n, max(batch, 1), real=real)
    if verified:
        # Host-side integrity check (ft/abft.py): O(n) reductions over
        # each operand/result row plus one more pass over the result.
        ops = {"fft": 2, "rfft": 2}.get(workload, 3)
        flops += max(batch, 1) * ops * 4.0 * n
        nbytes += max(batch, 1) * n * 8
    coll = 0
    if tier == "distributed":
        flops /= n_devices
        nbytes /= n_devices
        coll = _xla_collective_bytes(workload, n, max(batch, 1),
                                     n_devices, real=real)
    elif tier == "hbm":
        nbytes *= HBM_TIER_PASSES
    t_comp = flops / PEAK_FLOPS
    t_mem = nbytes / HBM_BW
    t_coll = coll / LINK_BW
    return TierCost(tier=tier, backend="xla", real=real, exact=exact,
                    seq_shards=n_devices if tier == "distributed" else 1,
                    total_s=max(t_comp, t_mem) + t_coll,
                    t_compute_s=t_comp, t_memory_s=t_mem,
                    t_collective_s=t_coll, collective_bytes=coll)


# ---------------------------------------------------------------------------
# PIM cost-twin estimates
# ---------------------------------------------------------------------------

def abft_check_cycles(workload: str, n: int, *,
                      cfg: PIMConfig = _PIM_CFG) -> int:
    """Closed-form cycles of one ABFT integrity check (ft/abft.py) at the
    planner's default device model — the quantity ``verified=True``
    pricing adds per work unit, and the counter-parity gate pins against
    ``abft.charge_check`` on a live sim. Lazy import: abft pulls the
    crossbar stack, and this module is imported by the planner on every
    bind — the check cost is only computed on verified paths."""
    from repro.ft import abft
    spec = _INT if workload == "polymul-mod" else _FP
    return abft.check_cycles(workload, n, cfg, spec)


def pim_local_unit_cycles(workload: str, n: int, *, batch: int = 2,
                          cfg: PIMConfig = _PIM_CFG) -> int:
    """Closed-form latency cycles of one unit of work on one crossbar —
    the quantity tests assert equal to ``CrossbarSim`` counters.

    Units: one transform (fft), one packed run of TWO real rows (rfft),
    one product (polymul / polymul-mod), a ``batch``-product real call
    (polymul-real: pairs share the inverse, so cycles are per-call)."""
    if workload == "fft":
        return fft_latency_cycles(n, cfg, _FP)
    if workload == "rfft":
        return rfft_latency_cycles(n, cfg, _FP)
    if workload == "polymul":
        return polymul_latency_cycles(n, cfg, _FP)
    if workload == "polymul-real":
        return polymul_real_batch_latency_cycles(n, batch, cfg, _FP)
    if workload == "polymul-mod":
        return ntt_polymul_latency_cycles(n, cfg, _INT)
    raise ValueError(f"unknown workload {workload!r}")


def _pim_local_throughput(workload: str, n: int,
                          cfg: PIMConfig = _PIM_CFG) -> float:
    if workload == "fft":
        return fft_throughput_per_s(n, cfg, _FP)
    if workload == "rfft":
        return rfft_throughput_per_s(n, cfg, _FP)
    if workload == "polymul":
        return polymul_throughput_per_s(n, cfg, _FP, real=False)
    if workload == "polymul-real":
        return polymul_throughput_per_s(n, cfg, _FP, real=True)
    # polymul-mod: mirror polymul_throughput_per_s's operand-area
    # accounting at the residue word width (a and b both resident).
    lat = ntt_polymul_latency_cycles(n, cfg, _INT) / cfg.clock_hz
    word = _INT.word_bits
    beta = max(1, n // (2 * cfg.crossbar_rows))
    data_cols = 2 * 2 * beta * word
    scratch = cfg.temp_words * word * cfg.partitions
    area = max(1.0, (data_cols + scratch) / cfg.crossbar_cols)
    return int(cfg.num_crossbars / area) * cfg.concurrency / lat


def pim_dist_unit_cycles(workload: str, n: int, n_devices: int, *,
                         cfg: PIMConfig = _PIM_CFG) -> int:
    """Per-shard closed-form cycles of one distributed unit. Transforms
    use the pinned dist closed forms; the polymul workloads compose them
    (2 forwards + 1 inverse + pointwise), mirroring the local forms."""
    spec = _FP
    serial = 1                      # n2 == r: beta = 1 per shard block
    if workload == "fft":
        return fft_distributed_latency_cycles(n, n_devices, cfg, spec)
    if workload == "rfft":
        return rfft_distributed_latency_cycles(n, n_devices, cfg, spec)
    if workload == "polymul":
        return (3 * fft_distributed_latency_cycles(n, n_devices, cfg, spec)
                + aritpim.complex_mul_cycles(spec) * serial)
    if workload == "polymul-real":
        # Per PAIR: 2 packed forwards + 1 inverse + 2 unpacks + 2 cmuls
        # + the Q-pack (same schedule as the local paired form).
        return (3 * fft_distributed_latency_cycles(n, n_devices, cfg, spec)
                + 2 * realpack_unpack_cycles(cfg, spec)
                + 2 * aritpim.complex_mul_cycles(spec))
    if workload == "polymul-mod":
        return (3 * ntt_distributed_latency_cycles(n, n_devices, cfg, _INT)
                + 4 * aritpim.mod_mul_cycles(_INT))
    raise ValueError(f"unknown workload {workload!r}")


def pim_dist_unit_bytes(workload: str, n: int, n_devices: int) -> int:
    """Inter-array transpose traffic of one distributed unit (global
    bytes across the fabric), from the pinned per-transform formulas."""
    if workload == "fft":
        return fft_distributed_a2a_bytes(n, _FP, ordered=True)
    if workload == "rfft":
        return (rfft_distributed_a2a_bytes(n, _FP)
                + rfft_distributed_permute_bytes(n, _FP))
    if workload == "polymul":
        # 2 fwd + 1 inv, two transposes each, no ordering move needed
        # inside the product: 6 full-width transform widths.
        return 3 * fft_distributed_a2a_bytes(n, _FP, ordered=False)
    if workload == "polymul-real":
        # Per PAIR: 3 packed transforms (2 transposes each) + the mirror
        # permute — the PIM twin of the TPU tier's 3.5-block-unit ratio.
        return (3 * fft_distributed_a2a_bytes(n, _FP, ordered=False)
                + rfft_distributed_permute_bytes(n, _FP))
    if workload == "polymul-mod":
        return 3 * ntt_distributed_a2a_bytes(n, n_devices, _INT)
    raise ValueError(f"unknown workload {workload!r}")


def _pim_workload(workload: str, real: bool) -> str:
    """Effective PIM schedule for a (workload, packing) candidate: the
    complex-fallback candidates of the real workloads run the plain
    complex schedules on the crossbar, exactly as they do on XLA."""
    if not real:
        if workload == "rfft":
            return "fft"
        if workload == "polymul-real":
            return "polymul"
    return workload


def _pim_units(workload: str, batch: int, *, real: bool) -> int:
    """Work units in a batch: packed real transforms carry two rows per
    run; real products pair per-call (pairs already amortized inside the
    closed forms, so units = calls of the batch form)."""
    if workload == "rfft" and real:
        return max(1, math.ceil(batch / 2))
    if workload == "polymul-real" and real:
        return 1            # one batched call; cycles already batch-wide
    return max(batch, 1)


def pim_cost(workload: str, n: int, batch: int, *, tier: str,
             n_devices: int = 1, real: bool = False,
             verified: bool = False,
             cfg: PIMConfig = _PIM_CFG) -> TierCost:
    exact = workload == "polymul-mod"
    batch = max(batch, 1)
    wl = _pim_workload(workload, real)
    check = abft_check_cycles(wl, n, cfg=cfg) if verified else 0
    if tier == "local":
        unit_cycles = pim_local_unit_cycles(wl, n, batch=batch, cfg=cfg)
        t = batch / _pim_local_throughput(wl, n, cfg)
        if verified:
            # The check rides the same vectored column ops as the
            # transform (batch rows in parallel), so throughput scales by
            # the per-unit cycle stretch — the closed-form overhead the
            # BENCH abft_overhead_ratio gate pins.
            t *= (unit_cycles + check) / unit_cycles
            unit_cycles += check
        return TierCost(tier="local", backend="pim", real=real, exact=exact,
                        seq_shards=1, total_s=t, t_compute_s=t,
                        pim_cycles=unit_cycles)
    unit_cycles = pim_dist_unit_cycles(wl, n, n_devices, cfg=cfg) + check
    unit_bytes = pim_dist_unit_bytes(wl, n, n_devices)
    units = _pim_units(workload, batch, real=real)
    if workload == "polymul-real" and real:
        units = max(1, math.ceil(batch / 2))    # dist form is per pair
    capacity = max(1, int(cfg.num_crossbars * cfg.concurrency))
    waves = math.ceil(units / capacity)
    t_comp = waves * unit_cycles / cfg.clock_hz
    coll = units * unit_bytes
    t_coll = (coll / n_devices) / LINK_BW
    return TierCost(tier="distributed", backend="pim", real=real,
                    exact=exact, seq_shards=n_devices,
                    total_s=t_comp + t_coll, t_compute_s=t_comp,
                    t_collective_s=t_coll, pim_cycles=unit_cycles,
                    collective_bytes=coll)


# ---------------------------------------------------------------------------
# The chooser
# ---------------------------------------------------------------------------

def _packings(workload: str) -> list[bool]:
    """Packing candidates (``real`` flag values) per workload. Real
    workloads may fall back to complex packing (cast + full-width route)
    when the real tier is pruned — e.g. the ordered distributed rfft's
    2*D^2 | n constraint where the complex tier only needs D^2 | n."""
    if workload in ("rfft", "polymul-real"):
        return [True, False]
    return [False]


def workload_cost(workload: str, n: int, batch: int, *,
                  n_devices: int = 1,
                  tiers: tuple[str, ...] = ("local", "distributed", "hbm"),
                  packings: list[bool] | None = None,
                  verified: bool = False, pim_ok: bool = True) -> dict:
    """Score every executable (tier, packing) candidate on both backends.

    ``verified=True`` prices the ABFT integrity check on every backend
    (``abft_check_cycles`` on PIM, the O(n) host reductions on XLA) so a
    verified serve bucket's predicted costs include the checksum
    overhead. ``pim_ok=False`` marks the PIM backend infeasible on every
    candidate — the serve engine's circuit breaker quarantining a faulty
    array re-plans with the PIM placement off the table.

    Returns a machine-readable breakdown::

        {"workload", "n", "batch", "n_devices",
         "candidates": [{"tier", "real", "exact", "total_s",
                         "backend_best", "backends": {...}}, ...],
         "pruned":     [{"tier", "real", "reason"}, ...],
         "best":       <cheapest candidate or None>,
         "constants":  {"peak_flops", "hbm_bw", "link_bw"}}

    A candidate is listed iff the XLA path can execute it (the planner
    never returns a plan ``bind()`` rejects); the PIM backend may be
    marked infeasible per candidate (crossbar columns, the ``n1 = D``
    cap) without pruning the candidate itself — the plan still runs on
    the host, it just doesn't win a PIM placement.
    """
    _require_pow2(n)
    if workload not in WORKLOADS:
        raise ValueError(f"unknown workload {workload!r}; "
                         f"expected one of {WORKLOADS}")
    exact = workload == "polymul-mod"
    candidates, pruned = [], []
    for tier in tiers:
        for real in (packings if packings is not None
                     else _packings(workload)):
            if tier == "local":
                reason = local_prune_reason(workload, n)
            elif tier == "hbm":
                reason = hbm_prune_reason(workload, n, n_devices, real=real)
            else:
                reason = dist_prune_reason(workload, n, n_devices,
                                           real=real)
            if reason is not None:
                pruned.append({"tier": tier, "real": real, "exact": exact,
                               "reason": reason})
                continue
            backends = {}
            xc = xla_cost(workload, n, batch, tier=tier,
                          n_devices=n_devices, real=real,
                          verified=verified)
            backends["xla"] = xc.as_dict()
            if not pim_ok:
                pim_bad = ("quarantined (circuit breaker): pim backend "
                           "disabled for this bucket")
            elif tier == "local":
                pim_bad = pim_local_infeasible(
                    _pim_workload(workload, real), n)
            elif tier == "hbm":
                pim_bad = ("pim hbm: the crossbar model has no "
                           "HBM-resident four-step")
            else:
                pim_bad = pim_dist_infeasible(n, n_devices)
            if pim_bad is None:
                pc = pim_cost(workload, n, batch, tier=tier,
                              n_devices=n_devices, real=real,
                              verified=verified)
                backends["pim"] = pc.as_dict()
                best_backend = ("pim" if pc.total_s <= xc.total_s
                                else "xla")
                total = min(pc.total_s, xc.total_s)
            else:
                backends["pim"] = {"infeasible": pim_bad}
                best_backend, total = "xla", xc.total_s
            candidates.append({"tier": tier, "real": real, "exact": exact,
                               "seq_shards": (n_devices
                                              if tier == "distributed"
                                              else 1),
                               "total_s": total,
                               "backend_best": best_backend,
                               "backends": backends})
    # Deterministic tie-break: cheapest first; on ties prefer local over
    # distributed (fewer moving parts), then real packing over complex
    # (the route the workload named). Sort is stable, so encode the
    # preference in the key.
    candidates.sort(key=lambda c: (c["total_s"],
                                   c["tier"] != "local",
                                   not c["real"]))
    return {"workload": workload, "n": n, "batch": batch,
            "n_devices": n_devices,
            "candidates": candidates, "pruned": pruned,
            "best": candidates[0] if candidates else None,
            "constants": {"peak_flops": PEAK_FLOPS, "hbm_bw": HBM_BW,
                          "link_bw": LINK_BW}}
