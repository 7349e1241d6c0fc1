"""Kernel/distribution planner for Fourier ops.

Given (batch, n, mesh) decide:
  * execution tier, one of three float tiers:
      - ``local``: one VMEM-resident Pallas kernel per row (n up to
        ``_MAX_LOCAL_N``), batch-sharded;
      - ``hbm``: one device, n above that ceiling, real products only: the
        four-step with the (n1, n2) matrix in HBM, column passes in HBM and
        rows of n2 <= the ceiling through the local kernel
        (``core.fft.hbm``);
      - ``distributed``: the four-step FFT sequence-sharded over the model
        axis (``model_shards > 1``);
  * kernel config: batch block (VMEM budget), and the hbm tier's inner
    length.

The decision mirrors the paper's configuration ladder (§4.3-4.5): the
r/2r-configurations are "fits in one array" (-> our single-kernel tier, batch
across crossbars ↔ batch across devices), the 2r-beta configuration is
"sequence spans multiple column units" (-> our four-step tier across devices,
with the all-to-all playing the role of the inter-unit column swaps).
"""
from __future__ import annotations

import dataclasses

from repro.kernels.fft import plan_batch_block


@dataclasses.dataclass(frozen=True)
class FFTPlan:
    tier: str           # "local" | "hbm" | "distributed"
    #: butterfly radix; only the distributed tiers' local XLA stages read
    #: it (the local Pallas kernels are radix-free, docs/fourier.md)
    radix: int
    block_b: int        # batch block per kernel invocation (local tier)
    seq_shards: int     # model-axis shards of the sequence (distributed tier)
    #: exact modular route: transforms dispatch to the NTT kernel
    #: (kernels.ntt, radix-2 Montgomery butterflies) instead of the float
    #: FFT — required for crypto polymul where results must be bit-exact
    #: mod q (docs/ntt.md).
    exact: bool = False
    #: real-Hermitian route: half-spectrum rfft/irfft kernels and the
    #: half-spectrum real polymul (kernels.fft.rfft_planes /
    #: kernels.polymul.polymul_real_planes) — half the matrix work and HBM
    #: traffic of the complex tier on real input, with the doubled batch
    #: block the halved working set buys (docs/fourier.md).
    real: bool = False
    #: machine-readable cost breakdown from ``core.cost.workload_cost``
    #: when the plan was auto-chosen (``plan(..., workload=...)``); None
    #: for explicit-knob plans. Excluded from eq/hash so auto plans and
    #: hand-built plans with the same execution config compare equal
    #: (the serve engine keys buckets on plans).
    cost: dict | None = dataclasses.field(default=None, compare=False)
    #: hbm tier: the row length n2 of the inner VMEM kernel (n1 = n / n2)
    inner_n: int = 0

    def describe(self) -> str:
        if self.exact:
            kind = "NTT (exact mod-q)"
        elif self.real:
            kind = "real-packed FFT (half spectrum)"
        else:
            kind = "FFT"
        if self.tier == "local":
            algo = ("radix-2 in-place stages" if self.exact
                    else "four-step DFT on the MXU")
            return (f"local Pallas {kind} kernel, {algo}, "
                    f"batch block {self.block_b} (VMEM-resident)")
        if self.tier == "hbm":
            return (f"HBM-resident four-step {kind} on one device: column "
                    f"DFTs and twiddles in HBM passes, rows of "
                    f"{self.inner_n} through the local kernel at batch "
                    f"block {self.block_b}")
        return (f"four-step distributed {kind} over {self.seq_shards} "
                f"devices, radix-{self.radix} local stages")


# Local ceilings are compile facts for v5e (tests/test_tpu_compile.py
# compiles the kernels there): at 2^16 every kernel compiles and its planned
# block fits the VMEM budget; at 2^18 the float kernels run out of VMEM even
# at one row. The exact tier holds per-stage twiddle planes instead of DFT
# matrices and has the same ceiling. The real tier's rows are half the
# planes of a complex row, which doubles the batch block, not the ceiling.
_MAX_LOCAL_N = 1 << 16
_MAX_LOCAL_N_EXACT = 1 << 16
_MAX_LOCAL_N_REAL = _MAX_LOCAL_N
# The hbm tier's inner row length, chosen by measuring a batch of 16
# products at n = 2^19 on a TPU v5e at each split (device busy 2.66 ms at
# 2^14, 2.84 at 2^15, 3.51 at 2^13, 3.82 at 2^16; PERF.md), and its fewest
# rows n1: one sublane tile of the column pass.
_HBM_INNER_N = 1 << 14
_HBM_MIN_ROWS = 8


def hbm_inner_n(n: int) -> int:
    """Row length of the hbm tier's inner kernel for an n-point row."""
    return min(_HBM_INNER_N, _MAX_LOCAL_N, n // _HBM_MIN_ROWS)


def _hbm_plan(n: int, cost: dict | None = None) -> FFTPlan:
    inner = hbm_inner_n(n)
    return FFTPlan(tier="hbm", radix=4, block_b=plan_batch_block(inner),
                   seq_shards=1, real=True, cost=cost, inner_n=inner)


def plan(n: int, batch: int, *, model_shards: int = 1,
         exact: bool = False, real: bool = False,
         force_distributed: bool = False,
         workload: str | None = None,
         verified: bool = False, pim_ok: bool = True) -> FFTPlan:
    """Execution plan for a batch of n-point transforms.

    ``exact=True`` routes to the modular-NTT tier (uint32 residues, radix-2
    only — the Montgomery butterfly has no radix-4 shortcut worth the lane
    pressure): the local Pallas kernel (``kernels.ntt``) while a sequence
    fits VMEM, else the four-step distributed decomposition
    (``core.ntt.distributed``) with per-shard roots
    (``NTTParams.subparams``) and ledger-accounted all-to-alls — the plan
    comes back with ``seq_shards > 1`` and ``exact=True``.
    ``real=True`` routes real-coefficient workloads (the paper's polymul
    serving case) to the half-spectrum tier: the rfft/irfft kernels and
    the real ``polymul_real`` with the DOUBLED batch block
    (``plan_batch_block(n, real=True)``) the halved per-row footprint buys.
    The local-n ceiling matches the complex tier. Above it, on one device
    (``model_shards == 1``), a real plan is the ``hbm`` tier: the
    four-step real product with its (n1, n2) matrix in HBM
    (``core.fft.hbm``; only polymul-real binds it). A complex plan above
    the ceiling needs ``model_shards > 1``: with one device it raises.
    Mutually exclusive with ``exact`` (residues are not packed).
    ``force_distributed=True`` pins the distributed tier even where the
    policy would keep the sequence local (serve's explicit --model-shards
    request) — shape validation still applies, so the returned plan is
    the one actually executable, not a hand-built record.
    ``workload=`` switches to AUTO mode (docs/planner.md): the cost model
    in ``core.cost`` scores every executable (tier, packing) candidate —
    local, hbm or four-step; real or complex packing; PIM or XLA backend —
    and the predicted-cheapest one comes back with the full breakdown on
    ``FFTPlan.cost``. Explicit knobs still win: ``real=True`` pins the
    packing, ``force_distributed=True`` pins the tier, and the legacy
    no-workload call is untouched. A workload with no executable
    candidate raises ValueError naming every pruned candidate's
    constraint (VMEM ceiling, ``D^2 | n`` tiling, ``2*D^2 | n`` for the
    ordered real tier) instead of a bare error.
    ``verified=True`` (auto mode only) prices the ABFT integrity check
    (``core.cost.abft_check_cycles``) into every candidate on both
    backends; ``pim_ok=False`` plans with the PIM backend off the table —
    the circuit-breaker re-bind of a quarantined serve bucket
    (docs/fault_tolerance.md).
    Raises ValueError on non-power-of-two n so misuse fails loudly instead
    of silently mis-planning (asserts vanish under ``python -O``).
    """
    if n <= 0 or n & (n - 1):
        raise ValueError(f"n={n} must be a power of two")
    if batch < 0:
        raise ValueError(f"batch={batch} must be non-negative")
    if exact and real:
        raise ValueError("exact (mod-q) and real (Hermitian) tiers are "
                         "mutually exclusive")
    if force_distributed and model_shards == 1:
        raise ValueError("force_distributed needs model_shards > 1")
    if workload is not None:
        return _plan_auto(n, batch, workload, model_shards,
                          exact=exact, real=real,
                          force_distributed=force_distributed,
                          verified=verified, pim_ok=pim_ok)
    if exact:
        if not force_distributed and (n <= _MAX_LOCAL_N_EXACT
                                      or model_shards == 1):
            return FFTPlan(tier="local", radix=2,
                           block_b=plan_batch_block(n), seq_shards=1,
                           exact=True)
        # The four-step NTT tiles identically to the float path (D^2 | n).
        _check_dist_shape(n, model_shards, real=False)
        return FFTPlan(tier="distributed", radix=2, block_b=1,
                       seq_shards=model_shards, exact=True)
    # Local tier: radix-4 halves the sweep count when n allows it. The
    # DISTRIBUTED tiers run their local stages through the XLA Stockham
    # (kops.fft, radix 2), so their plans record radix=2 — the plan
    # describes what executes, not the local kernel's preference.
    radix = 4 if (n.bit_length() - 1) >= 2 else 2
    if real:
        if not force_distributed and n <= _MAX_LOCAL_N_REAL:
            return FFTPlan(tier="local", radix=radix,
                           block_b=plan_batch_block(n, real=True),
                           seq_shards=1, real=True)
        if model_shards == 1:
            return _hbm_plan(n)
        # Distributed real tier: the four-step path runs the packed complex
        # transform on z = a + i b per row PAIR with the Hermitian split
        # performed per shard before the ordering all-to-all, so the
        # half-spectrum crosses the interconnect at half the complex width
        # (core/fft/distributed.py rfft_distributed; ~0.58x the complex
        # tier's collective bytes — docs/fourier.md §distributed).
        # Validated at the tier's common requirement (D^2 | n, the
        # transposes + twiddle tiling); the ordered-rfft half-width
        # all-to-all additionally needs 2*D^2 | n, enforced where it is an
        # op property, not a tier property: check_four_step_shape(real=
        # ordered) in the kernel-layer wrappers. polymul_real only needs
        # D^2 | n, so the plan must not reject shapes it can execute.
        _check_dist_shape(n, model_shards, real=False)
        return FFTPlan(tier="distributed", radix=2, block_b=1,
                       seq_shards=model_shards, real=True)
    if not force_distributed and n <= _MAX_LOCAL_N:
        return FFTPlan(tier="local", radix=radix,
                       block_b=plan_batch_block(n), seq_shards=1)
    if model_shards == 1:
        raise ValueError(
            f"no single-device tier for a complex transform of n={n}: it "
            f"exceeds the VMEM-resident ceiling _MAX_LOCAL_N={_MAX_LOCAL_N}"
            f" and the hbm tier serves real products only; pass "
            f"model_shards > 1")
    _check_dist_shape(n, model_shards, real=False)
    return FFTPlan(tier="distributed", radix=2, block_b=1,
                   seq_shards=model_shards)


def _plan_auto(n: int, batch: int, workload: str, model_shards: int, *,
               exact: bool, real: bool, force_distributed: bool,
               verified: bool = False, pim_ok: bool = True) -> FFTPlan:
    """Cost-model-driven tier choice (docs/planner.md).

    The candidate space is every (tier, packing) pair the XLA kernels can
    execute for ``workload``; ``core.cost.workload_cost`` scores each on
    both backends and this returns the predicted-cheapest as a normal
    executable ``FFTPlan`` with the breakdown attached. Explicit knobs
    narrow the space rather than being ignored: ``real=True`` keeps only
    real-packed candidates, ``force_distributed=True`` only distributed
    ones. ``exact=`` must agree with the workload — the modular route is
    a workload property (``polymul-mod``), not a packing choice.
    """
    from repro.core.cost import WORKLOADS, workload_cost
    if workload not in WORKLOADS:
        raise ValueError(f"unknown workload {workload!r}; "
                         f"expected one of {WORKLOADS}")
    wl_exact = workload == "polymul-mod"
    if exact and not wl_exact:
        raise ValueError(f"exact=True conflicts with workload="
                         f"{workload!r}: the exact mod-q route is the "
                         f"'polymul-mod' workload")
    if real and workload not in ("rfft", "polymul-real"):
        raise ValueError(f"real=True conflicts with workload="
                         f"{workload!r}: only 'rfft'/'polymul-real' "
                         f"have a real-packed route")
    tiers = (("distributed",) if force_distributed
             else ("local", "distributed", "hbm"))
    packings = [True] if real else None
    breakdown = workload_cost(workload, n, batch, n_devices=model_shards,
                              tiers=tiers, packings=packings,
                              verified=verified, pim_ok=pim_ok)
    best = breakdown["best"]
    if best is None:
        lines = [
            f"  - tier={p['tier']}"
            f"{', real-packed' if p['real'] else ''}: {p['reason']}"
            for p in breakdown["pruned"]]
        raise ValueError(
            f"no executable tier for workload={workload!r} n={n} over "
            f"{model_shards} shard(s); every candidate was pruned:\n"
            + "\n".join(lines))
    if best["tier"] == "local":
        radix = (2 if wl_exact
                 else (4 if (n.bit_length() - 1) >= 2 else 2))
        block = (plan_batch_block(n, real=True) if best["real"]
                 else plan_batch_block(n))
        return FFTPlan(tier="local", radix=radix, block_b=block,
                       seq_shards=1, exact=wl_exact, real=best["real"],
                       cost=breakdown)
    if best["tier"] == "hbm":
        return _hbm_plan(n, cost=breakdown)
    return FFTPlan(tier="distributed", radix=2, block_b=1,
                   seq_shards=model_shards, exact=wl_exact,
                   real=best["real"], cost=breakdown)


def _check_dist_shape(n: int, model_shards: int, *, real: bool) -> None:
    """Reject shapes the four-step decomposition cannot tile.

    The distributed tier needs D^2 | n (2*D^2 | n for the real tier's
    half-width ordering all-to-all). Such a shape cannot be re-tiered
    locally either — the planner only reaches here when n exceeds the
    local VMEM ceiling — so mis-sized shard counts fail at plan time
    instead of surfacing as truncated twiddle blocks mid-trace
    (``core.fft.distributed.check_four_step_shape`` is the same guard at
    the kernel layer).
    """
    from repro.core.fft.distributed import check_four_step_shape
    try:
        check_four_step_shape(n, model_shards, real=real)
    except ValueError as e:
        raise ValueError(
            f"cannot plan a distributed {'real ' if real else ''}FFT for "
            f"n={n} over {model_shards} shards: {e}") from e
