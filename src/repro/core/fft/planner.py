"""Execution planner for the Fourier ops: the rules that pick a tier.

``plan(n, batch, ...)`` returns the ``FFTPlan`` a route executes. The
rules, in order (docs/planner.md is the same table, and
``tests/test_routes.py`` pins it through the op registry):

  * ``n`` must be a power of two and ``batch`` non-negative; ``exact``
    and ``real`` are mutually exclusive.
  * ``local``: one VMEM-resident Pallas kernel per row while n is at most
    the tier's ceiling (``_MAX_LOCAL_N``, ``_MAX_LOCAL_N_REAL``,
    ``_MAX_LOCAL_N_EXACT``, all 2^16) and ``force_distributed`` is off.
    The batch block comes from the VMEM budget (doubled for real rows).
  * ``hbm``: one device (``model_shards == 1``), a real plan above the
    ceiling: the four-step real product with its (n1, n2) matrix in HBM
    and rows of ``hbm_inner_n(n)`` through the local kernel
    (``core.fft.hbm``; only polymul-real binds it).
  * ``distributed``: ``model_shards > 1`` above the ceiling, or whenever
    ``force_distributed`` asks for it: the four-step decomposition
    sequence-sharded over the model axis, which needs D^2 | n.
  * Anything else raises ``ValueError`` naming the constraint: a complex
    or exact plan above the ceiling on one device, or a shard count that
    does not tile n.

``plan_grid(shape)`` plans a 3-D transform on one device (tier ``grid``,
``core.fft.ft3d``): every axis a power of two, the x rows within the local
ceiling, and each column pass of the y and z axes within the VMEM budget.

The tiers mirror the paper's configuration ladder (§4.3-4.5): the
r/2r-configurations are "fits in one array" (-> the single-kernel tier,
batch across crossbars ↔ batch across devices), the 2r-beta configuration
is "sequence spans multiple column units" (-> the four-step tier across
devices, with the all-to-all playing the role of the inter-unit column
swaps).
"""
from __future__ import annotations

import dataclasses

from repro.kernels.fft import VMEM_BUDGET_BYTES, plan_batch_block


@dataclasses.dataclass(frozen=True)
class FFTPlan:
    tier: str           # "local" | "hbm" | "distributed" | "grid"
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
    #: hbm tier: the row length n2 of the inner VMEM kernel (n1 = n / n2)
    inner_n: int = 0
    #: grid tier: the grid's (NZ, NY, NX), C order, x contiguous
    grid: tuple[int, ...] = ()

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
        if self.tier == "grid":
            nz, ny, nx = self.grid
            return (f"3-D {kind} of a {nz}x{ny}x{nx} grid on one device: "
                    f"rows of {nx} through the local kernel at batch block "
                    f"{self.block_b}, the {ny}- and {nz}-point axes as "
                    f"column DFT passes over HBM, no transposes")
        if self.tier == "hbm":
            return (f"HBM-resident four-step {kind} on one device: column "
                    f"DFTs and twiddles in HBM passes, rows of "
                    f"{self.inner_n} through the local kernel at batch "
                    f"block {self.block_b}")
        return (f"four-step distributed {kind} over {self.seq_shards} "
                f"devices")


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


def plan(n: int, batch: int, *, model_shards: int = 1,
         exact: bool = False, real: bool = False,
         force_distributed: bool = False) -> FFTPlan:
    """Execution plan for a batch of n-point transforms (rules above).

    ``exact=True`` plans the modular NTT tier (uint32 residues): the local
    Pallas kernel (``kernels.ntt``) or the distributed four-step NTT
    (``core.ntt.distributed``) with per-shard roots. ``real=True`` plans
    the half-spectrum tier with the doubled batch block
    (``plan_batch_block(n, real=True)``). ``force_distributed=True`` pins
    the distributed tier where the rules would keep the sequence local
    (serving's ``--model-shards`` request); the shape checks still apply,
    so the returned plan is the one that executes.
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
    if exact:
        ceiling, name = _MAX_LOCAL_N_EXACT, "_MAX_LOCAL_N_EXACT"
    elif real:
        ceiling, name = _MAX_LOCAL_N_REAL, "_MAX_LOCAL_N_REAL"
    else:
        ceiling, name = _MAX_LOCAL_N, "_MAX_LOCAL_N"
    if not force_distributed and n <= ceiling:
        return FFTPlan(tier="local",
                       block_b=plan_batch_block(n, real=real),
                       seq_shards=1, exact=exact, real=real)
    if model_shards == 1:
        if real:
            inner = hbm_inner_n(n)
            return FFTPlan(tier="hbm", block_b=plan_batch_block(inner),
                           seq_shards=1, real=True, inner_n=inner)
        kind = "an exact" if exact else "a complex"
        raise ValueError(
            f"no single-device tier for {kind} transform of n={n}: it "
            f"exceeds the VMEM-resident ceiling {name}={ceiling} and the "
            f"hbm tier serves real products only; pass model_shards > 1")
    # The four-step tiles D^2 | n for every kind. The distributed real
    # tier's ordered rfft additionally needs 2*D^2 | n for its half-width
    # all-to-all; that is an op property, enforced where the op runs
    # (check_four_step_shape(real=ordered) in the kernel-layer wrappers),
    # since polymul_real only needs D^2 | n.
    _check_dist_shape(n, model_shards)
    return FFTPlan(tier="distributed", block_b=1, seq_shards=model_shards,
                   exact=exact, real=real)


def plan_grid(shape: tuple[int, int, int]) -> FFTPlan:
    """Plan of a 3-D transform of one (NZ, NY, NX) grid on one device
    (tier ``grid``): the x rows through the local kernel at its planned
    block, the y and z axes through ``hbm.axis_dft``. Raises
    ``ValueError`` naming the constraint a grid breaks."""
    from repro.core.fft import hbm
    nz, ny, nx = shape
    for m in shape:
        if m < 2 or m & (m - 1):
            raise ValueError(f"grid {shape}: axis of {m} points is not a "
                             f"power of two >= 2")
    if nx > _MAX_LOCAL_N:
        raise ValueError(f"grid {shape}: rows of NX={nx} exceed the "
                         f"VMEM-resident ceiling _MAX_LOCAL_N={_MAX_LOCAL_N}")
    for m, width in ((ny, nx), (nz, ny * nx)):
        need = hbm.axis_vmem_bytes(m, width)
        if need > VMEM_BUDGET_BYTES:
            raise ValueError(
                f"grid {shape}: the {m}-point column pass needs {need} bytes "
                f"of VMEM, over the budget of {VMEM_BUDGET_BYTES}")
    return FFTPlan(tier="grid", block_b=plan_batch_block(nx), seq_shards=1,
                   grid=tuple(shape))


def _check_dist_shape(n: int, model_shards: int) -> None:
    """Reject shapes the four-step decomposition cannot tile (D^2 | n),
    at plan time, instead of as truncated twiddle blocks mid-trace
    (``core.fft.distributed.check_four_step_shape`` is the same guard at
    the kernel layer)."""
    from repro.core.fft.distributed import check_four_step_shape
    try:
        check_four_step_shape(n, model_shards)
    except ValueError as e:
        raise ValueError(
            f"cannot plan a distributed FFT for n={n} over {model_shards} "
            f"shards: {e}") from e
