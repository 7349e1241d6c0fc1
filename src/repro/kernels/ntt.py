"""Pallas TPU kernel: batched NTT + fused modular polymul.

The exact (mod-q) counterpart of ``kernels/fft.py`` / ``kernels/polymul.py``:
the same single-VMEM-residency, row-at-a-time structure over the same (R, C)
view of a row (``kernels.fft.split``), with radix-2 modular butterflies in
uint32 lanes. This opens the paper's §5 crypto workload end to end —
RLWE/FHE polynomial products must be bit-exact, which the float FFT path
cannot deliver.

Arithmetic strategy (all in 32-bit lanes; no 64-bit integers needed, so the
kernel runs identically under jax's default x64-disabled config and on TPU):

* Residues live in uint32, q an odd prime < 2^31 (``core.ntt.ref`` selects
  it), so sums fit without carry columns and 2q < 2^32.
* 32x32 -> 64-bit products are built from four 16x16 partial products with
  explicit carry recovery (``_mul32_full``) — the VPU analogue of AritPIM's
  bit-serial shift-and-add multiplier.
* Twiddles are stored in Montgomery form (w^k * 2^32 mod q), so each
  butterfly multiply is ONE Montgomery REDC: mont(v, w_mont) = v*w mod q.
  Data itself stays in the normal domain throughout — the same trick NTT
  libraries use so no domain conversion passes are needed.

Butterfly schedule (docs/ntt.md): in-place, no permutation inside a kernel.
A stage of distance d pairs element i with i ^ d. For d >= C the partner is
d/C sublane rows away, below C it is d lanes away; either way it is one
``pltpu.roll`` of the (R, C) row plane plus a select, so no stage ever
shrinks or strides the lane axis. The forward transform is decimation in
frequency (natural in, bit-reversed out), the inverse decimation in time
(bit-reversed in, natural out): in the fused polymul the two orders cancel
(the paper's §5 permutation cancellation), and ``ntt_batched`` restores
natural order with one gather outside the kernel.

The fused ``ntt_polymul`` folds the negacyclic psi-twist into the input
multiply and the psi^{-1}/n untwist into the output multiply — the exact
analogue of ``kernels/polymul.py``'s fusion (paper §5): forward x2 ->
pointwise modmul -> inverse, one VMEM residency, zero extra passes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ntt.ref import NTTParams, bit_reverse_indices
from repro.kernels.fft import (_fit_block, batched_call, for_each_row,
                               pad_rows, plan_batch_block, resolve_interpret,
                               split)

# Plain Python ints: weak-typed scalars stay out of the kernel closure
# (pallas_call rejects captured traced constants).
_U16 = 16
_MASK16 = 0xFFFF


# ---------------------------------------------------------------------------
# uint32 modular primitives
# ---------------------------------------------------------------------------

def _mul32_full(a, b):
    """Full 64-bit product of uint32 lanes as a (hi, lo) uint32 pair.

    Four 16x16 partials; each fits uint32 exactly. Carries recovered with
    unsigned-compare tricks (x + y wrapped iff result < x).
    """
    a0, a1 = a & _MASK16, a >> _U16
    b0, b1 = b & _MASK16, b >> _U16
    ll = a0 * b0
    lh = a0 * b1
    hl = a1 * b0
    hh = a1 * b1
    mid = lh + hl
    carry_mid = (mid < lh).astype(jnp.uint32)
    lo = ll + (mid << _U16)
    carry_lo = (lo < ll).astype(jnp.uint32)
    hi = hh + (mid >> _U16) + (carry_mid << _U16) + carry_lo
    return hi, lo


def _u32(v):
    """uint32 scalar from either a static python int or a traced value (the
    RNS kernel reads per-limb constants out of a Ref, so q/qinv/r2 arrive as
    tracers there while the single-modulus kernels keep them static)."""
    return v if isinstance(v, jax.Array) else jnp.uint32(v)


def _mont_mul(a, b, q, qinv):
    """Montgomery product a*b*2^-32 mod q (q odd, < 2^31; qinv = -q^-1 mod
    2^32). With b in Montgomery form this is a*b mod q in one REDC."""
    qq = _u32(q)
    hi, lo = _mul32_full(a, b)
    m = lo * _u32(qinv)                       # mod 2^32 wrap is the point
    mq_hi, _ = _mul32_full(m, qq)
    # lo + (m*q mod 2^32) == 0 mod 2^32 by construction: carry iff lo != 0.
    t = hi + mq_hi + (lo != 0).astype(jnp.uint32)
    return jnp.where(t >= qq, t - qq, t)      # t < 2q always


def _add_mod(a, b, q):
    qq = _u32(q)
    s = a + b                                  # a, b < q < 2^31: no wrap
    return jnp.where(s >= qq, s - qq, s)


def _sub_mod(a, b, q):
    return jnp.where(a >= b, a - b, a + _u32(q) - b)


# ---------------------------------------------------------------------------
# In-place radix-2 stages on one (R, C) row plane
# ---------------------------------------------------------------------------

def _distances(n: int) -> list[int]:
    """Butterfly distances of the decimation-in-frequency order: n/2 .. 1."""
    return [n >> s for s in range(1, n.bit_length())]


def _partner(x, d: int, axis: int):
    """(x[i ^ d] along ``axis``, mask of positions whose bit d is set).

    ``pltpu.roll`` by d brings either x[i - d] or x[i + d] to position i;
    rolling the index plane the same way tells which, so the select holds
    whichever direction the roll has.
    """
    i = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    down = pltpu.roll(i, d, axis) == (i ^ d)
    p = jnp.where(down, pltpu.roll(x, d, axis),
                  pltpu.roll(x, x.shape[axis] - d, axis))
    return p, (i & d) != 0


def _stage(rows, lanes, s: int, d: int, shape):
    """(roll axis, distance along it, twiddle plane) of the stage with
    element distance d, the s-th of the DIF order, on an (R, C) plane."""
    r_, c_ = shape
    if d >= c_:
        return 0, d // c_, rows[s]
    return 1, d, lanes[s - (r_.bit_length() - 1)]


def ntt_dif(x, rows, lanes, q, qinv):
    """Forward cyclic NTT of one (R, C) row, natural in, bit-reversed out.

    ``rows``/``lanes`` are the ``stage_tables(..., dif=True)`` ref or array
    stacks: stage s turns the pair (u, v) into (u + v, (u - v) * w_s), with
    Montgomery one at the lower positions so one multiply covers both.
    """
    for s, dist in enumerate(_distances(x.size)):
        axis, d, tw = _stage(rows, lanes, s, dist, x.shape)
        p, up = _partner(x, d, axis)
        v = jnp.where(up, _sub_mod(p, x, q), _add_mod(x, p, q))
        x = _mont_mul(v, tw, q, qinv)
    return x


def ntt_dit(x, rows, lanes, q, qinv):
    """Unscaled cyclic NTT of one (R, C) row, bit-reversed in, natural out
    (``stage_tables(..., dif=False)``): (u, v) -> (u + v w_s, u - v w_s)."""
    dists = _distances(x.size)
    for s in reversed(range(len(dists))):
        axis, d, tw = _stage(rows, lanes, s, dists[s], x.shape)
        p, up = _partner(x, d, axis)
        v = _mont_mul(jnp.where(up, x, p), tw, q, qinv)
        x = jnp.where(up, _sub_mod(p, v, q), _add_mod(x, v, q))
    return x


@functools.lru_cache(maxsize=64)
def stage_tables(params: NTTParams, base: int, dif: bool
                 ) -> tuple[np.ndarray, np.ndarray]:
    """Per-stage Montgomery twiddles, uint32, in the DIF stage order.

    The stage of distance d multiplies element i by base^((i mod d) n/(2d))
    — for i < d exactly the old strided slice ``W[::n/(2d)][:d]`` of the
    master table W = base^k. ``dif=True`` puts Montgomery one at the lower
    positions (bit d clear). Stages with d >= C vary over the whole plane:
    ``rows`` is (max(1, #such stages), R, C). Below C they vary along lanes
    only: ``lanes`` is (#such stages, 1, C). Cached as numpy.
    """
    n = params.n
    r_, c_ = split(n)
    pw = params.powers(base)
    i = np.arange(n)
    one = params.to_montgomery(np.ones(1, np.uint64))[0]
    rows, lanes = [], []
    for d in _distances(n):
        t = params.to_montgomery(pw[(i % d) * (n // (2 * d))])
        if dif:
            t = np.where(i & d, t, one)
        t = t.astype(np.uint32)
        if d >= c_:
            rows.append(t.reshape(r_, c_))
        else:
            lanes.append(t[:c_].reshape(1, c_))
    if not rows:
        rows.append(np.zeros((r_, c_), np.uint32))      # never read
    return np.stack(rows), np.stack(lanes)


def untwist_table(params: NTTParams, negacyclic: bool) -> np.ndarray:
    """Output-pass multiplier values (normal domain, uint64): psi^{-j}·n^{-1}
    for the negacyclic untwist+scale, or the n^{-1} broadcast for cyclic.
    THE single definition — the fused kernel, the RNS limb tables, and the
    distributed four-step edge passes all read it from here, so a change to
    the untwist convention cannot silently diverge per path."""
    if negacyclic:
        return (params.powers(params.psi_inv) * np.uint64(params.n_inv)
                % np.uint64(params.q))
    return np.full(params.n, params.n_inv, np.uint64)


@functools.lru_cache(maxsize=64)
def polymul_tables(params: NTTParams, negacyclic: bool
                   ) -> tuple[np.ndarray, ...]:
    """Every table of the fused polymul, uint32: forward DIF and inverse
    DIT stage stacks, and the (R, C) twist and untwist planes (Montgomery
    form; the untwist folds in n^{-1})."""
    r_, c_ = split(params.n)
    twist = params.to_montgomery(
        params.powers(params.psi) if negacyclic
        else np.ones(params.n, np.uint64))            # cyclic: identity
    untwist = params.to_montgomery(untwist_table(params, negacyclic))
    return (*stage_tables(params, params.w, True),
            *stage_tables(params, params.w_inv, False),
            twist.astype(np.uint32).reshape(r_, c_),
            untwist.astype(np.uint32).reshape(r_, c_))


def _polymul_row(a, b, tabs, q, qinv, r2, negacyclic: bool):
    """twist -> DIF x2 -> pointwise (one operand hops into Montgomery form
    via r2) -> DIT -> untwist·n^{-1}, one (R, C) row."""
    fr, fl, ir, il, twist, untwist = tabs
    if negacyclic:                 # psi^j twist: x^n+1 products via cyclic NTT
        a = _mont_mul(a, twist[...], q, qinv)
        b = _mont_mul(b, twist[...], q, qinv)
    fa = ntt_dif(a, fr, fl, q, qinv)
    fb = ntt_dif(b, fr, fl, q, qinv)
    p = _mont_mul(_mont_mul(fa, _u32(r2), q, qinv), fb, q, qinv)
    return _mont_mul(ntt_dit(p, ir, il, q, qinv), untwist[...], q, qinv)


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

def _ntt_kernel(rows_ref, lanes_ref, x_ref, o_ref, *, q: int, qinv: int,
                dif: bool, scale_mont: int | None):
    stages = ntt_dif if dif else ntt_dit

    def row(b):
        y = stages(x_ref[b], rows_ref, lanes_ref, q, qinv)
        if scale_mont is not None:     # inverse: fold in n^-1 (Montgomery)
            y = _mont_mul(y, jnp.uint32(scale_mont), q, qinv)
        o_ref[b] = y
    for_each_row([x_ref], row)


def _ntt_polymul_kernel(*refs, q: int, qinv: int, r2: int, negacyclic: bool):
    """Fused modular polymul, one VMEM residency (paper §5 structure, exact
    arithmetic)."""
    *tabs, a_ref, b_ref, c_ref = refs

    def row(b):
        c_ref[b] = _polymul_row(a_ref[b], b_ref[b], tabs, q, qinv, r2,
                                negacyclic)
    for_each_row([a_ref], row)


def _rns_ntt_polymul_kernel(scal_ref, *refs, negacyclic: bool,
                            prefetch: bool):
    """One grid cell = one (limb, batch-block) tile of the RNS polymul.

    Identical dataflow to ``_ntt_polymul_kernel``; the limb's modulus
    constants are *data* (scal_ref row: q, qinv, r2) instead of closure
    constants, which is what lets k different-q transforms share a single
    pallas launch on the (limb, batch) grid.

    ``prefetch=True`` is the scalar-prefetch layout
    (``pltpu.PrefetchScalarGridSpec``): ``scal_ref`` is the WHOLE (k, 4)
    table resident in SMEM before the body runs — the per-limb constants
    never occupy a VMEM block. ``prefetch=False`` is the scalar-Ref layout
    (a (1, 4) VMEM block per grid cell). Both paths are pinned bit-exactly
    equal in tests/test_rns_ntt.py.
    """
    *tabs, a_ref, b_ref, c_ref = refs
    if prefetch:
        row_id = pl.program_id(0)
        q, qinv, r2 = (scal_ref[row_id, j] for j in range(3))
    else:
        q, qinv, r2 = (scal_ref[0, j] for j in range(3))

    def row(b):
        c_ref[b] = _polymul_row(a_ref[b], b_ref[b], tabs, q, qinv, r2,
                                negacyclic)
    for_each_row([a_ref], row)


# ---------------------------------------------------------------------------
# Host-side wrappers
# ---------------------------------------------------------------------------

def _as_residues(x, q: int):
    """Reduce integer coefficients into [0, q) uint32 — same contract as
    ``core.ntt.ref.as_residues``: floats raise, negatives wrap Python-style.
    The in-kernel butterflies assume operands < q; skipping this reduction
    would silently corrupt results for unreduced input."""
    x = jnp.asarray(x)
    assert x.ndim == 2, f"expected (batch, n), got {x.shape}"
    if not jnp.issubdtype(x.dtype, jnp.integer):
        raise TypeError(f"NTT needs integer residues, got {x.dtype}")
    if jnp.issubdtype(x.dtype, jnp.signedinteger):
        # jnp.remainder takes the divisor's sign: (-1) % q == q - 1, and
        # the result fits int32 since q < 2^31.
        return (x.astype(jnp.int32) % q).astype(jnp.uint32)
    return x.astype(jnp.uint32) % jnp.uint32(q)


@functools.partial(jax.jit, static_argnames=("params", "inverse",
                                             "interpret", "block_b"))
def ntt_batched(x: jax.Array, params: NTTParams, *, inverse: bool = False,
                interpret: bool | None = None, block_b: int | None = None
                ) -> jax.Array:
    """Batched cyclic NTT of uint32 residues (B, n) mod ``params.q``.

    Bit-exact equal to ``core.ntt.ref.ntt``/``intt`` (tests/test_ntt.py).
    Same grid/tiling contract as ``fft_planes``. The forward kernel leaves
    bit-reversed order and the inverse kernel expects it, so one gather
    (after the forward, before the inverse) keeps the API in natural order.
    """
    x = _as_residues(x, params.q)
    b, n = x.shape
    assert n == params.n, f"n={n} != params.n={params.n}"
    interpret = resolve_interpret(interpret)
    r_, c_ = split(n)
    rev = jnp.asarray(bit_reverse_indices(n))
    if inverse:
        x = x[:, rev]
    blk = block_b or _fit_block(plan_batch_block(n), b)
    (xp,) = pad_rows([x.reshape(b, r_, c_)], blk)
    scale = params.n_inv * (1 << 32) % params.q if inverse else None
    kern = functools.partial(_ntt_kernel, q=params.q, qinv=params.qinv,
                             dif=not inverse, scale_mont=scale)
    base = params.w_inv if inverse else params.w
    (y,) = batched_call(kern, stage_tables(params, base, not inverse), [xp],
                        [jax.ShapeDtypeStruct(xp.shape, jnp.uint32)],
                        blk=blk, interpret=interpret)
    y = y[:b].reshape(b, n)
    return y if inverse else y[:, rev]


@functools.partial(jax.jit, static_argnames=("params", "negacyclic",
                                             "interpret", "block_b"))
def ntt_polymul(a: jax.Array, b: jax.Array, params: NTTParams, *,
                negacyclic: bool = True, interpret: bool | None = None,
                block_b: int | None = None) -> jax.Array:
    """Exact polynomial product mod (x^n + 1, q) — or x^n - 1 with
    ``negacyclic=False`` — of residue batches (B, n), fully fused.

    Matches ``core.ntt.ref.negacyclic_polymul`` (and the schoolbook oracle)
    bit-exactly; see docs/ntt.md for the RLWE semantics.
    """
    a = _as_residues(a, params.q)
    bb = _as_residues(b, params.q)
    assert a.shape == bb.shape
    bsz, n = a.shape
    assert n == params.n, f"n={n} != params.n={params.n}"
    interpret = resolve_interpret(interpret)
    r_, c_ = split(n)
    blk = block_b or _fit_block(max(1, plan_batch_block(n) // 2), bsz)
    ops = pad_rows([v.reshape(bsz, r_, c_) for v in (a, bb)], blk)
    kern = functools.partial(_ntt_polymul_kernel, q=params.q,
                             qinv=params.qinv, r2=params.r2,
                             negacyclic=negacyclic)
    (c,) = batched_call(kern, polymul_tables(params, negacyclic), ops,
                        [jax.ShapeDtypeStruct(ops[0].shape, jnp.uint32)],
                        blk=blk, interpret=interpret)
    return c[:bsz].reshape(bsz, n)


# ---------------------------------------------------------------------------
# RNS: k limbs through one launch on the (limb, batch) grid
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _rns_tables(rns, negacyclic: bool):
    """Per-limb stacks for the RNS kernel, all uint32: scalars (k, 1, 4) =
    [q, qinv, r2, 0] and each ``polymul_tables`` array with a leading limb
    axis, so every block is one limb's whole table (its last two dims are
    the table's own). Cached on the hashable RNSParams as NUMPY arrays
    (caching jnp values across jit traces would leak tracers)."""
    scal = np.array([[(p.q, p.qinv, p.r2, 0)] for p in rns.limbs], np.uint32)
    per_limb = [polymul_tables(p, negacyclic) for p in rns.limbs]
    return (scal, *(np.stack(t) for t in zip(*per_limb)))


@functools.partial(jax.jit, static_argnames=("rns", "negacyclic",
                                             "interpret", "block_b",
                                             "scalar_prefetch"))
def rns_ntt_polymul(ar: jax.Array, br: jax.Array, rns, *,
                    negacyclic: bool = True, interpret: bool | None = None,
                    block_b: int | None = None,
                    scalar_prefetch: bool | None = None) -> jax.Array:
    """Limb-batched exact polymul: residue stacks (k, B, n) -> (k, B, n).

    ``rns`` is a ``core.ntt.rns.RNSParams`` (kept opaque here so the kernel
    layer has no core->kernels cycle); inputs are per-limb REDUCED residues
    (< q_i each, as ``rns.to_rns`` produces). All k limbs and all batch
    blocks run through ONE pallas launch on the (limb, batch-block) grid, so
    an 8-limb 100-bit-Q product costs one kernel dispatch, not eight.
    CRT reconstruction (``rns.crt_to_modulus``) lives with the caller.

    ``scalar_prefetch`` hoists the per-limb q/qinv/r2 table to TPU scalar
    prefetch (SMEM, ``PrefetchScalarGridSpec``) instead of streaming it as
    a (1, 4) VMEM block per grid cell. Default: enabled exactly when the
    kernel compiles for real hardware (``not interpret``); pass explicitly
    to pin either layout (tests force both and assert bit-equality).
    """
    ar = jnp.asarray(ar)
    br = jnp.asarray(br)
    assert ar.shape == br.shape and ar.ndim == 3, (ar.shape, br.shape)
    assert ar.dtype == jnp.uint32 and br.dtype == jnp.uint32, \
        "RNS kernel wants pre-reduced uint32 residue stacks (rns.to_rns)"
    k, bsz, n = ar.shape
    assert k == rns.k and n == rns.n, (ar.shape, rns.k, rns.n)
    interpret = resolve_interpret(interpret)
    r_, c_ = split(n)
    blk = block_b or _fit_block(max(1, plan_batch_block(n) // 2), bsz)
    pad = (-bsz) % blk
    ar, br = (jnp.pad(v.reshape(k, bsz, r_, c_),
                      ((0, 0), (0, pad), (0, 0), (0, 0))) for v in (ar, br))
    bp = bsz + pad
    scal, *tabs = (jnp.asarray(t) for t in _rns_tables(rns, negacyclic))
    prefetch = (not interpret) if scalar_prefetch is None else scalar_prefetch
    kern = functools.partial(_rns_ntt_polymul_kernel, negacyclic=negacyclic,
                             prefetch=prefetch)
    out_shape = jax.ShapeDtypeStruct((k, bp, r_, c_), jnp.uint32)
    # With prefetch the index maps get the scalar Ref as a trailing argument.
    def limb_spec(shape):        # one limb's whole table, limb axis squeezed
        nd = len(shape) - 1
        return pl.BlockSpec((None,) + tuple(shape[1:]),
                            lambda l, i, *_: (l,) + (0,) * nd)

    bspec = pl.BlockSpec((None, blk, r_, c_), lambda l, i, *_: (l, i, 0, 0))
    in_specs = [limb_spec(t.shape) for t in tabs] + [bspec, bspec]
    if prefetch:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(k, bp // blk),
            in_specs=in_specs, out_specs=bspec)
        c = pl.pallas_call(kern, grid_spec=grid_spec, out_shape=out_shape,
                           interpret=interpret)(scal[:, 0], *tabs, ar, br)
    else:
        c = pl.pallas_call(
            kern, grid=(k, bp // blk),
            in_specs=[limb_spec(scal.shape)] + in_specs, out_specs=bspec,
            out_shape=out_shape, interpret=interpret,
        )(scal, *tabs, ar, br)
    return c[:, :bsz].reshape(k, bsz, n)
