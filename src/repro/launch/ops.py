"""Unified op-dispatch registry: the one source of op truth for serving.

Every transform endpoint the repo serves — fft / rfft / polymul /
polymul-real / polymul-mod, including their RNS and distributed
parameterizations — is described ONCE here as an :class:`OpSpec` and
resolved into a :class:`BoundOp` (plan + route + jitted batch fn + payload
conventions) by ``OpSpec.bind``. ``launch/serve.py`` (both the single-op
``FFTService`` and the continuous-batching ``--service engine``),
``launch/engine.py``, ``benchmarks/run.py --smoke`` and the serve tests all
dispatch through this table instead of carrying their own per-op ``if``
ladders, so adding an endpoint is one ``register_op`` call.

The OpSpec contract (docs/serving.md):

  * ``arity``            — payload operands per request (1 = transform,
                           2 = product); the engine stacks them host-side.
  * ``route(n, ctx)``    — the op's one route decision: validate the
                           ``(op, n, modulus_bits, model_shards)``
                           combination (raising :class:`OpConfigError`, a
                           ``ValueError`` subclass, with the registry's own
                           message — no deep ``ValueError`` from three
                           layers down) and return ``(plan, route)``,
                           building no mesh and no jit.
  * ``bind(n, ctx)``     — the route decision, then the executable route:
                           jitted batch fn, and any NTT/RNS params or mesh
                           the route needs.
  * ``warmup`` payload   — zeros of the route's payload dtype, so deploy
                           warmup compiles the steady-state shape.
  * ``random_payload``   — the honest traffic generator (complex payloads
                           for the complex endpoint, big-int coefficients
                           for RNS, ...) the producers draw from.
  * ``verify``           — a numpy-oracle check of one served result
                           (exact ``==`` for the modular routes).

Config knobs that an op does not consume are rejected by ``bind`` (strict
mode, the CLI single-op path) or stripped by ``OpSpec.narrow`` (the mixed
engine, where one process-level context feeds ops with different knobs).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Sequence

import numpy as np

from repro import telemetry


class OpConfigError(ValueError):
    """Invalid (op, n, modulus_bits, model_shards) combination, raised at
    registry-validation time — before any mesh/jit work — with a message
    that names the offending knob."""


@dataclasses.dataclass(frozen=True)
class OpContext:
    """Process-level route parameterization shared by every op of a serve
    process (CLI flags); ``OpSpec.narrow`` strips the knobs an op ignores.

    ``verified=True`` binds the route for ABFT-checked serving
    (docs/fault_tolerance.md): the RNS route proves at bind time that its
    modulus is checkable (factors over the limb primes). It applies to
    every op, so ``narrow`` preserves it."""
    modulus_bits: int | None = None
    model_shards: int = 1
    verified: bool = False


@dataclasses.dataclass
class BoundOp:
    """A resolved (op, n, context): everything an executor needs.

    ``fn`` takes the stacked operand arrays (``stack``'s output, one array
    per operand) at ANY batch size — tail batches run at their actual size;
    the kernels' ``_fit_block`` clamps the VMEM block instead of padding.

    ``result_dtype`` is ``np.complex64`` on the routes whose results are
    complex: their ``fn`` returns the same float32 numbers as one array of
    words, (re, im) interleaved along the last axis (``_as_words``), and
    ``to_numpy`` views those as complex64. It is None where the host result
    has the dtype the device returns.

    ``execute`` and ``to_numpy`` record spans (``repro.telemetry``) into
    the recorder current on the calling thread: ``serve.dispatch`` over
    ``serve.stack``, ``serve.h2d`` and ``serve.launch``; ``serve.fetch``
    over ``serve.sync``, ``serve.d2h`` and, where the result comes back as
    words, ``serve.join``.

    On every route but RNS, ``stack`` writes each batch into host slots
    the bound op owns and reuses (:class:`_SlotRing`), counting
    ``stage.slot_alloc`` where it (re)allocates them.
    """
    spec: "OpSpec"
    n: int
    ctx: OpContext
    plan: Any                       # core.fft.planner.FFTPlan
    route: str
    fn: Callable[..., Any]
    payload_dtype: Any              # numpy dtype, or object for big ints
    result_dtype: Any = None        # complex64 where fn returns words
    ntt_params: Any = None
    rns: Any = None
    mesh: Any = None
    _slots: "_SlotRing | None" = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    @property
    def key(self) -> tuple[str, int]:
        return (self.spec.name, self.n)

    def stack(self, payloads: Sequence[Any]) -> tuple:
        """Host-side batch assembly: a list of per-request payloads ->
        the operand arrays ``fn`` consumes, at the list's actual length.

        The RNS route stacks into fresh object arrays and stays on the
        host. Every other route writes the batch into the leading rows of
        a slot of its ring (``_SlotRing.take``) and sends those views to
        the device."""
        import jax.numpy as jnp
        with telemetry.span("serve.stack"):
            if self.payload_dtype is object:      # RNS: stays host-side
                return _stack_operands(self, payloads)
            cols = self._stage(payloads)
        with telemetry.span("serve.h2d"):
            operands = tuple(jnp.asarray(c) for c in cols)
        # until ``execute`` names the result that reads them
        self._slots.read_by(operands)
        return operands

    def _stage(self, payloads: Sequence[Any]) -> tuple[np.ndarray, ...]:
        """Write the batch into a slot of the ring. A batch with more rows than
        the ring makes a new ring of at least twice the rows, so warming
        every size from 1 to B allocates about log2(B) rings, not B."""
        rows = len(payloads)
        if self._slots is None or rows > self._slots.rows:
            grown = 2 * self._slots.rows if self._slots is not None else 0
            self._slots = _SlotRing(max(rows, grown), self.n,
                                    self.payload_dtype, self.spec.arity)
            telemetry.count("stage.slot_alloc")
        return _stack_operands(self, payloads, out=self._slots.take(rows))

    def execute(self, payloads: Sequence[Any]):
        """Dispatch one batch (async where the route is a jitted fn)."""
        with telemetry.span("serve.dispatch"):
            operands = self.stack(payloads)
            with telemetry.span("serve.launch"):
                out = self.fn(*operands)
            if self._slots is not None:
                self._slots.read_by(out)
            return out

    def to_numpy(self, out) -> np.ndarray:
        """Materialize a dispatched batch (blocks until ready)."""
        import jax
        with telemetry.span("serve.fetch"):
            if self.payload_dtype is object:  # RNS: already on the host
                return np.asarray(out)
            with telemetry.span("serve.sync"):
                out = jax.block_until_ready(out)
            with telemetry.span("serve.d2h"):
                arr = np.asarray(out)
            if self.result_dtype is None:
                return arr
            with telemetry.span("serve.join"):
                return arr.view(self.result_dtype)

    def warmup(self, batch: int) -> None:
        """Compile the route at the steady-state batch (deploy warmup)."""
        zeros = self.spec.warmup_payload(self, batch)
        self.to_numpy(self.fn(*zeros))

    def random_payload(self, rng: np.random.Generator):
        return self.spec.random_payload(self, rng)

    def verify(self, payload, result: np.ndarray) -> float:
        """Check one served result against the op's numpy or big-int
        oracle (raises AssertionError on a miss); returns the relative
        error, 0.0 for the exact routes."""
        return self.spec.verify(self, payload, result)

    def check_payload(self, payload) -> None:
        """Admission guard: reject non-finite float/complex operands with
        a structured :class:`OpConfigError` BEFORE they join a batch — a
        NaN poisons every row it batches with, and once ABFT is on it
        would masquerade as an integrity failure and burn the retry
        budget on a client bug."""
        operands = (payload,) if self.spec.arity == 1 else tuple(payload)
        for i, op in enumerate(operands):
            arr = np.asarray(op)
            if arr.dtype == object or not np.issubdtype(arr.dtype,
                                                        np.inexact):
                continue            # big ints / residues: no NaN to carry
            if not np.isfinite(arr).all():
                raise OpConfigError(
                    f"op {self.spec.name!r}: operand {i} contains "
                    f"non-finite values (NaN/Inf) — rejected at submit "
                    f"(it would poison the whole batch)")

    def integrity(self, payloads: Sequence[Any],
                  rows: np.ndarray):
        """Run the op's ABFT check on one DELIVERABLE batch: the stacked
        result rows against the request payloads (ft/abft.py). Returns
        an ``IntegrityVerdict``."""
        return self.spec.integrity(self, payloads, rows)


@dataclasses.dataclass(frozen=True)
class OpSpec:
    """Registry entry: the full contract of one serveable op."""
    name: str
    arity: int
    summary: str
    uses_modulus_bits: bool
    uses_model_shards: bool
    #: the op's route decision: ``(spec, n, ctx) -> (plan, route)``
    _route: Callable[["OpSpec", int, OpContext], tuple[Any, str]]
    _bind: Callable[["OpSpec", int, OpContext, Any, str], BoundOp]
    warmup_payload: Callable[[BoundOp, int], tuple]
    random_payload: Callable[[BoundOp, np.random.Generator], Any]
    verify: Callable[[BoundOp, Any, np.ndarray], float]
    #: batch-level ABFT check (ft/abft.py): ``integrity(bound, payloads,
    #: rows) -> IntegrityVerdict`` validating a whole result batch
    #: against its request payloads in O(n) per row — the gate the
    #: verified serve engine runs before delivering any result.
    integrity: Callable[[BoundOp, Sequence[Any], np.ndarray], Any] = None

    def route(self, n: int, ctx: OpContext = OpContext()
              ) -> tuple[Any, str]:
        """The plan and route tag (n, ctx) binds to; raises
        :class:`OpConfigError` unless (n, ctx) is serveable. Pure: it
        builds no mesh and no jit."""
        if ctx.modulus_bits is not None and not self.uses_modulus_bits:
            raise OpConfigError(
                f"--modulus-bits applies to "
                f"{', '.join(ops_using('modulus_bits'))}; "
                f"op {self.name!r} has no modular route")
        if ctx.model_shards != 1 and not self.uses_model_shards:
            raise OpConfigError(
                f"--model-shards applies to "
                f"{', '.join(ops_using('model_shards'))}; "
                f"op {self.name!r} has no distributed route")
        return self._route(self, n, ctx)

    def validate(self, n: int, ctx: OpContext = OpContext()) -> None:
        """Raise :class:`OpConfigError` unless (n, ctx) is serveable."""
        self.route(n, ctx)

    def narrow(self, ctx: OpContext) -> OpContext:
        """Strip the knobs this op ignores — the mixed engine resolves one
        process-level context against ops with different routes."""
        return OpContext(
            modulus_bits=ctx.modulus_bits if self.uses_modulus_bits else None,
            model_shards=ctx.model_shards if self.uses_model_shards else 1,
            verified=ctx.verified)

    def bind(self, n: int, ctx: OpContext = OpContext(), *,
             strict: bool = True) -> BoundOp:
        """Decide the route and build it.

        ``strict=True`` (the single-op CLI path) rejects knobs this op
        does not consume; ``strict=False`` narrows them away first (the
        mixed engine's per-bucket bind).
        """
        if not strict:
            ctx = self.narrow(ctx)
        plan, route = self.route(n, ctx)
        return self._bind(self, n, ctx, plan, route)


_REGISTRY: dict[str, OpSpec] = {}


def register_op(**kw) -> OpSpec:
    spec = OpSpec(**kw)
    if spec.name in _REGISTRY:
        raise ValueError(f"op {spec.name!r} already registered")
    _REGISTRY[spec.name] = spec
    return spec


def registry() -> tuple[OpSpec, ...]:
    return tuple(_REGISTRY.values())


def op_names() -> tuple[str, ...]:
    return tuple(_REGISTRY)


def get_op(name: str) -> OpSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise OpConfigError(
            f"unknown op {name!r}; registered: {', '.join(_REGISTRY)}"
        ) from None


def ops_using(knob: str) -> tuple[str, ...]:
    flag = {"modulus_bits": "uses_modulus_bits",
            "model_shards": "uses_model_shards"}[knob]
    return tuple(s.name for s in _REGISTRY.values() if getattr(s, flag))


def cli_help() -> str:
    """--op help text, derived from the registry (the argparse surface must
    never drift from the dispatch table)."""
    return "; ".join(f"{s.name}: {s.summary}" for s in _REGISTRY.values())


def cli_knob_help(knob: str, base: str) -> str:
    return f"{base} (applies to: {', '.join(ops_using(knob))})"


# ---------------------------------------------------------------------------
# Shared payload / verification helpers
# ---------------------------------------------------------------------------

def _rel_err(got: np.ndarray, want: np.ndarray) -> float:
    scale = max(1.0, float(np.max(np.abs(want))))
    return float(np.max(np.abs(np.asarray(got) - want))) / scale


def _float_verify(want_of: Callable[[np.ndarray], np.ndarray], tol: float,
                  bound: BoundOp, payload, result: np.ndarray) -> float:
    want = want_of(payload) if bound.spec.arity == 1 else want_of(*payload)
    err = _rel_err(result, want)
    assert err < tol, (f"{bound.spec.name} route {bound.route} diverged "
                      f"from the numpy oracle: rel err {err:.2e} >= {tol}")
    return err


def _zeros(bound: BoundOp, batch: int) -> tuple:
    if bound.payload_dtype is object:
        z = np.zeros((batch, bound.n), object) + 0   # python-int zeros
    else:
        z = np.zeros((batch, bound.n), bound.payload_dtype)
    return bound.stack([z[i] if bound.spec.arity == 1
                        else tuple(z[i] for _ in range(bound.spec.arity))
                        for i in range(batch)])


def _cnormal(rng: np.random.Generator, n: int) -> np.ndarray:
    return (rng.standard_normal(n)
            + 1j * rng.standard_normal(n)).astype(np.complex64)


def _circular_real(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.fft.ifft(np.fft.fft(a) * np.fft.fft(b)).real


def _circular_complex(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.fft.ifft(np.fft.fft(a) * np.fft.fft(b))


def _stack_operands(bound: BoundOp, payloads: Sequence[Any],
                    out: tuple[np.ndarray, ...] | None = None
                    ) -> tuple[np.ndarray, ...]:
    """Host-side numpy stacking, one array per operand: into ``out``
    (``BoundOp.stack``'s slot views) where given, else into fresh arrays
    (the RNS route and the operands of the integrity checks)."""
    arity = bound.spec.arity
    return tuple(
        np.stack([np.asarray(p if arity == 1 else p[i], bound.payload_dtype)
                  for p in payloads], out=None if out is None else out[i])
        for i in range(arity))


class _SlotRing:
    """The host buffers ``BoundOp.stack`` writes batches into: two slots,
    each one C-contiguous ``(rows, n)`` array per operand. A batch of
    ``B`` rows fills a slot's leading ``B`` rows.

    ``jnp.asarray`` returns before its copy to the device ends, and on the
    CPU backend may alias the buffer, so a slot is written again only once
    the result of the batch that last read it is ready. ``take`` gives out
    the slot it gave out last where that result is already ready, so a
    bucket whose previous batch is done keeps writing the same pages,
    still in the caches; else the other slot, once its reader is done. In
    ``ServeEngine.run`` that reader has been fetched by then (the engine
    keeps one batch in flight), so the wait costs nothing; callers that
    dispatch several batches without fetching rely on it.
    """

    def __init__(self, rows: int, n: int, dtype, arity: int):
        self.rows = rows
        # np.full writes every page now, in set-up, not in a served batch
        self._bufs = [tuple(np.full((rows, n), 0, dtype)
                            for _ in range(arity))
                      for _ in range(2)]
        self._readers: list[Any] = [None, None]
        self._last = 0

    def take(self, rows: int) -> tuple[np.ndarray, ...]:
        """A slot's leading ``rows`` rows, once the work that last read
        the slot is done."""
        import jax
        slot = self._last
        if not _is_ready(self._readers[slot]):
            slot = 1 - slot
            jax.block_until_ready(self._readers[slot])
        self._last = slot
        return tuple(buf[:rows] for buf in self._bufs[slot])

    def read_by(self, reader: Any) -> None:
        """Name what reads the slot ``take`` last gave out: the device
        operands, then the result computed from them."""
        self._readers[self._last] = reader


def _is_ready(tree: Any) -> bool:
    """True where every array of ``tree`` is computed; leaves without
    ``is_ready`` (host arrays, None) already are."""
    import jax
    return all(leaf.is_ready() for leaf in jax.tree.leaves(tree)
               if hasattr(leaf, "is_ready"))


def _integrity_fft(bound: BoundOp, payloads, rows):
    from repro.ft import abft
    (x,) = _stack_operands(bound, payloads)
    return abft.check_fft(x, rows)


def _integrity_rfft(bound: BoundOp, payloads, rows):
    from repro.ft import abft
    (x,) = _stack_operands(bound, payloads)
    return abft.check_rfft(x, rows)


def _integrity_polymul(bound: BoundOp, payloads, rows):
    from repro.ft import abft
    a, b = _stack_operands(bound, payloads)
    return abft.check_polymul(a, b, rows)


def _integrity_polymul_real(bound: BoundOp, payloads, rows):
    from repro.ft import abft
    a, b = _stack_operands(bound, payloads)
    return abft.check_polymul_real(a, b, rows)


def _integrity_polymul_mod(bound: BoundOp, payloads, rows):
    from repro.ft import abft
    a, b = _stack_operands(bound, payloads)
    if bound.rns is not None:
        return abft.check_polymul_rns(a, b, rows, bound.rns)
    return abft.check_polymul_mod(a, b, rows, bound.ntt_params)


def _as_words(planes: Callable[..., tuple]) -> dict:
    """``BoundOp`` fields of a route whose results are complex.

    ``planes`` computes the result as float32 planes ``(re, im)``; the
    jitted ``fn`` interleaves them on the device into one float32
    (..., 2m) array, which the host views as complex64 without a copy.
    On a TPU v5e a 64 x 2^14 complex64 result takes ~29 ms to reach the
    host, the same numbers as float32 words ~2.4 ms (PERF.md §6).
    """
    import jax
    import jax.numpy as jnp

    def words(*operands):
        re, im = planes(*operands)
        return jnp.stack([re, im], axis=-1).reshape(
            *re.shape[:-1], 2 * re.shape[-1])
    return {"fn": jax.jit(words), "result_dtype": np.complex64}


def _plan(n: int, ctx: OpContext, **kind):
    """Run the planner for (n, ctx), lifting its ValueError into the
    registry's own error type so callers see one failure surface.
    ``model_shards > 1`` demands the distributed tier (``force_distributed``);
    the ops without a distributed route never get here with it."""
    from repro.core import fft as fft_core
    if ctx.model_shards > 1:
        kind.update(model_shards=ctx.model_shards, force_distributed=True)
    try:
        return fft_core.plan(n, 0, **kind)
    except ValueError as e:
        raise OpConfigError(str(e)) from e


# ---------------------------------------------------------------------------
# fft — complex transform endpoint
# ---------------------------------------------------------------------------

def _route_fft(spec: OpSpec, n: int, ctx: OpContext) -> tuple[Any, str]:
    return _plan(n, ctx), "fft"


def _bind_fft(spec: OpSpec, n: int, ctx: OpContext, plan,
              route: str) -> BoundOp:
    from repro.core import fft as fft_core
    return BoundOp(spec=spec, n=n, ctx=ctx, plan=plan, route=route,
                   payload_dtype=np.complex64,
                   **_as_words(lambda x: fft_core.fft_planes(x)))


register_op(
    name="fft", arity=1,
    summary="batched complex FFT (local Pallas/XLA tier)",
    uses_modulus_bits=False, uses_model_shards=False,
    _route=_route_fft, _bind=_bind_fft,
    warmup_payload=_zeros,
    random_payload=lambda b, rng: _cnormal(rng, b.n),
    verify=functools.partial(_float_verify, np.fft.fft, 1e-3),
    integrity=_integrity_fft,
)


# ---------------------------------------------------------------------------
# rfft — real-Hermitian half-spectrum endpoint (half-spectrum kernel)
# ---------------------------------------------------------------------------

def _route_rfft(spec: OpSpec, n: int, ctx: OpContext) -> tuple[Any, str]:
    plan = _plan(n, ctx, real=True)
    if plan.tier == "hbm":
        raise OpConfigError(
            f"rfft at n={n} exceeds the VMEM-resident ceiling on one "
            f"device; the HBM-resident tier serves polymul-real only")
    return plan, "rfft-real"


def _bind_rfft(spec: OpSpec, n: int, ctx: OpContext, plan,
               route: str) -> BoundOp:
    from repro.core import fft as fft_core
    return BoundOp(spec=spec, n=n, ctx=ctx, plan=plan, route=route,
                   payload_dtype=np.float32,
                   **_as_words(lambda x: fft_core.rfft_planes(x)))


register_op(
    name="rfft", arity=1,
    summary="real-input half-spectrum FFT (Hermitian symmetry)",
    uses_modulus_bits=False, uses_model_shards=False,
    _route=_route_rfft, _bind=_bind_rfft,
    warmup_payload=_zeros,
    random_payload=lambda b, rng: rng.standard_normal(b.n).astype(np.float32),
    verify=functools.partial(_float_verify, np.fft.rfft, 1e-3),
    integrity=_integrity_rfft,
)


# ---------------------------------------------------------------------------
# polymul — complex circular product (three-transform path)
# ---------------------------------------------------------------------------

def _route_polymul(spec: OpSpec, n: int, ctx: OpContext) -> tuple[Any, str]:
    return _plan(n, ctx), "polymul"


def _bind_polymul(spec: OpSpec, n: int, ctx: OpContext, plan,
                  route: str) -> BoundOp:
    import jax.numpy as jnp
    from repro.core import fft as fft_core
    return BoundOp(
        spec=spec, n=n, ctx=ctx, plan=plan, route=route,
        payload_dtype=np.complex64,
        **_as_words(lambda a, b: fft_core.polymul_planes(
            a.astype(jnp.complex64), b.astype(jnp.complex64),
            mode="circular")))


register_op(
    name="polymul", arity=2,
    summary="complex circular polynomial product (convolution theorem)",
    uses_modulus_bits=False, uses_model_shards=False,
    _route=_route_polymul, _bind=_bind_polymul,
    warmup_payload=_zeros,
    random_payload=lambda b, rng: (_cnormal(rng, b.n), _cnormal(rng, b.n)),
    verify=functools.partial(_float_verify, _circular_complex, 1e-3),
    integrity=_integrity_polymul,
)


# ---------------------------------------------------------------------------
# polymul-real — half-spectrum real product; above the VMEM ceiling the
# HBM-resident four-step on one device, the distributed four-step with
# model_shards > 1 (odd batches padded internally, docs/fourier.md)
# ---------------------------------------------------------------------------

_POLYMUL_REAL_ROUTES = {"local": "polymul-real-packed",
                        "hbm": "polymul-real-hbm",
                        "distributed": "polymul-real-distributed"}


def _route_polymul_real(spec: OpSpec, n: int,
                        ctx: OpContext) -> tuple[Any, str]:
    plan = _plan(n, ctx, real=True)
    return plan, _POLYMUL_REAL_ROUTES[plan.tier]


def _hbm_route(plan) -> Callable[..., Any]:
    """The hbm tier's batch fn: ``core.fft.hbm.polymul_real_hbm`` at the
    plan's inner length, counting each batch and its outer HBM passes
    (``hbm.batches``, ``hbm.outer_passes``) into the current recorder."""
    from repro.core.fft import hbm

    def fn(a, b):
        telemetry.count("hbm.batches")
        telemetry.count("hbm.outer_passes", n=hbm.OUTER_PASSES)
        return hbm.polymul_real_hbm(a, b, inner_n=plan.inner_n)
    return fn


def _bind_polymul_real(spec: OpSpec, n: int, ctx: OpContext, plan,
                       route: str) -> BoundOp:
    import jax
    from repro.core import fft as fft_core
    if route == "polymul-real-distributed":
        from repro.core.fft import distributed as dfft
        mesh = jax.make_mesh((ctx.model_shards,), ("model",))
        return BoundOp(
            spec=spec, n=n, ctx=ctx, plan=plan, route=route,
            fn=jax.jit(dfft.make_sharded_polymul_real(mesh, batch_axes=())),
            payload_dtype=np.float32, mesh=mesh)
    if route == "polymul-real-hbm":
        return BoundOp(spec=spec, n=n, ctx=ctx, plan=plan, route=route,
                       fn=_hbm_route(plan), payload_dtype=np.float32)
    return BoundOp(
        spec=spec, n=n, ctx=ctx, plan=plan, route=route,
        fn=jax.jit(lambda a, b: fft_core.polymul_real(a, b,
                                                      mode="circular")),
        payload_dtype=np.float32)


register_op(
    name="polymul-real", arity=2,
    summary="real circular product via the half-spectrum Hermitian fast "
            "path; above the VMEM ceiling the HBM-resident four-step on one "
            "device; --model-shards > 1 runs the distributed four-step tier",
    uses_modulus_bits=False, uses_model_shards=True,
    _route=_route_polymul_real, _bind=_bind_polymul_real,
    warmup_payload=_zeros,
    random_payload=lambda b, rng: (
        rng.standard_normal(b.n).astype(np.float32),
        rng.standard_normal(b.n).astype(np.float32)),
    verify=functools.partial(_float_verify, _circular_real, 1e-3),
    integrity=_integrity_polymul_real,
)


# ---------------------------------------------------------------------------
# polymul-mod — exact negacyclic product mod (x^n + 1, q); parameterized
# routes: single-word fused NTT kernel, multi-limb RNS (> 30-bit Q),
# distributed four-step NTT (model_shards > 1, single-limb only)
# ---------------------------------------------------------------------------

def _route_polymul_mod(spec: OpSpec, n: int,
                       ctx: OpContext) -> tuple[Any, str]:
    bits = ctx.modulus_bits
    rns = bits is not None and bits > 30
    if rns and ctx.model_shards > 1:
        raise OpConfigError(
            "distributed polymul-mod is single-limb: RNS "
            "(modulus_bits > 30) shards limbs, not the sequence — drop "
            "--model-shards or use modulus_bits <= 30")
    plan = _plan(n, ctx, exact=True)
    try:
        if rns:
            from repro.core.ntt import RNSParams
            RNSParams.make(n, modulus_bits=bits)
        else:
            from repro.core.ntt import NTTParams
            NTTParams.make(n, bits=30 if bits is None else bits)
    except ValueError as e:
        raise OpConfigError(
            f"no NTT modulus for n={n}, modulus_bits={bits}: {e}") from e
    if plan.tier == "distributed":
        return plan, "polymul-mod-distributed"
    return plan, "polymul-mod-rns" if rns else "polymul-mod-single"


def _bind_polymul_mod(spec: OpSpec, n: int, ctx: OpContext, plan,
                      route: str) -> BoundOp:
    bits = ctx.modulus_bits
    if route == "polymul-mod-distributed":
        import jax
        from repro.core.ntt import NTTParams
        from repro.core.ntt import distributed as dntt
        params = NTTParams.make(n, bits=30 if bits is None else bits)
        mesh = jax.make_mesh((ctx.model_shards,), ("data",))
        return BoundOp(
            spec=spec, n=n, ctx=ctx, plan=plan, route=route,
            fn=jax.jit(dntt.make_sharded_ntt_polymul(mesh, params)),
            payload_dtype=np.uint32, ntt_params=params, mesh=mesh)
    if route == "polymul-mod-rns":
        from repro.core.ntt import RNSParams, rns_polymul
        rns = RNSParams.make(n, modulus_bits=bits)
        if ctx.verified:
            # A verified bind must be CHECKABLE: the per-factor
            # eval-at-psi check needs Q to factor over the limb primes.
            # Prove it here, not on the first served batch.
            from repro.ft import abft
            try:
                abft.check_limbs_for(rns)
            except abft.ABFTUnsupportedModulus as e:
                raise OpConfigError(
                    f"verified polymul-mod (RNS) bind rejected: {e}"
                ) from e
        return BoundOp(spec=spec, n=n, ctx=ctx, plan=plan, route=route,
                       fn=functools.partial(rns_polymul, rns=rns),
                       payload_dtype=object, rns=rns)
    from repro.core.ntt import NTTParams
    from repro.kernels import ntt as kntt
    params = NTTParams.make(n, bits=30 if bits is None else bits)
    return BoundOp(spec=spec, n=n, ctx=ctx, plan=plan, route=route,
                   fn=functools.partial(kntt.ntt_polymul, params=params),
                   payload_dtype=np.uint32, ntt_params=params)


def _random_mod_payload(bound: BoundOp, rng: np.random.Generator):
    if bound.rns is not None:
        from repro.core.ntt.rns import random_poly
        return (random_poly(rng, bound.n, bound.rns.modulus),
                random_poly(rng, bound.n, bound.rns.modulus))
    q = bound.ntt_params.q
    return (rng.integers(0, q, bound.n).astype(np.uint32),
            rng.integers(0, q, bound.n).astype(np.uint32))


def _verify_mod(bound: BoundOp, payload, result: np.ndarray) -> float:
    a, b = payload
    if bound.rns is not None:
        from repro.core.ntt import rns_polymul_reference
        want = rns_polymul_reference(np.asarray(a, object),
                                     np.asarray(b, object), bound.rns)
    else:
        from repro.core.ntt import negacyclic_polymul
        want = negacyclic_polymul(np.asarray(a), np.asarray(b),
                                  bound.ntt_params)
    assert (np.asarray(result) == want).all(), \
        f"{bound.route} is not bit-exact against the reference NTT"
    return 0.0


register_op(
    name="polymul-mod", arity=2,
    summary="exact negacyclic product mod (x^n+1, q); --modulus-bits > 30 "
            "routes through multi-limb RNS/CRT, --model-shards > 1 the "
            "distributed four-step NTT",
    uses_modulus_bits=True, uses_model_shards=True,
    _route=_route_polymul_mod, _bind=_bind_polymul_mod,
    warmup_payload=_zeros,
    random_payload=_random_mod_payload,
    verify=_verify_mod,
    integrity=_integrity_polymul_mod,
)


# ---------------------------------------------------------------------------
# nas-ft — NAS Parallel Benchmarks FT (NAS-95-020): one 3-D spectral solve
# of the diffusion equation per request, on one device (core.fft.ft3d)
# ---------------------------------------------------------------------------

#: the NPB FT classes this op binds, keyed by n: (class, (NZ, NY, NX) in
#: C order with x contiguous, steps)
NAS_FT_CLASSES = {
    1 << 18: ("S", (64, 64, 64), 6),
    1 << 23: ("A", (128, 256, 256), 6),
    1 << 25: ("B", (256, 256, 512), 20),
    1 << 27: ("C", (512, 512, 512), 20),
}
#: NPB FT's diffusion constant
NAS_FT_ALPHA = 1e-6


def nas_ft_reference(u0: np.ndarray, shape: tuple[int, int, int],
                     niter: int, alpha: float = NAS_FT_ALPHA) -> np.ndarray:
    """NPB FT's checksums of one grid in plain float64 numpy: U1 =
    fftn(u0); for t = 1..niter, W_t = ifftn(U1 * E^t) with E = exp(-4
    alpha pi^2 |k'|^2) (k' the signed frequency of each axis), and chk_t
    = the sum of W_t[5j mod NZ, 3j mod NY, j mod NX], j = 1..1024."""
    u1 = np.fft.fftn(np.asarray(u0, np.complex128).reshape(shape))
    k2 = 0.0
    for axis, m in enumerate(shape):
        kbar = ((np.arange(m) + m // 2) % m - m // 2).astype(np.float64)
        k2 = np.add.outer(k2, kbar ** 2) if axis else kbar ** 2
    j = np.arange(1, 1025)
    pts = (5 * j % shape[0], 3 * j % shape[1], j % shape[2])
    return np.array([
        np.fft.ifftn(u1 * np.exp(-4.0 * alpha * np.pi ** 2 * t * k2))[pts]
        .sum() for t in range(1, niter + 1)])


def _route_nas_ft(spec: OpSpec, n: int, ctx: OpContext) -> tuple[Any, str]:
    if n not in NAS_FT_CLASSES:
        raise OpConfigError(
            f"op 'nas-ft' binds the NPB FT classes by their grid's n: "
            + ", ".join(f"{c} {'x'.join(map(str, s[::-1]))} (n={k})"
                        for k, (c, s, _) in NAS_FT_CLASSES.items())
            + f"; n={n} is none of them")
    if ctx.verified:
        raise OpConfigError(
            "verified bind rejected: op 'nas-ft' has no ABFT integrity "
            "check")
    from repro.core.fft import planner
    try:
        return planner.plan_grid(NAS_FT_CLASSES[n][1]), "nas-ft-grid"
    except ValueError as e:
        raise OpConfigError(str(e)) from e


def _bind_nas_ft(spec: OpSpec, n: int, ctx: OpContext, plan,
                 route: str) -> BoundOp:
    """The grid tier's job fn: ``ft3d.nas_ft`` returning float32 words,
    counting each job, its 3-D transforms and their column passes
    (``ft.jobs``, ``ft.transforms``, ``ft.axis_passes``) into the current
    recorder."""
    from repro.core.fft import ft3d
    _, shape, niter = NAS_FT_CLASSES[n]
    words = _as_words(lambda u0: ft3d.nas_ft(u0, shape=shape, niter=niter,
                                             alpha=NAS_FT_ALPHA))
    solve = words.pop("fn")

    def fn(u0):
        jobs = u0.shape[0]
        telemetry.count("ft.jobs", n=jobs)
        telemetry.count("ft.transforms", n=jobs * (1 + niter))
        telemetry.count("ft.axis_passes",
                        n=jobs * (1 + niter) * ft3d.AXIS_PASSES)
        return solve(u0)
    return BoundOp(spec=spec, n=n, ctx=ctx, plan=plan, route=route, fn=fn,
                   payload_dtype=np.complex64, **words)


def _verify_nas_ft(bound: BoundOp, payload, result: np.ndarray) -> float:
    """The checksums against ``nas_ft_reference``, by the float ops'
    measure: the largest error over the steps relative to the largest
    reference checksum."""
    _, shape, niter = NAS_FT_CLASSES[bound.n]
    return _float_verify(lambda u0: nas_ft_reference(u0, shape, niter),
                         1e-6, bound, payload, result)


def _random_grid(bound: BoundOp, rng: np.random.Generator) -> np.ndarray:
    """NPB FT's initial condition: both planes uniform on (0, 1)."""
    return (rng.random(bound.n, dtype=np.float32)
            + 1j * rng.random(bound.n, dtype=np.float32)).astype(np.complex64)


register_op(
    name="nas-ft", arity=1,
    summary="NAS Parallel Benchmarks FT: one 3-D spectral solve a request "
            "(flat complex64 grid of an NPB class S/A/B/C -> its complex "
            "checksums), x rows VMEM-resident, y/z as column passes",
    uses_modulus_bits=False, uses_model_shards=False,
    _route=_route_nas_ft, _bind=_bind_nas_ft,
    warmup_payload=_zeros,
    random_payload=_random_grid,
    verify=_verify_nas_ft,
)
