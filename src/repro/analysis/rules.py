"""The rule set: one class per bug this repo actually shipped and fixed.

Every rule here is an executable postmortem.  The coding contracts that
keep the counter-parity / ledger-parity invariants true (numpy-only table
caches, f64 twiddle phases, ledgered collectives, registry-only dispatch,
lock discipline in signal handlers, durable checkpoint writes, planner-only
FFTPlan construction) were each learned from a real regression in PRs 3-9;
until this module existed they lived only in CHANGES.md prose and a string
grep.  Each rule's docstring names the PR and the original bug so a finding
reads as "you are about to reship this", not as style nagging.

Rules are pure: ``check(ctx)`` yields ``(line, col, message)`` tuples from
the stdlib ``ast`` tree in ``ctx.tree`` (no third-party deps, no imports of
the code under analysis).  ``ctx.path`` is the forward-slash-normalized
file path; rules that encode *placement* contracts (the one module allowed
to do X) match on path suffixes.

Suppression: ``# repro: noqa[rule-id]: reason`` on the finding line, or on
a comment-only line directly above it.  The reason is mandatory
(``noqa-reason``) and the suppression must actually hit (``unused-noqa``)
— see ``repro.analysis.engine``.
"""
from __future__ import annotations

import ast
from typing import Iterable, Iterator, Tuple

RawFinding = Tuple[int, int, str]

#: The served op names of the launch/ops.py registry. Kept literal so the
#: analyzer stays importable without jax; tests/test_analysis.py asserts
#: this set == op_registry.op_names() so the two cannot drift.
OP_NAMES = frozenset({"fft", "rfft", "polymul", "polymul-real",
                      "polymul-mod", "nas-ft"})

#: Data-moving jax.lax collectives. axis_index is deliberately absent —
#: it moves no bytes, so calling it raw cannot break ledger parity.
LAX_COLLECTIVES = frozenset({
    "psum", "pmean", "pmax", "pmin", "all_to_all", "all_gather",
    "ppermute", "pshuffle", "psum_scatter", "pbroadcast",
})


def _dotted(node: ast.AST) -> str | None:
    """'jax.lax.psum' for an Attribute/Name chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_jnp_ref(node: ast.AST) -> bool:
    if isinstance(node, ast.Name) and node.id == "jnp":
        return True
    d = _dotted(node)
    return bool(d) and (d == "jax.numpy" or d.startswith("jax.numpy."))


def _mentions_float32(node: ast.AST) -> bool:
    if isinstance(node, ast.Attribute) and node.attr == "float32":
        return True
    if isinstance(node, ast.Name) and node.id == "float32":
        return True
    return isinstance(node, ast.Constant) and node.value == "float32"


def _walk_skip_nested(fn: ast.AST) -> Iterator[ast.AST]:
    """Walk a function body without descending into nested function
    definitions (their bodies run on whatever thread *calls* them, not on
    the enclosing frame)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


class Rule:
    """Base: ``id`` is the noqa key; ``kind`` is 'ast' for tree rules or
    'noqa' for the engine-hosted suppression-hygiene rules."""

    id: str = ""
    kind: str = "ast"

    def check(self, ctx) -> Iterable[RawFinding]:
        return ()

    @property
    def summary(self) -> str:
        return (self.__doc__ or "").strip().splitlines()[0]


class TracerLeakRule(Rule):
    """lru_cache / functools.cache over a function that touches jnp — PR 3:
    the RNS kernel's per-limb constant tables were cached across jit traces
    and leaked tracers; the fix ("tables lru-cached as NUMPY") only holds if
    every cached table builder stays numpy-only."""

    id = "tracer-leak"
    _CACHE_DECOS = frozenset({"functools.lru_cache", "lru_cache",
                              "functools.cache", "cache"})

    def check(self, ctx) -> Iterable[RawFinding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            cached = any(
                _dotted(d.func if isinstance(d, ast.Call) else d)
                in self._CACHE_DECOS
                for d in node.decorator_list)
            if not cached:
                continue
            if any(_is_jnp_ref(sub) for sub in ast.walk(node)):
                yield (node.lineno, node.col_offset,
                       "cached function references jnp: lru-cached values "
                       "must be NUMPY — caching jnp arrays across jit "
                       "traces leaks tracers (PR 3, RNS table cache)")


class Fp32PhaseRule(Rule):
    """Twiddle/root phases built with float32 or traced operands — PR 5:
    the four-step FFT's step-3 twiddles were f32 ``k1*j2`` products with a
    separately-rounded in-graph device phase (~4e-7 error at n=2^20); the
    fix computes exact integer exponents with f64 host trig, rounded ONCE."""

    id = "fp32-phase"
    _TRIG = frozenset({"exp", "cos", "sin"})
    _HOST = frozenset({"np", "numpy", "math"})
    _GRAPH = frozenset({"jnp", "jax.numpy"})

    def check(self, ctx) -> Iterable[RawFinding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func)
            if not d or "." not in d:
                continue
            base, _, fn = d.rpartition(".")
            if fn not in self._TRIG:
                continue
            args = list(node.args) + [kw.value for kw in node.keywords]
            subs = [s for a in args for s in ast.walk(a)]
            has_f32 = any(_mentions_float32(s) for s in subs)
            if base in self._HOST:
                if has_f32:
                    yield (node.lineno, node.col_offset,
                           f"host {d}() over a float32 phase: twiddle/root "
                           "angles must be exact-integer exponents in f64, "
                           "rounded once after the trig (PR 5, fp32 "
                           "four-step twiddle bug)")
                elif any(_is_jnp_ref(s) for s in subs):
                    yield (node.lineno, node.col_offset,
                           f"host {d}() fed a traced (jnp) operand: phase "
                           "tables are built host-side from integer "
                           "exponents, never from in-graph values (PR 5, "
                           "fp32 four-step twiddle bug)")
            elif base in self._GRAPH and has_f32:
                yield (node.lineno, node.col_offset,
                       f"in-graph {d}() over an explicitly float32 phase: "
                       "separately-rounded f32 phases cost ~10x twiddle "
                       "accuracy (PR 5, fp32 four-step twiddle bug)")


class MutableDefaultRule(Rule):
    """Mutable or config-dataclass default arguments — PR 7: a shared
    ``WatchdogConfig()`` default meant every StepWatchdog mutated the same
    config instance; the fix is a None sentinel. Flags mutable literals,
    mutable constructors, and calls to ``*Config`` names in defaults."""

    id = "mutable-default"
    _LITERALS = (ast.List, ast.Dict, ast.Set,
                 ast.ListComp, ast.SetComp, ast.DictComp)
    _CTORS = frozenset({"list", "dict", "set", "bytearray", "deque",
                        "defaultdict", "Counter", "OrderedDict"})

    def check(self, ctx) -> Iterable[RawFinding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda)):
                continue
            defaults = list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None]
            for default in defaults:
                what = None
                if isinstance(default, self._LITERALS):
                    what = "mutable literal"
                elif isinstance(default, ast.Call):
                    last = (_dotted(default.func) or "").rpartition(".")[2]
                    if last in self._CTORS:
                        what = f"mutable {last}() constructor"
                    elif last.endswith("Config"):
                        what = f"config-dataclass instance {last}()"
                if what:
                    yield (default.lineno, default.col_offset,
                           f"{what} as a default argument is shared "
                           "across every call — use a None sentinel "
                           "(PR 7, shared-mutable WatchdogConfig bug)")


class RawCollectiveRule(Rule):
    """jax.lax collectives outside repro/dist/collectives.py — PR 1 built
    the byte-ledger wrappers and PRs 5/8 pinned closed-form byte formulas
    against that ledger; a raw ``jax.lax.psum``/``all_to_all`` call site
    moves bytes the ledger never sees, silently breaking ledger parity."""

    id = "raw-collective"
    _ALLOWED = ("repro/dist/collectives.py",)

    def check(self, ctx) -> Iterable[RawFinding]:
        if ctx.path.endswith(self._ALLOWED):
            return
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.Attribute)
                    and node.attr in LAX_COLLECTIVES
                    and _dotted(node.value) in ("jax.lax", "lax")):
                yield (node.lineno, node.col_offset,
                       f"raw jax.lax.{node.attr}: collectives must go "
                       "through the byte-ledgered wrappers in "
                       "repro.dist.collectives, or their bytes never hit "
                       "the ledger the closed forms are pinned against "
                       "(PR 1 ledger, PR 5/8 parity gates)")


class DispatchLadderRule(Rule):
    """if/elif string ladders over the served op names outside the
    launch/ops.py registry — PR 6 replaced serve.py's per-op ladders with
    the OpSpec registry; a new ladder is a second dispatch surface that
    drifts from registry validation/binding. Promotes the PR 6 string-grep
    test (which a renamed variable could dodge) to an AST rule."""

    id = "dispatch-ladder"
    _ALLOWED = ("repro/launch/ops.py",)

    def check(self, ctx) -> Iterable[RawFinding]:
        if ctx.path.endswith(self._ALLOWED):
            return
        elif_nodes = set()
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.If) and len(node.orelse) == 1
                    and isinstance(node.orelse[0], ast.If)):
                elif_nodes.add(id(node.orelse[0]))
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.If) or id(node) in elif_nodes:
                continue
            subjects: dict[str, set[str]] = {}
            cur = node
            while True:
                m = self._str_eq(cur.test)
                if m:
                    subjects.setdefault(m[0], set()).add(m[1])
                if len(cur.orelse) == 1 and isinstance(cur.orelse[0], ast.If):
                    cur = cur.orelse[0]
                else:
                    break
            for vals in subjects.values():
                hit = sorted(vals & OP_NAMES)
                if len(hit) >= 2:
                    yield (node.lineno, node.col_offset,
                           f"op-name dispatch ladder ({', '.join(hit)}): "
                           "dispatch belongs in the launch/ops.py OpSpec "
                           "registry, not string switches (PR 6, serve "
                           "ladder removal)")
                    break

    @staticmethod
    def _str_eq(test: ast.AST) -> tuple[str, str] | None:
        if (isinstance(test, ast.Compare) and len(test.ops) == 1
                and isinstance(test.ops[0], ast.Eq)):
            left, right = test.left, test.comparators[0]
            if isinstance(right, ast.Constant) and isinstance(right.value,
                                                             str):
                return ast.dump(left), right.value
            if isinstance(left, ast.Constant) and isinstance(left.value,
                                                             str):
                return ast.dump(right), left.value
        return None


class SignalLockRule(Rule):
    """Taking a non-reentrant lock inside a signal handler body — PR 7:
    the serve SIGTERM handler called ``engine.request_stop()`` on the
    interrupted main thread, whose frame may already hold the engine's
    Condition — a self-deadlock. The fix hands the call to a separate
    thread; nested defs are exempt (they run on whichever thread calls
    them, which is the hand-off pattern)."""

    id = "signal-lock"
    _LOCKY_CALLS = frozenset({"acquire", "wait", "notify", "notify_all",
                              "request_stop", "submit", "snapshot"})
    _LOCKY_NAMES = ("lock", "cv", "cond", "mutex")

    def check(self, ctx) -> Iterable[RawFinding]:
        handler_names: set[str] = set()
        handler_lambdas: list[ast.Lambda] = []
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.Call)
                    and _dotted(node.func) == "signal.signal"
                    and len(node.args) >= 2):
                h = node.args[1]
                if isinstance(h, ast.Name):
                    handler_names.add(h.id)
                elif isinstance(h, ast.Lambda):
                    handler_lambdas.append(h)
        bodies: list[ast.AST] = list(handler_lambdas)
        bodies += [n for n in ast.walk(ctx.tree)
                   if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                   and n.name in handler_names]
        for fn in bodies:
            yield from self._scan(fn)

    def _scan(self, fn: ast.AST) -> Iterator[RawFinding]:
        for sub in _walk_skip_nested(fn):
            if isinstance(sub, ast.With):
                for item in sub.items:
                    name = ((_dotted(item.context_expr) or "")
                            .rpartition(".")[2].lower())
                    if any(k in name for k in self._LOCKY_NAMES):
                        yield (sub.lineno, sub.col_offset,
                               "signal handler enters a lock: the handler "
                               "runs on the interrupted main thread, which "
                               "may already hold it — self-deadlock (PR 7, "
                               "SIGTERM drain bug); hand off to a thread")
            elif (isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                    and sub.func.attr in self._LOCKY_CALLS):
                yield (sub.lineno, sub.col_offset,
                       f"signal handler calls .{sub.func.attr}() directly: "
                       "engine methods take the non-reentrant Condition — "
                       "on the interrupted main thread that self-deadlocks "
                       "(PR 7, SIGTERM drain bug); spawn a thread for it")


class DurableWriteRule(Rule):
    """Raw writes inside repro/ft/ — PR 9: a crash between payload and
    manifest writes left torn checkpoints that restore half-read; every
    durable file must go through the fsync+rename, manifest-LAST helper in
    ft/checkpoint.py. Flags open(..., 'w'/'a'/'x'/'+'), json.dump and
    np.save* in ft modules; the helper's own internals carry noqa reasons."""

    id = "durable-write"
    _SCOPE = "repro/ft/"
    _WRITE_FNS = frozenset({"json.dump", "np.save", "np.savez",
                            "np.savez_compressed", "numpy.save",
                            "numpy.savez", "numpy.savez_compressed"})

    def check(self, ctx) -> Iterable[RawFinding]:
        if self._SCOPE not in ctx.path:
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func)
            if d == "open":
                mode = None
                if len(node.args) >= 2 and isinstance(node.args[1],
                                                      ast.Constant):
                    mode = node.args[1].value
                for kw in node.keywords:
                    if kw.arg == "mode" and isinstance(kw.value,
                                                       ast.Constant):
                        mode = kw.value.value
                if isinstance(mode, str) and any(c in mode for c in "wax+"):
                    yield (node.lineno, node.col_offset,
                           f"raw open(..., {mode!r}) under ft/: durable "
                           "state must go through the fsync+rename "
                           "manifest-last helper, or a crash tears the "
                           "checkpoint (PR 9, torn-manifest bug)")
            elif d in self._WRITE_FNS:
                yield (node.lineno, node.col_offset,
                       f"raw {d}() under ft/: durable state must go "
                       "through the fsync+rename manifest-last helper "
                       "(PR 9, torn-manifest bug)")


class BarePlanLiteralRule(Rule):
    """Hand-built FFTPlan(...) literals outside planner.py — PR 5:
    serve carried literal plans that silently skipped planner validation
    (the exact tier's shard checks among them); the fix routes every forced
    plan through ``plan(..., force_distributed=True)`` so the constraints
    fire. Only the planner may construct FFTPlan."""

    id = "bare-plan-literal"
    _ALLOWED = ("repro/core/fft/planner.py",)

    def check(self, ctx) -> Iterable[RawFinding]:
        if ctx.path.endswith(self._ALLOWED):
            return
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.Call)
                    and (_dotted(node.func) or "").rpartition(".")[2]
                    == "FFTPlan"):
                yield (node.lineno, node.col_offset,
                       "hand-built FFTPlan literal: construct plans via "
                       "plan(n, batch, ...) so planner validation "
                       "(shard divisibility, VMEM ceilings) runs (PR 5, "
                       "serve literal-plan bug)")


class NoqaReasonRule(Rule):
    """Suppression without a reason (or naming an unknown/meta rule) —
    PR 10's own contract: every ``# repro: noqa[rule]`` must carry
    ``: reason`` explaining why the historical bug does not apply here;
    a bare suppression is how contracts silently rot. Engine-hosted: the
    malformed suppression is reported and does NOT suppress."""

    id = "noqa-reason"
    kind = "noqa"


class UnusedNoqaRule(Rule):
    """Suppression that suppresses nothing — PR 10's own contract: a noqa
    left behind after the code it excused was fixed (or that never matched)
    is a latent hole; the engine reports it so suppressions track the code.
    Engine-hosted post-pass over the suppression table."""

    id = "unused-noqa"
    kind = "noqa"


RULES: tuple[Rule, ...] = (
    TracerLeakRule(),
    Fp32PhaseRule(),
    MutableDefaultRule(),
    RawCollectiveRule(),
    DispatchLadderRule(),
    SignalLockRule(),
    DurableWriteRule(),
    BarePlanLiteralRule(),
    NoqaReasonRule(),
    UnusedNoqaRule(),
)

RULE_IDS = tuple(r.id for r in RULES)
