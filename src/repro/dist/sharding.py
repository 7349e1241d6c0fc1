"""Logical-axis sharding: ``constrain`` + the logical->mesh rules table.

Model and kernel code annotates activations with *logical* axis names
("batch", "model", "sp", ...); a registerable rules table (MaxText-style)
maps each logical name to one or more *mesh* axes, and ``constrain`` turns
the result into ``with_sharding_constraint`` against the ambient mesh.

Outside any mesh context — single-device tests, CPU smoke training,
``launch/dryrun.py`` helpers before the mesh is entered — ``constrain``
validates its arguments and returns the array unchanged, so annotated code
runs everywhere.

Mesh axes named by a rule that are absent from the ambient mesh, or that do
not divide the corresponding dimension, are dropped (same sanitization
contract as ``launch.specs``): a rule like ``batch -> (pod, data)`` works
on single-pod and multi-pod meshes alike.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: Default logical->mesh rules. ``batch`` spans the pure-data axes (FSDP and
#: the paper-§6 batch-over-arrays dimension both extend over (pod, data));
#: ``sp`` (sequence parallel) reuses the tensor axis, as do experts/vocab.
_DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "data": ("data",),
    "pod": ("pod",),
    "model": ("model",),
    "sp": ("model",),
    "expert": ("model",),
    "vocab": ("model",),
}


class _State(threading.local):
    def __init__(self):
        self.mesh_stack: list[Mesh] = []
        self.rules: dict[str, tuple[str, ...]] = dict(_DEFAULT_RULES)


_STATE = _State()


# ---------------------------------------------------------------------------
# Rules table
# ---------------------------------------------------------------------------

def register_rule(logical: str, *mesh_axes: str) -> None:
    """Add or override one logical->mesh rule (process-wide for this thread).

    ``register_rule("expert", "data", "model")`` shards the expert dimension
    over both axes; ``register_rule("sp")`` makes "sp" a no-op.
    """
    if not isinstance(logical, str) or not logical:
        raise ValueError(f"logical axis must be a non-empty str: {logical!r}")
    for a in mesh_axes:
        if not isinstance(a, str):
            raise ValueError(f"mesh axes must be strs: {mesh_axes!r}")
    _STATE.rules[logical] = tuple(mesh_axes)


def current_rules() -> dict[str, tuple[str, ...]]:
    """Snapshot of the active logical->mesh rules table."""
    return dict(_STATE.rules)


@contextlib.contextmanager
def axis_rules(rules: dict[str, tuple[str, ...]], *, extend: bool = True):
    """Temporarily override the rules table (extend=False replaces it)."""
    saved = _STATE.rules
    merged = {**saved, **rules} if extend else dict(rules)
    _STATE.rules = {k: tuple(v) for k, v in merged.items()}
    try:
        yield current_rules()
    finally:
        _STATE.rules = saved


def reset_rules() -> None:
    """Restore the built-in default rules table."""
    _STATE.rules = dict(_DEFAULT_RULES)


# ---------------------------------------------------------------------------
# Ambient mesh
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Make ``mesh`` the ambient mesh ``constrain`` resolves against."""
    _STATE.mesh_stack.append(mesh)
    try:
        yield mesh
    finally:
        _STATE.mesh_stack.pop()


def current_mesh() -> Mesh | None:
    """The ambient mesh: ``use_mesh`` stack, else jax's own mesh context."""
    if _STATE.mesh_stack:
        return _STATE.mesh_stack[-1]
    # A concrete mesh activated by jax.set_mesh (get_mesh raises under jit).
    try:
        m = jax.sharding.get_mesh()
        if isinstance(m, Mesh) and not m.empty:
            return m
    except ValueError:
        pass
    # The resource env filled by ``with mesh:``.
    try:
        from jax._src import mesh as _mesh_lib
        m = _mesh_lib.thread_resources.env.physical_mesh
        if m is not None and not m.empty:
            return m
    except Exception:
        pass
    return None


# ---------------------------------------------------------------------------
# Spec construction
# ---------------------------------------------------------------------------

def sanitize_spec(spec: P, shape: tuple, mesh: Mesh) -> P:
    """Drop mesh axes that don't exist in ``mesh`` or don't divide the dim.

    For tuple entries the longest dividing prefix of present axes is kept,
    so ``("pod", "data")`` degrades gracefully on a single-pod mesh.
    """
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept: list[str] = []
        prod = 1
        for a in axes:
            if a not in mesh.shape:
                continue
            if dim % (prod * mesh.shape[a]) == 0:
                kept.append(a)
                prod *= mesh.shape[a]
            else:
                break
        if not kept:
            out.append(None)
        elif len(kept) == 1:
            out.append(kept[0])
        else:
            out.append(tuple(kept))
    return P(*out)


def _validate(logical_axes: tuple, rules: dict) -> None:
    for a in logical_axes:
        if a is None:
            continue
        if not isinstance(a, str):
            raise ValueError(
                f"logical axis must be a str or None, got {a!r}")
        if a not in rules:
            raise ValueError(
                f"unknown logical axis {a!r}; known: {sorted(rules)} "
                f"(register_rule() to add)")


def logical_to_spec(logical_axes: tuple, shape: tuple,
                    mesh: Mesh) -> P:
    """Resolve logical names through the rules table into a sanitized
    ``PartitionSpec`` for an array of ``shape`` on ``mesh``."""
    rules = _STATE.rules
    _validate(tuple(logical_axes), rules)
    if len(logical_axes) != len(shape):
        raise ValueError(
            f"{len(logical_axes)} logical axes for rank-{len(shape)} array")
    raw = []
    for a in logical_axes:
        if a is None:
            raw.append(None)
            continue
        mesh_axes = rules[a]
        if len(mesh_axes) == 0:
            raw.append(None)
        elif len(mesh_axes) == 1:
            raw.append(mesh_axes[0])
        else:
            raw.append(mesh_axes)
    return sanitize_spec(P(*raw), tuple(shape), mesh)


def constrain(x: jax.Array, *logical_axes) -> jax.Array:
    """Constrain ``x``'s sharding by logical axis names, one per dimension.

    ``constrain(x, "batch", None, "model")`` shards dim 0 over the mesh axes
    the "batch" rule names and dim 2 over the "model" rule's axes. A no-op
    (after validation) outside any mesh context or on a 1-device mesh.
    """
    _validate(logical_axes, _STATE.rules)
    if len(logical_axes) != x.ndim:
        raise ValueError(
            f"constrain got {len(logical_axes)} logical axes for a rank-"
            f"{x.ndim} array (shape {x.shape})")
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return x
    spec = logical_to_spec(logical_axes, x.shape, mesh)
    if all(e is None for e in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
