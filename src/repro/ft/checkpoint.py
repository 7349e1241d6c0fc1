"""Fault-tolerant checkpointing: atomic, sharded, elastic.

Design (1000+ node posture, DESIGN.md §6):
  * atomic step directories: write to ``step_N.tmp`` then rename — a crash
    mid-write never corrupts the latest checkpoint;
  * durable, ordered writes: every file lands via temp-file + flush +
    ``os.fsync`` + ``os.replace`` and the manifest is written LAST, so a
    manifest that exists implies its arrays are already durable; the
    containing directory is fsynced after each rename so the entries
    themselves survive power loss;
  * checked reads: a truncated or partial manifest (torn write, disk
    full) raises :class:`CheckpointCorruptError` by name instead of a
    bare ``JSONDecodeError`` deep in restore;
  * every array is saved with a manifest (tree paths, shapes, dtypes) and
    the data as host-local .npz shards; restore re-shards onto WHATEVER mesh
    is bound at restore time (elastic re-scaling: checkpoints taken on N
    devices restore onto M);
  * retention: keep the last K steps; auto-resume picks the newest complete
    step; partial (crashed) writes are garbage-collected on startup.

No orbax dependency — msgpack-free, npz + json only.
"""
from __future__ import annotations

import json
import os
import re
import shutil
from typing import Any, Optional

import jax
import numpy as np

_STEP_RE = re.compile(r"^step_(\d+)$")


class CheckpointCorruptError(RuntimeError):
    """A checkpoint file exists but cannot be trusted: truncated or
    malformed manifest, or a manifest missing its required keys. Raised
    by name so callers can distinguish "this snapshot is damaged" (fall
    back to an older step, or refuse to resume) from "no snapshot"
    (FileNotFoundError)."""


def _fsync_dir(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _durable_replace(part: str, dest: str) -> None:
    """Atomically publish ``part`` as ``dest`` and make both the data and
    the directory entry durable (fsync file, rename, fsync dir)."""
    os.replace(part, dest)
    _fsync_dir(os.path.dirname(dest))


def _load_manifest(final: str) -> dict:
    """Read + validate one step's manifest; truncated/partial manifests
    (torn write mid-crash) surface as :class:`CheckpointCorruptError`."""
    path = os.path.join(final, "manifest.json")
    try:
        with open(path, "rb") as f:
            raw = f.read()
        manifest = json.loads(raw.decode("utf-8"))
    except (json.JSONDecodeError, UnicodeDecodeError) as e:
        raise CheckpointCorruptError(
            f"{path} is truncated or malformed ({e}); the step cannot be "
            f"trusted — fall back to an older step or delete it") from e
    if not isinstance(manifest, dict) or "arrays" not in manifest \
            or "step" not in manifest:
        raise CheckpointCorruptError(
            f"{path} parsed but is missing required keys "
            f"('step', 'arrays'): partial manifest from an interrupted "
            f"save — fall back to an older step or delete it")
    return manifest


def _flatten_with_paths(tree: Any):
    flat, treedef = jax.tree.flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out.append((key, leaf))
    return out, treedef


def save(ckpt_dir: str, step: int, tree: Any, *, keep: int = 3,
         extra: Optional[dict] = None) -> str:
    """Atomically save a pytree of (possibly sharded) jax arrays."""
    os.makedirs(ckpt_dir, exist_ok=True)
    final = os.path.join(ckpt_dir, f"step_{step}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    leaves, _ = _flatten_with_paths(tree)
    manifest = {"step": step, "arrays": [], "extra": extra or {}}
    arrays = {}
    for i, (key, leaf) in enumerate(leaves):
        arr = np.asarray(jax.device_get(leaf))
        name = f"a{i}"
        manifest["arrays"].append({"key": key, "name": name,
                                   "shape": list(arr.shape),
                                   "dtype": str(arr.dtype)})
        arrays[name] = arr
    # Arrays first, manifest LAST, every file fsynced before its rename:
    # a manifest that exists implies its arrays are already durable, so
    # readers never see a step whose data lags its metadata.
    npz_part = os.path.join(tmp, "arrays.npz.part")
    # repro: noqa[durable-write]: this IS the durable helper — .part file, fsync below, rename after
    with open(npz_part, "wb") as f:
        np.savez(f, **arrays)  # repro: noqa[durable-write]: into the fsynced .part file opened above
        f.flush()
        os.fsync(f.fileno())
    _durable_replace(npz_part, os.path.join(tmp, "arrays.npz"))
    man_part = os.path.join(tmp, "manifest.json.part")
    # repro: noqa[durable-write]: this IS the durable helper — manifest lands LAST via _durable_replace
    with open(man_part, "w") as f:
        json.dump(manifest, f)  # repro: noqa[durable-write]: into the fsynced .part file opened above
        f.flush()
        os.fsync(f.fileno())
    _durable_replace(man_part, os.path.join(tmp, "manifest.json"))
    if os.path.exists(final):
        # Re-saving an existing step must land the FRESH arrays. os.replace
        # cannot atomically replace a non-empty directory, so the old step
        # is first moved aside under a .tmp suffix (which _retain GCs like
        # any crashed partial write) and removed only after the rename. A
        # crash between the two renames leaves no step_N listed — never a
        # stale one masquerading as the new save.
        old = final + ".old.tmp"
        if os.path.exists(old):
            shutil.rmtree(old)
        os.replace(final, old)
        os.replace(tmp, final)
        shutil.rmtree(old, ignore_errors=True)
    else:
        os.replace(tmp, final)
    _fsync_dir(ckpt_dir)    # the step_N entry itself survives power loss
    _retain(ckpt_dir, keep)
    return final


def _retain(ckpt_dir: str, keep: int):
    steps = all_steps(ckpt_dir)
    for s in steps[:-keep]:
        shutil.rmtree(os.path.join(ckpt_dir, f"step_{s}"),
                      ignore_errors=True)
    # GC half-written tmp dirs
    for d in os.listdir(ckpt_dir):
        if d.endswith(".tmp"):
            shutil.rmtree(os.path.join(ckpt_dir, d), ignore_errors=True)


def all_steps(ckpt_dir: str) -> list[int]:
    if not os.path.isdir(ckpt_dir):
        return []
    steps = []
    for d in os.listdir(ckpt_dir):
        m = _STEP_RE.match(d)
        if m and os.path.exists(os.path.join(ckpt_dir, d, "manifest.json")):
            steps.append(int(m.group(1)))
    return sorted(steps)


def latest_step(ckpt_dir: str) -> Optional[int]:
    steps = all_steps(ckpt_dir)
    return steps[-1] if steps else None


def read_manifest(ckpt_dir: str, step: int) -> dict:
    """The saved manifest (tree paths/shapes/dtypes + the ``extra`` payload
    callers stash host-side state in: watchdog EWMA/events, data-pipeline
    step cursor, engine bucket config — docs/fault_tolerance.md).

    Raises :class:`CheckpointCorruptError` for a truncated or partial
    manifest rather than handing the caller half a JSON document."""
    final = os.path.join(ckpt_dir, f"step_{step}")
    return _load_manifest(final)


def read_extra(ckpt_dir: str, step: int) -> dict:
    return read_manifest(ckpt_dir, step).get("extra", {})


def restore(ckpt_dir: str, step: int, like: Any,
            shardings: Any = None) -> Any:
    """Restore into the structure of ``like``; if ``shardings`` is given the
    arrays are device_put with those shardings (elastic re-shard: the saved
    mesh size is irrelevant — data is stored unsharded per tree leaf)."""
    final = os.path.join(ckpt_dir, f"step_{step}")
    manifest = _load_manifest(final)
    data = np.load(os.path.join(final, "arrays.npz"))
    by_key = {e["key"]: data[e["name"]] for e in manifest["arrays"]}
    leaves, treedef = _flatten_with_paths(like)
    sh_leaves = None
    if shardings is not None:
        sh_flat, _ = _flatten_with_paths(shardings)
        sh_leaves = dict(sh_flat)
    out = []
    for key, leaf in leaves:
        if key not in by_key:
            raise KeyError(
                f"checkpoint {final} has no array for {key!r} — the saved "
                f"payload does not match the restore tree (e.g. resuming "
                f"--compress-grads from a checkpoint saved without the "
                f"grad_err residual); saved keys: "
                f"{sorted(by_key)[:8]}...")
        arr = by_key[key]
        want_dtype = leaf.dtype if hasattr(leaf, "dtype") else arr.dtype
        arr = arr.astype(want_dtype)
        if sh_leaves is not None and key in sh_leaves:
            out.append(jax.device_put(arr, sh_leaves[key]))
        else:
            out.append(jax.numpy.asarray(arr))
    return jax.tree.unflatten(treedef, out)


def restore_latest(ckpt_dir: str, like: Any, shardings: Any = None
                   ) -> tuple[Optional[int], Any]:
    step = latest_step(ckpt_dir)
    if step is None:
        return None, like
    return step, restore(ckpt_dir, step, like, shardings)
