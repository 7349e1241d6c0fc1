"""Chaos tier: the verified serve engine under injected faults
(docs/fault_tolerance.md).

The pin this file owns: under seeded stuck-at / bit-flip / dead-array
injection, a mixed-op engine run serves EVERY request with a result that
matches the fault-free oracle — corruption is always detected by the ABFT
gate, recovery is bounded (retry cap, then the circuit breaker unwraps
the bucket's fault injector and quarantines the array), and no
corrupted batch is ever delivered. Plus the robustness satellites:
per-request deadlines, non-finite rejection at submit, request_stop
racing a blocked submit, and checked/atomic checkpoint manifests.
"""
import json
import os
import threading
import time

import numpy as np
import pytest

from repro.core.pim import FaultModel
from repro.ft import checkpoint as ckpt_lib
from repro.launch.engine import EngineStopped, ServeEngine
from repro.launch.ops import OpConfigError

N = 128


def _chaos_model(**kw):
    defaults = dict(seed=1, stuck_per_array=1, n_arrays=4, spares=4)
    defaults.update(kw)
    return FaultModel(**defaults)


def _run_verified(engine, combos, rng, per_bucket=6):
    """Submit per_bucket requests to each (op, n), run, and oracle-verify
    EVERY delivered result (the zero-incorrect-results half of the pin)."""
    kept = {}
    already = engine._served   # run() targets the absolute served count
    for op, n in combos:
        bound = engine.register(op, n)
        for _ in range(per_bucket):
            payload = bound.random_payload(rng)
            kept[engine.submit(op, n, payload)] = (op, n, payload)
    stats = engine.run(already + len(kept))
    for rid, (op, n, payload) in kept.items():
        assert rid in engine.results
        engine.bound(op, n).verify(payload, engine.results[rid])
    return stats


# ---------------------------------------------------------------------------
# The chaos pin: mixed ops, permanent faults, every result correct
# ---------------------------------------------------------------------------

def test_chaos_mixed_ops_all_results_match_oracles(rng):
    """fft + polymul-real + polymul-mod (RNS) under permanent stuck-cell
    faults on every array: detection -> bounded retries -> breaker ->
    clean re-execution; every delivered result matches the registry's
    fault-free numpy oracle bit-for-bit (mod) / within float tol."""
    fm = _chaos_model(bitflip_per_gate=1e-4)
    engine = ServeEngine(max_batch=4, modulus_bits=60,
                         verified=True, fault_model=fm,
                         collect_timeout_s=0.01)
    combos = [("fft", N), ("polymul-real", N), ("polymul-mod", N)]
    assert engine.bound("polymul-mod", N).rns is not None  # RNS route
    stats = _run_verified(engine, combos, rng)
    total = {k: sum(b["integrity"][k] for b in stats["buckets"].values())
             for k in ("checked", "corrupted", "retried", "fell_back")}
    # permanent faults: every bucket detects, exhausts retries, trips
    assert total["corrupted"] >= len(combos)
    assert total["retried"] >= len(combos)
    assert total["fell_back"] == len(combos)
    for key, b in stats["buckets"].items():
        assert b["integrity"]["breaker_open"], key
    assert len(fm.quarantined) == len(combos)


def test_chaos_single_limb_mod(rng):
    fm = _chaos_model()
    engine = ServeEngine(max_batch=4, verified=True,
                         fault_model=fm, collect_timeout_s=0.01)
    assert engine.bound("polymul-mod", N).rns is None   # single-limb route
    stats = _run_verified(engine, [("polymul-mod", N)], rng)
    b = stats["buckets"][f"polymul-mod/n={N}"]["integrity"]
    assert b["corrupted"] >= 1 and b["fell_back"] == 1 and b["breaker_open"]


def test_permanent_fault_breaker_quarantines_and_serves_clean(rng):
    """Forced dead array: the breaker quarantines the array and the
    bucket serves on its unwrapped route, not on the faulty array."""
    fm = FaultModel(seed=5, dead_arrays=(0,), n_arrays=2, spares=1)
    engine = ServeEngine(max_batch=4, verified=True,
                         fault_model=fm, collect_timeout_s=0.01)
    stats = _run_verified(engine, [("fft", N)], rng, per_bucket=4)
    assert fm.is_quarantined(0)
    corrupted = stats["buckets"][f"fft/n={N}"]["integrity"]["corrupted"]
    # breaker is sticky: later batches serve cleanly on the unwrapped op
    stats = _run_verified(engine, [("fft", N)], rng, per_bucket=3)
    b = stats["buckets"][f"fft/n={N}"]["integrity"]
    assert b["breaker_open"] and b["fell_back"] == 1
    assert b["corrupted"] == corrupted


def test_fault_model_requires_verified():
    with pytest.raises(ValueError, match="verified"):
        ServeEngine(fault_model=_chaos_model())


def test_clean_verified_run_counts_checks_only(rng):
    engine = ServeEngine(max_batch=4, verified=True,
                         collect_timeout_s=0.01)
    stats = _run_verified(engine, [("fft", N), ("polymul", N)], rng)
    for b in stats["buckets"].values():
        integ = b["integrity"]
        assert integ["checked"] >= 1
        assert integ["corrupted"] == integ["retried"] == 0
        assert integ["fell_back"] == 0 and not integ["breaker_open"]


def test_unverified_stats_report_zero_integrity(rng):
    engine = ServeEngine(max_batch=4, collect_timeout_s=0.01)
    stats = _run_verified(engine, [("fft", N)], rng, per_bucket=2)
    integ = stats["buckets"][f"fft/n={N}"]["integrity"]
    assert integ == {"checked": 0, "corrupted": 0, "retried": 0,
                     "fell_back": 0, "breaker_open": False}


def test_verified_survives_snapshot_roundtrip(tmp_path, rng):
    d = str(tmp_path / "snap")
    engine = ServeEngine(max_batch=4, verified=True,
                         collect_timeout_s=0.01)
    _run_verified(engine, [("fft", N)], rng, per_bucket=2)
    engine.snapshot(d)
    restored = ServeEngine.from_snapshot(d)
    assert restored.verified and restored.ctx.verified


# ---------------------------------------------------------------------------
# Satellites: deadlines, non-finite rejection, stop race
# ---------------------------------------------------------------------------

def test_deadline_expired_request_gets_structured_error(rng):
    engine = ServeEngine(max_batch=4, collect_timeout_s=0.01)
    bound = engine.register("fft", N)
    rid_ok = engine.submit("fft", N, bound.random_payload(rng))
    rid_exp = engine.submit("fft", N, bound.random_payload(rng),
                            deadline_s=1e-4)
    time.sleep(0.01)    # both expire-eligible before the loop starts
    stats = engine.run(2)
    assert stats["expired"] == 1
    assert stats["buckets"][f"fft/n={N}"]["expired"] == 1
    err = engine.errors[rid_exp]
    assert err["error"] == "deadline_exceeded"
    assert err["op"] == "fft" and err["n"] == N and err["waited_s"] > 0
    assert rid_exp not in engine.results and rid_ok in engine.results
    # expired requests never enter the latency record: p99 describes
    # delivered results only
    assert len(engine._latencies_s) == 1
    with pytest.raises(ValueError):
        engine.submit("fft", N, bound.random_payload(rng), deadline_s=0)


def test_nonfinite_payload_rejected_at_submit(rng):
    engine = ServeEngine(max_batch=4, collect_timeout_s=0.01)
    bad = np.zeros(N, np.complex64)
    bad[3] = np.nan
    with pytest.raises(OpConfigError, match="non-finite"):
        engine.submit("fft", N, bad)
    a = np.zeros(N, np.float32)
    b = np.zeros(N, np.float32)
    b[0] = np.inf
    with pytest.raises(OpConfigError, match="operand 1"):
        engine.submit("polymul-real", N, (a, b))
    # integer/object payloads have no NaN to carry: admitted untouched
    engine.register("polymul-mod", N)
    p = engine.bound("polymul-mod", N).random_payload(rng)
    engine.submit("polymul-mod", N, p)
    engine.run(1)


def test_request_stop_unblocks_waiting_submit(rng):
    """A submit blocked on a FULL queue must raise EngineStopped promptly
    when request_stop lands — not wait out its backpressure timeout."""
    engine = ServeEngine(max_batch=4, max_pending=1,
                         collect_timeout_s=0.01)
    bound = engine.register("fft", N)
    engine.submit("fft", N, bound.random_payload(rng))   # fills the queue
    outcome: list = []

    def blocked_submit():
        try:
            engine.submit("fft", N, bound.random_payload(rng))
            outcome.append("admitted")
        except EngineStopped:
            outcome.append("stopped")

    th = threading.Thread(target=blocked_submit, daemon=True)
    th.start()
    time.sleep(0.15)                 # let it reach the cv.wait loop
    assert th.is_alive()             # genuinely blocked on backpressure
    t0 = time.perf_counter()
    engine.request_stop()
    th.join(timeout=5.0)
    assert not th.is_alive() and outcome == ["stopped"]
    assert time.perf_counter() - t0 < 1.0, "stop must interrupt promptly"
    engine.run(1)                    # drain the admitted request


# ---------------------------------------------------------------------------
# Satellite: checked, durable checkpoint manifests
# ---------------------------------------------------------------------------

def test_checkpoint_refuses_truncated_manifest(tmp_path):
    d = str(tmp_path / "ck")
    ckpt_lib.save(d, 3, {"w": np.arange(4.0)}, extra={"k": 1})
    man = os.path.join(d, "step_3", "manifest.json")
    with open(man, "rb") as f:
        raw = f.read()
    with open(man, "wb") as f:
        f.write(raw[:len(raw) // 2])        # torn write
    with pytest.raises(ckpt_lib.CheckpointCorruptError, match="truncated"):
        ckpt_lib.read_manifest(d, 3)
    with pytest.raises(ckpt_lib.CheckpointCorruptError):
        ckpt_lib.restore(d, 3, {"w": np.zeros(4)})


def test_checkpoint_refuses_partial_manifest(tmp_path):
    d = str(tmp_path / "ck")
    ckpt_lib.save(d, 1, {"w": np.arange(4.0)})
    man = os.path.join(d, "step_1", "manifest.json")
    with open(man, "w") as f:
        json.dump({"extra": {}}, f)         # parses, but missing keys
    with pytest.raises(ckpt_lib.CheckpointCorruptError, match="missing"):
        ckpt_lib.read_manifest(d, 1)


def test_checkpoint_save_publishes_manifest_last_and_clean(tmp_path):
    d = str(tmp_path / "ck")
    path = ckpt_lib.save(d, 2, {"w": np.arange(8.0)}, extra={"s": "x"})
    # no .part residue: every file landed via its atomic rename
    assert not [f for f in os.listdir(path) if f.endswith(".part")]
    assert sorted(os.listdir(path)) == ["arrays.npz", "manifest.json"]
    man = ckpt_lib.read_manifest(d, 2)
    assert man["step"] == 2 and man["extra"] == {"s": "x"}
    _, tree = ckpt_lib.restore_latest(d, {"w": np.zeros(8)})
    np.testing.assert_array_equal(np.asarray(tree["w"]), np.arange(8.0))
