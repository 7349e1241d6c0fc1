"""Continuous-batching serve engine over the op-dispatch registry.

One engine process multiplexes a mixed request stream: every request
carries its own ``(op, n)`` (and payload dtype via the registry), is
admitted against a bounded queue, and lands in a per-``(op, n)`` shape
bucket. A single scheduler loop drains buckets with continuous batching:

  * **shape bucketing** — requests only ever batch with shape-compatible
    peers; each bucket dispatches through its :class:`~repro.launch.ops.
    BoundOp` (plan, route, jitted fn) resolved once from the registry;
  * **tail batches at actual size** — a bucket holding 3 requests
    dispatches 3 rows; nothing is padded to the block cap (the kernels'
    ``_fit_block`` clamps the VMEM block to the real batch instead);
  * **async dispatch, deferred sync** — ``jax.block_until_ready`` for
    batch k is deferred until AFTER batch k+1 has been staged and
    dispatched, so host-side stacking/transfer of the next batch overlaps
    the current batch's compute (one batch in flight, the maxtext
    decode-microbenchmark warmup/steady-state split);
  * **oldest-head scheduling** — among non-empty buckets the one whose
    head request has waited longest dispatches next, so a hot bucket
    cannot starve a cold one;
  * **backpressure** — ``submit`` blocks (or raises :class:`Backpressure`
    with ``block=False``) while ``max_pending`` requests are queued: the
    admission policy is a bounded queue, pushing the wait back into
    producers instead of growing host memory without bound.

Metrics (docs/serving.md has the glossary): per-request latency
(offer to ``submit`` -> result materialized) percentiles p50/p90/p99,
end-to-end and busy-only throughput, and per-bucket batch-size traces with
utilization (mean dispatched batch / block cap) — the number that says
whether traffic actually fills the arrays the paper's throughput claims
assume. Each engine owns a :class:`~repro.telemetry.Recorder`
(``engine.telemetry``): spans at every layer boundary it crosses
(``serve.admit`` ... ``serve.deliver``, and the ``BoundOp`` and RNS spans
beneath, recorded while ``run`` makes it current on its thread) and the
queue-wait counter; ``stats()`` reports them.

Fault tolerance (docs/fault_tolerance.md): the engine is preemption-safe.
``request_stop`` (the SIGTERM path in ``launch/serve.py``) stops admission
and lets ``run`` DRAIN — every already-admitted request is dispatched,
resolved and delivered before ``run`` returns; ``snapshot`` then persists
the lifetime stats, bucket config and watchdog state through
``ft.checkpoint`` (atomic step dirs), and ``ServeEngine.from_snapshot``
warm-restarts: buckets re-register and re-bind on the CURRENT context —
which may have a different ``model_shards`` — while served counters, the
latency record and the watchdog's timing baseline carry over. A
:class:`~repro.ft.watchdog.StepWatchdog` observes per-batch service times;
its ``on_evict`` hook is the elastic trigger the watchdog module
documents (checkpoint -> resize -> restore).

Integrity (``verified=True``): every deliverable batch passes its op's
ABFT check (``ft/abft.py``) before any client sees a result. A failed
check triggers bounded re-execution with exponential backoff; when the
retry budget is exhausted the bucket's circuit breaker trips — the
bucket's fault injector is unwrapped, the simulated crossbar array behind
it is quarantined to a spare, and the batch re-runs on the clean route.
``--inject-faults`` chaos testing drives this path deterministically via
a seeded :class:`~repro.core.pim.FaultModel` whose per-bucket injectors
corrupt delivered rows. Per-request deadlines (``submit(..., deadline_s=...)``)
complete expired requests with a structured timeout error instead of a
result; expired requests never enter the latency record.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Any, Callable, Optional

import numpy as np

from repro import telemetry
from repro.ft import checkpoint as ckpt_lib
from repro.ft.watchdog import StepWatchdog, WatchdogConfig
from repro.launch import ops as op_registry

SNAPSHOT_SCHEMA = "serve_engine_snapshot/v1"

#: Bounded window for the per-request latency record. Lifetime percentiles
#: are computed over (at most) the most recent window, and — the actual
#: bug this bounds — ``snapshot`` persists at most this many samples, so a
#: restart loop (snapshot -> from_snapshot -> snapshot ...) plateaus
#: instead of growing the payload by one generation's traffic each cycle.
#: Older samples DECAY out of the percentile inputs by design: a
#: deployment's p99 should describe recent service, not the union of every
#: generation since the first boot.
LATENCY_WINDOW = 4096


class Backpressure(RuntimeError):
    """Admission rejected: the bounded request queue is full."""


class EngineStopped(RuntimeError):
    """Admission rejected: the engine is draining toward a stop/snapshot."""


@dataclasses.dataclass
class _Request:
    rid: int
    key: tuple[str, int]
    payload: Any
    # admission, after any backpressure wait: scheduling, deadlines and
    # queue wait run from here
    t_submit: float
    # entry to ``submit``: the latency record runs from here
    t_offer: float
    # absolute perf_counter() deadline; expired requests complete with a
    # structured error instead of a result (and never batch)
    deadline: float | None = None


@dataclasses.dataclass
class _BucketStats:
    served: int = 0
    batches: int = 0
    batch_sizes: list = dataclasses.field(default_factory=list)
    # accumulated dispatch -> materialized seconds of this bucket's
    # batches
    service_s: float = 0.0
    # deadline-expired requests swept from this bucket's queue
    expired: int = 0
    # ABFT ledger (verified=True): checks run / failures detected /
    # re-executions / breaker trips (detected -> retried -> fell_back is
    # the recovery state machine in docs/fault_tolerance.md)
    checked: int = 0
    corrupted: int = 0
    retried: int = 0
    fell_back: int = 0


class _FaultInjector:
    """Chaos hook for one serve bucket: wraps the bucket's bound ``fn``,
    runs the real kernel, then — driven by the engine's seeded
    :class:`~repro.core.pim.FaultModel` for this bucket's virtual array —
    corrupts delivered rows. Deterministic per (model seed, array id,
    dispatch index), so a chaos run replays exactly.

    Corruption mirrors the sim-level fault modes at the result surface:
    permanent faults (dead / stuck cells) corrupt EVERY dispatch, transient
    bit-flips fire with probability ``1 - (1 - rate)^gates`` where gates
    scales with the batch's work (rows * n * log2 n). Injected damage is a
    magnitude change (float/complex) or a low-bit flip (modular) on one
    element — exactly the class of error the ABFT checks are sound
    against."""

    def __init__(self, model, array_id: int, bound):
        self.model = model
        self.array_id = array_id
        self.bound = bound
        self.inner = bound.fn
        self.dispatches = 0

    def __call__(self, *operands):
        out = self.inner(*operands)
        idx = self.dispatches
        self.dispatches += 1
        faults = self.model.for_array(self.array_id)
        if faults is None:
            return out      # quarantined-to-spare or clean array
        arr = np.array(self.bound.to_numpy(out), copy=True)
        rows = arr if arr.ndim > 1 else arr.reshape(1, -1)
        rng = self.model.rng_for(self.array_id, salt=1000 + idx)
        if faults.permanent:
            corrupt = True
        else:
            n = rows.shape[1]
            gates = rows.shape[0] * n * max(1, n.bit_length() - 1)
            p = 1.0 - (1.0 - faults.bitflip_per_gate) ** gates
            corrupt = bool(rng.random() < p)
        if corrupt:
            r = int(rng.integers(rows.shape[0]))
            j = int(rng.integers(rows.shape[1]))
            row = rows[r]
            if row.dtype == object:
                row[j] = row[j] + 1
            elif np.issubdtype(row.dtype, np.complexfloating):
                row[j] += (1.0 + float(np.abs(row).max())) * (3.0 + 3.0j)
            elif np.issubdtype(row.dtype, np.floating):
                row[j] += (1.0 + float(np.abs(row).max())) * 3.0
            else:
                row[j] = row.dtype.type(int(row[j]) ^ 1)
        # hand back what the route returns: its float32 words, if it has them
        return arr if self.bound.result_dtype is None else arr.view(np.float32)


class ServeEngine:
    """Multiplexing continuous-batching executor for registry ops.

    ``max_batch`` caps one dispatch (the continuous-batching block);
    ``max_pending`` bounds the admission queue across all buckets. The
    process-level ``modulus_bits`` / ``model_shards`` context feeds each
    op through ``OpSpec.narrow`` unless a bucket is registered with
    ``strict=True`` (the single-op CLI path, which rejects knobs the op
    does not consume).
    """

    def __init__(self, *, max_batch: int = 64, max_pending: int = 1024,
                 modulus_bits: int | None = None, model_shards: int = 1,
                 collect_timeout_s: float = 0.05,
                 watchdog_cfg: Optional[WatchdogConfig] = None,
                 on_evict: Optional[Callable[["ServeEngine", int], None]]
                 = None,
                 verified: bool = False,
                 fault_model=None,
                 retry_cap: int = 2,
                 retry_backoff_s: float = 0.001):
        if max_batch < 1:
            raise ValueError(f"max_batch={max_batch} must be >= 1")
        if max_pending < 1:
            raise ValueError(f"max_pending={max_pending} must be >= 1")
        if retry_cap < 0:
            raise ValueError(f"retry_cap={retry_cap} must be >= 0")
        if fault_model is not None and not verified:
            raise ValueError(
                "fault_model without verified=True would deliver corrupted "
                "results: chaos injection requires the ABFT gate")
        self.max_batch = max_batch
        self.max_pending = max_pending
        self.collect_timeout_s = collect_timeout_s
        self.ctx = op_registry.OpContext(modulus_bits=modulus_bits,
                                         model_shards=model_shards,
                                         verified=verified)
        # ABFT recovery knobs (docs/fault_tolerance.md): detected
        # corruption -> up to retry_cap re-executions with exponential
        # backoff -> circuit breaker (injector unwrapped + array
        # quarantine).
        self.verified = verified
        self.fault_model = fault_model
        self.retry_cap = retry_cap
        self.retry_backoff_s = retry_backoff_s
        self._injectors: dict[tuple[str, int], _FaultInjector] = {}
        self._breaker_open: set[tuple[str, int]] = set()
        self._next_array_id = 0
        # rid -> structured error for requests completed WITHOUT a result
        # (deadline_exceeded today); disjoint from ``results``.
        self.errors: dict[int, dict] = {}
        self._bound: dict[tuple[str, int], op_registry.BoundOp] = {}
        self._strict: dict[tuple[str, int], bool] = {}
        self._bucket_stats: dict[tuple[str, int], _BucketStats] = {}
        self._buckets: dict[tuple[str, int], deque[_Request]] = {}
        self._bind_lock = threading.Lock()
        self._cv = threading.Condition()
        self._pending = 0
        self._served = 0
        self._next_rid = 0
        self._stopping = False
        self.results: dict[int, np.ndarray] = {}
        self._latencies_s: list[float] = []
        # this engine's spans and wait counters (``stats()["spans"]``)
        self.telemetry = telemetry.Recorder()
        # Warm-restart carry-over (``from_snapshot`` fills these): lifetime
        # counters from before the restart, so the trajectory a deployment
        # reports survives preemption instead of resetting to zero.
        self.restarts = 0
        self._prev_served = 0
        self._prev_batches = 0
        self._prev_bucket_served: dict[str, int] = {}
        self._prev_latencies_s: list[float] = []
        # Straggler watchdog over per-batch service times (dispatch ->
        # materialized); ``on_evict(engine, batch_idx)`` is the elastic
        # hook — the driver checkpoints, resizes the mesh and warm-restarts
        # (``elastic_restart``). Default: record the event.
        self.evictions: list[int] = []
        self._user_on_evict = on_evict
        self.watchdog = StepWatchdog(watchdog_cfg,
                                     on_evict=self._handle_evict)
        self._batch_idx = 0

    def _handle_evict(self, batch_idx: int) -> None:
        self.evictions.append(batch_idx)
        if self._user_on_evict is not None:
            self._user_on_evict(self, batch_idx)

    # -- registration -------------------------------------------------------

    def register(self, op: str, n: int, *, strict: bool = False
                 ) -> op_registry.BoundOp:
        """Resolve (op, n) against the registry and open its bucket.

        Validation errors surface as :class:`~repro.launch.ops.
        OpConfigError` here, at admission of the SHAPE, not mid-stream.
        """
        key = (op, n)
        with self._bind_lock:
            if key not in self._bound:
                spec = op_registry.get_op(op)
                bound = spec.bind(n, self.ctx, strict=strict)
                if self.fault_model is not None:
                    # one virtual crossbar array per bucket, assigned
                    # round-robin over the model's array space
                    aid = self._next_array_id % self.fault_model.n_arrays
                    self._next_array_id += 1
                    inj = _FaultInjector(self.fault_model, aid, bound)
                    bound.fn = inj
                    self._injectors[key] = inj
                self._bound[key] = bound
                self._strict[key] = strict
                self._buckets[key] = deque()
                self._bucket_stats[key] = _BucketStats()
            return self._bound[key]

    def bound(self, op: str, n: int) -> op_registry.BoundOp:
        return self.register(op, n)

    def warmup(self) -> None:
        """Compile every registered bucket at its block cap (deploy-time
        warmup: reported throughput is steady state, not trace+compile)."""
        for bound in list(self._bound.values()):
            bound.warmup(self.max_batch)

    # -- admission ----------------------------------------------------------

    def submit(self, op: str, n: int, payload, *, rid: int | None = None,
               block: bool = True, timeout: float | None = None,
               deadline_s: float | None = None) -> int:
        """Enqueue one request; returns its rid.

        Blocks while the bounded queue is full (``block=False`` raises
        :class:`Backpressure` instead — the caller's cue to shed load).
        ``timeout`` bounds THIS call's wait for queue space;
        ``deadline_s`` bounds the REQUEST's total time-to-result: a
        request still queued past its deadline is completed with a
        structured ``deadline_exceeded`` error (``engine.errors[rid]``)
        instead of a result, and is excluded from the latency record.
        """
        t_offer = time.perf_counter()
        with self.telemetry.span("serve.admit"):
            if deadline_s is not None and deadline_s <= 0:
                raise ValueError(f"deadline_s={deadline_s} must be > 0")
            if self._stopping:
                raise EngineStopped(
                    "engine is draining (request_stop/SIGTERM); submit "
                    "after the warm restart")
            bound = self.register(op, n)    # validates shape/route once
            bound.check_payload(payload)    # reject NaN/Inf BEFORE it batches
            deadline = (None if timeout is None
                        else time.perf_counter() + timeout)
            with self._cv:
                if self._pending >= self.max_pending:
                    with self.telemetry.span("serve.backpressure"):
                        self._wait_for_space(block, timeout, deadline)
                if rid is None:
                    rid = self._next_rid
                self._next_rid = max(self._next_rid, rid + 1)
                now = time.perf_counter()
                self._buckets[bound.key].append(
                    _Request(rid, bound.key, payload, now, t_offer,
                             deadline=(None if deadline_s is None
                                       else now + deadline_s)))
                self._pending += 1
                self._cv.notify_all()
            return rid

    def _wait_for_space(self, block: bool, timeout: float | None,
                        deadline: float | None) -> None:
        """Block until the bounded queue has room (caller holds ``_cv``)."""
        while self._pending >= self.max_pending:
            if self._stopping:
                raise EngineStopped(
                    "engine is draining (request_stop/SIGTERM); "
                    "submit after the warm restart")
            if not block:
                raise Backpressure(
                    f"queue full ({self._pending}/{self.max_pending} "
                    f"pending); retry or shed load")
            remaining = (None if deadline is None
                         else deadline - time.perf_counter())
            if remaining is not None and remaining <= 0:
                raise Backpressure(
                    f"queue full after {timeout}s "
                    f"({self._pending}/{self.max_pending} pending)")
            self._cv.wait(remaining if remaining is not None else 0.1)

    # -- scheduling ---------------------------------------------------------

    def _sweep_expired_locked(self) -> None:
        """Complete deadline-expired queued requests with a structured
        error (caller holds ``_cv``). Expired requests count as served —
        they are COMPLETED, just without a result — so ``run`` terminates;
        they never enter the latency record, so p99 describes delivered
        results only."""
        now = time.perf_counter()
        for key, q in self._buckets.items():
            if not any(r.deadline is not None and r.deadline < now
                       for r in q):
                continue
            keep: deque[_Request] = deque()
            for r in q:
                if r.deadline is not None and r.deadline < now:
                    self.errors[r.rid] = {
                        "error": "deadline_exceeded",
                        "op": key[0], "n": key[1],
                        "waited_s": now - r.t_submit,
                    }
                    self._bucket_stats[key].expired += 1
                    self._pending -= 1
                    self._served += 1
                else:
                    keep.append(r)
            self._buckets[key] = keep
        self._cv.notify_all()

    def _pop_batch(self, timeout: float) -> tuple[tuple[str, int],
                                                  list[_Request]] | None:
        """Take up to ``max_batch`` requests from the non-empty bucket whose
        head has waited longest; None if nothing arrives within timeout."""
        with self.telemetry.span("serve.batch"), self._cv:
            if not any(self._buckets.values()):
                with self.telemetry.span("serve.wait"):
                    self._cv.wait(timeout)
            self._sweep_expired_locked()
            ready = [(q[0].t_submit, key)
                     for key, q in self._buckets.items() if q]
            if not ready:
                return None
            _, key = min(ready)
            q = self._buckets[key]
            take = min(len(q), self.max_batch)
            reqs = [q.popleft() for _ in range(take)]
            self._pending -= take
            self._cv.notify_all()
            now = time.perf_counter()
            self.telemetry.count("queue_wait",
                                 sum(now - r.t_submit for r in reqs), take)
            return key, reqs

    def _dispatch(self, key: tuple[str, int], reqs: list[_Request]):
        """Stage + launch one batch at its ACTUAL size (async for device
        routes); the sync happens later in ``_resolve``."""
        return self._bound[key].execute([r.payload for r in reqs])

    def _verified_rows(self, key: tuple[str, int], reqs: list[_Request],
                       arr: np.ndarray) -> np.ndarray:
        """ABFT gate for one deliverable batch: check, then on detected
        corruption re-execute up to ``retry_cap`` times with exponential
        backoff; exhausted retries trip the bucket's circuit breaker
        (injector unwrapped + array quarantine) and re-run once on the
        clean route. Raises RuntimeError only if even the fallback route
        fails its check — no corrupted batch is ever delivered."""
        bound = self._bound[key]
        payloads = [r.payload for r in reqs]
        bs = self._bucket_stats[key]
        verdict = bound.integrity(payloads, arr)
        bs.checked += 1
        if verdict.ok:
            return arr
        bs.corrupted += 1
        for attempt in range(self.retry_cap):
            time.sleep(self.retry_backoff_s * (2 ** attempt))
            bs.retried += 1
            arr = bound.to_numpy(bound.execute(payloads))
            verdict = bound.integrity(payloads, arr)
            bs.checked += 1
            if verdict.ok:
                return arr
            bs.corrupted += 1
        bs.fell_back += 1
        bound = self._trip_breaker(key)
        arr = bound.to_numpy(bound.execute(payloads))
        verdict = bound.integrity(payloads, arr)
        bs.checked += 1
        if not verdict.ok:
            bs.corrupted += 1
            raise RuntimeError(
                f"integrity check still failing after circuit-breaker "
                f"fallback for {key[0]}/n={key[1]}: "
                f"{verdict.detail or verdict.check}")
        return arr

    def _trip_breaker(self, key: tuple[str, int]):
        """Open the bucket's circuit breaker: restore the bucket's
        unwrapped ``fn``, so no fault injector runs for it from now on,
        and quarantine its simulated array to a spare."""
        bound = self._bound[key]
        with self._bind_lock:
            self._breaker_open.add(key)
            inj = self._injectors.pop(key, None)
            if inj is not None:
                bound.fn = inj.inner
                from repro.core.pim.faults import SparesExhausted
                try:
                    self.fault_model.quarantine(inj.array_id)
                except SparesExhausted:
                    pass    # the unwrapped fn still isolates the bucket
        return bound

    def _resolve(self, key: tuple[str, int], reqs: list[_Request],
                 out) -> None:
        """Materialize a dispatched batch: record results + latencies."""
        arr = self._bound[key].to_numpy(out)
        if self.verified:
            with self.telemetry.span("serve.verify"):
                arr = self._verified_rows(key, reqs, arr)
        with self.telemetry.span("serve.deliver"):
            t_done = time.perf_counter()
            assert arr.shape[0] == len(reqs), \
                f"batch executed at {arr.shape[0]} rows for {len(reqs)} " \
                f"requests"
            stats = self._bucket_stats[key]
            for j, req in enumerate(reqs):
                self.results[req.rid] = arr[j]
                self._latencies_s.append(t_done - req.t_offer)
            stats.served += len(reqs)
            stats.batches += 1
            stats.batch_sizes.append(len(reqs))
            self._served += len(reqs)

    # -- the serve loop -----------------------------------------------------

    def request_stop(self) -> None:
        """SIGTERM path: stop ADMITTING (submit raises
        :class:`EngineStopped`) but keep serving — ``run`` drains every
        already-admitted request, resolves the in-flight batch, and
        returns. The caller then ``snapshot``s and warm-restarts."""
        with self._cv:
            self._stopping = True
            self._cv.notify_all()

    @property
    def stopping(self) -> bool:
        return self._stopping

    def run(self, total_requests: int) -> dict:
        """Serve until ``total_requests`` results have materialized in this
        engine instance (lifetime counters from BEFORE a warm restart do
        not raise the bar — ``_served`` restarts at zero), or — after
        ``request_stop`` — until the admitted backlog has fully drained.

        One batch is kept in flight: batch k+1 is staged and dispatched
        before batch k is synced, so transfer and compute overlap. Each
        batch's service time (dispatch -> materialized) feeds the straggler
        watchdog. The engine's recorder is current on this thread while it
        serves, so the spans of ``BoundOp`` and the routes beneath land in
        it. Returns the stats dict (see ``stats``).
        """
        target = total_requests
        t0 = time.perf_counter()
        inflight: tuple | None = None

        def finish(flight) -> None:
            key, reqs, out, t_disp = flight
            self._resolve(key, reqs, out)
            t_done = time.perf_counter()
            self._batch_idx += 1
            self.watchdog.observe(self._batch_idx, t_done - t_disp)
            # observed service time, attributed to the bucket: the
            # measured side of predicted-vs-observed in stats()
            self._bucket_stats[key].service_s += t_done - t_disp

        with self.telemetry.current():
            while self._served < target:
                if self._stopping and self._pending == 0:
                    break   # drained: nothing left to admit or schedule
                picked = self._pop_batch(
                    0.0 if self._stopping else self.collect_timeout_s)
                if picked is None:
                    if inflight is not None:
                        finish(inflight)
                        inflight = None
                    continue
                key, reqs = picked
                tb = time.perf_counter()
                out = self._dispatch(key, reqs)
                if inflight is not None:
                    finish(inflight)
                inflight = (key, reqs, out, tb)
            if inflight is not None:
                finish(inflight)
        return self.stats(seconds=time.perf_counter() - t0)

    # -- metrics ------------------------------------------------------------

    def stats(self, *, seconds: float) -> dict:
        # Percentiles over the bounded recent window (LATENCY_WINDOW):
        # lifetime inputs decay instead of accumulating across restarts.
        lat = np.asarray((self._prev_latencies_s
                          + self._latencies_s)[-LATENCY_WINDOW:],
                         np.float64) * 1e3
        if lat.size:
            p50, p90, p99 = np.percentile(lat, [50, 90, 99])
            latency_ms = {"p50": float(p50), "p90": float(p90),
                          "p99": float(p99), "mean": float(lat.mean()),
                          "max": float(lat.max())}
        else:
            latency_ms = {"p50": 0.0, "p90": 0.0, "p99": 0.0,
                          "mean": 0.0, "max": 0.0}
        buckets = {}
        for key, bs in self._bucket_stats.items():
            op, n = key
            sizes = bs.batch_sizes
            buckets[f"{op}/n={n}"] = {
                "op": op, "n": n, "served": bs.served,
                "lifetime_served": (self._prev_bucket_served.get(
                    f"{op}/{n}", 0) + bs.served),
                "batches": bs.batches,
                "route": self._bound[key].route,
                "max_block": self.max_batch,
                "mean_batch": (sum(sizes) / len(sizes)) if sizes else 0.0,
                # fill of the continuous-batching block: 1.0 means every
                # dispatch ran at the cap, < 1 quantifies tail/trickle cost
                "utilization": (sum(sizes) / (len(sizes) * self.max_batch))
                               if sizes else 0.0,
                "batch_sizes": list(sizes),
                # observed per-request service seconds (dispatch ->
                # materialized, batch time amortized over its rows)
                "observed_s_per_req": (bs.service_s / bs.served
                                       if bs.served else None),
                "expired": bs.expired,
                # ABFT ledger (all zeros when verified=False): the
                # detected -> retried -> fell_back recovery trajectory
                "integrity": {
                    "checked": bs.checked,
                    "corrupted": bs.corrupted,
                    "retried": bs.retried,
                    "fell_back": bs.fell_back,
                    "breaker_open": key in self._breaker_open,
                },
            }
        batches = sum(b.batches for b in self._bucket_stats.values())
        recorded = self.telemetry.snapshot()
        spans, counters = recorded["spans"], recorded["counters"]
        # host seconds the serve loop spent dispatching, fetching and
        # delivering batches (not queue collection)
        compute_s = sum(spans[name][1] for name in
                        ("serve.dispatch", "serve.fetch", "serve.deliver")
                        if name in spans)
        queued, queued_s = counters.get("queue_wait", (0, 0.0))
        admits = spans.get("serve.admit", (0, 0.0, 0.0))[0]
        blocked_s = spans.get("serve.backpressure", (0, 0.0, 0.0))[1]
        return {
            "served": self._served,
            "batches": batches,
            # requests completed with a deadline_exceeded error (included
            # in ``served`` — they are finished — but never in latency_ms)
            "expired": sum(b.expired for b in self._bucket_stats.values()),
            "seconds": seconds,
            "throughput_per_s": self._served / max(seconds, 1e-9),
            # busy-only rate: excludes queue-collection waits, so endpoint
            # comparisons reflect dispatch+compute, not the driver
            "compute_seconds": compute_s,
            "compute_throughput_per_s": self._served / max(compute_s, 1e-9),
            "latency_ms": latency_ms,
            # mean per request of admission -> pop
            "queue_wait_ms": 1e3 * queued_s / queued if queued else 0.0,
            # mean per submit call of offer -> admission: the wait for
            # queue space (serve.backpressure)
            "admission_wait_ms": 1e3 * blocked_s / admits if admits else 0.0,
            "spans": {name: {"count": c, "total_ms": 1e3 * total,
                             "self_ms": 1e3 * own}
                      for name, (c, total, own) in spans.items()},
            # every counter of the recorder: queue_wait (requests), and on
            # the hbm tier hbm.batches and hbm.outer_passes
            "counters": {name: {"count": c, "total_ms": 1e3 * total}
                         for name, (c, total) in counters.items()},
            "buckets": buckets,
            # deployment-lifetime view: counters carried across warm
            # restarts (``from_snapshot``), so preemption does not reset
            # the trajectory a long-running endpoint reports
            "lifetime": {
                "served": self._prev_served + self._served,
                "batches": self._prev_batches + batches,
                "restarts": self.restarts,
            },
            "watchdog": {"events": list(self.watchdog.events),
                         "evictions": list(self.evictions),
                         "ewma_s": self.watchdog.ewma},
        }

    # -- snapshot / warm restart (docs/fault_tolerance.md) ------------------

    def snapshot(self, ckpt_dir: str) -> str:
        """Persist the engine's durable state through ``ft.checkpoint``.

        Must be called DRAINED (after ``request_stop`` + ``run`` returned):
        a snapshot with admitted-but-unserved requests would silently drop
        them on restart, so pending requests are a hard error. The saved
        tree carries the lifetime latency record; the manifest ``extra``
        carries bucket config (op, n, strict), engine knobs, counters and
        the watchdog state. Results themselves are NOT snapshotted —
        delivered results belong to the clients that collected them.
        """
        if self._pending:
            raise RuntimeError(
                f"snapshot with {self._pending} pending requests would "
                f"drop them: request_stop() and let run() drain first")
        # Bounded: persist at most the recent LATENCY_WINDOW samples, so a
        # snapshot -> restart -> snapshot loop plateaus instead of growing
        # the payload by each generation's traffic (the old unbounded
        # prev+current concatenation did exactly that).
        lat = np.asarray((self._prev_latencies_s
                          + self._latencies_s)[-LATENCY_WINDOW:],
                         np.float64)
        extra = {
            "schema": SNAPSHOT_SCHEMA,
            "engine": {"max_batch": self.max_batch,
                       "max_pending": self.max_pending,
                       "collect_timeout_s": self.collect_timeout_s,
                       "modulus_bits": self.ctx.modulus_bits,
                       "model_shards": self.ctx.model_shards,
                       "verified": self.verified},
            "buckets": [{"op": op, "n": n, "strict": self._strict[(op, n)]}
                        for op, n in self._bound],
            "counters": {
                "served": self._prev_served + self._served,
                "batches": self._prev_batches
                           + sum(b.batches for b in
                                 self._bucket_stats.values()),
                "next_rid": self._next_rid,
                "restarts": self.restarts,
                "bucket_served": {
                    f"{op}/{n}": (self._prev_bucket_served.get(
                        f"{op}/{n}", 0) + self._bucket_stats[(op, n)].served)
                    for op, n in self._bound},
            },
            "watchdog": self.watchdog.state_dict(),
        }
        step = self._prev_served + self._served
        return ckpt_lib.save(ckpt_dir, step,
                             {"latencies_s": lat}, extra=extra)

    @classmethod
    def from_snapshot(cls, ckpt_dir: str, *,
                      model_shards: int | None = None,
                      max_batch: int | None = None,
                      watchdog_cfg: Optional[WatchdogConfig] = None,
                      on_evict: Optional[Callable[["ServeEngine", int],
                                                  None]] = None
                      ) -> "ServeEngine":
        """Warm-restart from ``snapshot``: rebuild the engine, re-register
        and re-BIND every bucket on the restart-time context (pass
        ``model_shards`` to re-shard elastically — this is the resize leg
        of the watchdog's checkpoint -> resize -> restore path), and carry
        the lifetime counters, latency record and watchdog baseline over.
        """
        step = ckpt_lib.latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no engine snapshot under {ckpt_dir}")
        extra = ckpt_lib.read_extra(ckpt_dir, step)
        if extra.get("schema") != SNAPSHOT_SCHEMA:
            raise ValueError(
                f"{ckpt_dir} step {step} is not an engine snapshot "
                f"(schema={extra.get('schema')!r})")
        _, restored = ckpt_lib.restore_latest(
            ckpt_dir, {"latencies_s": np.zeros(0, np.float64)})
        eng_cfg = extra["engine"]
        engine = cls(
            max_batch=max_batch or eng_cfg["max_batch"],
            max_pending=eng_cfg["max_pending"],
            collect_timeout_s=eng_cfg["collect_timeout_s"],
            modulus_bits=eng_cfg["modulus_bits"],
            model_shards=(eng_cfg["model_shards"] if model_shards is None
                          else model_shards),
            verified=bool(eng_cfg.get("verified", False)),
            watchdog_cfg=watchdog_cfg, on_evict=on_evict)
        for b in extra["buckets"]:
            engine.register(b["op"], int(b["n"]), strict=bool(b["strict"]))
        counters = extra["counters"]
        engine._prev_served = int(counters["served"])
        engine._prev_batches = int(counters["batches"])
        engine._prev_bucket_served = dict(counters["bucket_served"])
        engine._next_rid = int(counters["next_rid"])
        engine.restarts = int(counters["restarts"]) + 1
        engine._prev_latencies_s = [
            float(v) for v in np.asarray(restored["latencies_s"])]
        engine.watchdog.load_state_dict(extra.get("watchdog", {}))
        return engine

    def elastic_restart(self, ckpt_dir: str, *,
                        model_shards: int | None = None,
                        max_batch: int | None = None) -> "ServeEngine":
        """The on_evict path in one move: snapshot this (drained) engine,
        then warm-restart it with a resized context. Returns the NEW
        engine; this one stays stopped."""
        self.snapshot(ckpt_dir)
        return ServeEngine.from_snapshot(
            ckpt_dir, model_shards=model_shards, max_batch=max_batch,
            watchdog_cfg=self.watchdog.cfg, on_evict=self._user_on_evict)
