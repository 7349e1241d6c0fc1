"""Serving drivers.

--service fft    : single-op batched transform service. The op, its route
                   (local / RNS / distributed), payload dtype, warmup
                   shape, traffic generator and result verifier all come
                   from the op-dispatch registry (``launch/ops.py``) — the
                   same table the continuous-batching engine, benchmarks
                   and tests dispatch through.

--service engine : multiplexing continuous-batching engine
                   (``launch/engine.py``): a mixed stream of requests,
                   each with its own (op, n), is shape-bucketed and served
                   from ONE process with tail batches at actual size,
                   deferred device sync (next batch transfers while the
                   current one computes), bounded-queue backpressure, and
                   per-request p50/p99 latency reported alongside
                   throughput (docs/serving.md).

--service lm     : batched greedy decode for any --arch (reduced with
                   --smoke): prefill then token-by-token decode_step.

Example:
  PYTHONPATH=src python -m repro.launch.serve --service fft --n 1024 \
      --batch 64 --requests 512 --op polymul-real
  # exact modular (RLWE negacyclic) polymul endpoint:
  PYTHONPATH=src python -m repro.launch.serve --service fft --n 1024 \
      --batch 32 --requests 128 --op polymul-mod
  # multi-limb RNS route for FHE-scale moduli (limb count from the bits):
  PYTHONPATH=src python -m repro.launch.serve --service fft --n 1024 \
      --batch 8 --requests 16 --op polymul-mod --modulus-bits 120
  # distributed exact tier (four-step NTT over 8 sequence shards):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.serve --service fft --n 1024 --batch 4 \
      --requests 16 --op polymul-mod --model-shards 8
  # mixed-op continuous batching: one engine, four ops, two lengths,
  # the polymul-real / polymul-mod buckets on the distributed tier:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.serve --service engine \
      --ops fft,rfft,polymul-real,polymul-mod --ns 512,1024 \
      --model-shards 8 --batch 8 --requests 64
  PYTHONPATH=src python -m repro.launch.serve --service lm \
      --arch qwen3-1.7b --smoke --prompt-len 32 --gen 32
"""
from __future__ import annotations

import argparse
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.launch import ops as op_registry
from repro.launch.cache import setup_compile_cache
from repro.launch.engine import ServeEngine
from repro.models import lm


# ---------------------------------------------------------------------------
# FFT service (single-op): a thin wrapper over the registry + engine
# ---------------------------------------------------------------------------

class FFTService:
    """Single-op transform service: one registry bucket on the engine.

    All op dispatch — route selection (local packed / RNS / distributed),
    payload dtype, warmup shape — flows through ``launch/ops.py``; this
    class only pins ONE (op, n) bucket and keeps the legacy surface
    (``plan``/``route``/``_fn``/``ntt_params``/``rns``/``mesh``) that
    tests and callers assert against. Invalid combinations (RNS +
    model_shards, unknown knobs, non-tileable shapes) raise
    :class:`~repro.launch.ops.OpConfigError` from the registry's own
    validation, here at construction.
    """

    def __init__(self, n: int, batch: int, op: str = "fft",
                 modulus_bits: int | None = None, model_shards: int = 1):
        self.n = n
        self.batch = batch
        self.op = op
        self.engine = ServeEngine(max_batch=batch,
                                  modulus_bits=modulus_bits,
                                  model_shards=model_shards)
        # strict: knobs the op does not consume are config errors, not
        # silently ignored flags
        self.bound = self.engine.register(op, n, strict=True)
        self.plan = self.bound.plan
        self.route = self.bound.route
        self._fn = self.bound.fn
        self.ntt_params = self.bound.ntt_params
        self.rns = self.bound.rns
        self.mesh = self.bound.mesh
        self.results = self.engine.results

    def warmup(self) -> None:
        """Compile the batch function before serving (deploy-time warmup):
        the reported throughput is steady-state, not trace+compile."""
        self.engine.warmup()

    def submit(self, req_id: int, payload):
        self.engine.submit(self.op, self.n, payload, rid=req_id)

    def run(self, total_requests: int) -> dict:
        return self.engine.run(total_requests)


def run_fft_service(args) -> dict:
    rng = np.random.default_rng(0)
    svc = FFTService(args.n, args.batch, args.op,
                     modulus_bits=args.modulus_bits,
                     model_shards=args.model_shards)
    svc.warmup()
    first: dict[int, object] = {}

    def producer():
        for rid in range(args.requests):
            payload = svc.bound.random_payload(rng)
            if rid == 0:
                first[rid] = payload
            svc.submit(rid, payload)

    th = threading.Thread(target=producer, daemon=True)
    th.start()
    stats = svc.run(args.requests)
    th.join()
    # verify one served result against the registry's numpy oracle
    if first:
        svc.bound.verify(first[0], svc.results[0])
    limbs = f" limbs={svc.rns.k} Q~2^{svc.rns.modulus.bit_length()}" \
        if svc.rns is not None else ""
    lat = stats["latency_ms"]
    print(f"[serve:fft] op={args.op}{limbs} route={svc.route} n={args.n} "
          f"batch={args.batch} served={stats['served']} in "
          f"{stats['seconds']:.2f}s "
          f"-> {stats['throughput_per_s']:.1f} req/s "
          f"(compute-only {stats['compute_throughput_per_s']:.1f} req/s) "
          f"p50={lat['p50']:.2f}ms p99={lat['p99']:.2f}ms "
          f"[{svc.plan.describe()}]")
    return stats


# ---------------------------------------------------------------------------
# Mixed-op continuous-batching engine service
# ---------------------------------------------------------------------------

def _watchdog_cfg_from_args(args):
    from repro.ft.watchdog import WatchdogConfig
    if (args.watchdog_threshold is None and args.watchdog_evict_after
            is None and args.watchdog_warmup is None):
        return None
    base = WatchdogConfig()
    return WatchdogConfig(
        threshold=(base.threshold if args.watchdog_threshold is None
                   else args.watchdog_threshold),
        evict_after=(base.evict_after if args.watchdog_evict_after is None
                     else args.watchdog_evict_after),
        warmup_steps=(base.warmup_steps if args.watchdog_warmup is None
                      else args.watchdog_warmup))


def _arm_chaos(engine: ServeEngine, args) -> None:
    """Deterministic straggler injection for exercising the elastic path
    at the CLI (tests/CI): batches after --inject-straggler-after sleep
    --inject-straggler-ms before dispatch, so the watchdog's EWMA sees a
    consecutive run of breaches. Armed only on the FIRST generation —
    after an elastic restart the resized engine serves cleanly."""
    if not args.inject_straggler_ms or engine.restarts > 0:
        return
    counter = {"i": 0}

    def make_slow(fn):
        def slow(*a):
            counter["i"] += 1
            if counter["i"] > args.inject_straggler_after:
                time.sleep(args.inject_straggler_ms / 1e3)
            return fn(*a)
        return slow

    for bound in engine._bound.values():
        bound.fn = make_slow(bound.fn)


def run_engine_service(args) -> dict:
    """Serve a mixed (op, n) stream from one engine process.

    Buckets come from the cross product of ``--ops`` and ``--ns``; the
    process-level ``--modulus-bits`` / ``--model-shards`` context is
    narrowed per op (ops without that route stay local), so one engine can
    serve local fft next to the distributed polymul-mod tier. One result
    per bucket is verified against the registry's numpy oracle after the
    drain.

    With ``--snapshot-dir`` the process is preemption-safe
    (docs/fault_tolerance.md): SIGTERM stops admission, the engine drains
    every already-admitted request, and the lifetime stats + bucket config
    + watchdog state are snapshotted through ``ft.checkpoint``; a restart
    with the same ``--snapshot-dir`` warm-restarts from the snapshot
    (buckets re-bind on the restart-time context, counters carry over).

    ``--elastic`` (requires ``--snapshot-dir``) closes the watchdog loop
    AT the CLI: an eviction drains the engine, snapshots, and
    warm-restarts it with ``--model-shards`` halved (floor 1) — the
    checkpoint -> resize -> restore path that previously only tests could
    drive — then keeps serving the remaining requests.
    """
    ops = [s.strip() for s in args.ops.split(",") if s.strip()]
    ns = [int(s) for s in args.ns.split(",") if s.strip()]
    from repro.ft import checkpoint as ckpt_lib
    from repro.launch.engine import EngineStopped

    # chaos fault model (docs/fault_tolerance.md): one virtual crossbar
    # array per bucket plus enough spares to quarantine every one of them
    fault_model = None
    if args.inject_faults or args.inject_stuck:
        from repro.core.pim import FaultModel
        n_buckets = max(4, len(ops) * len(ns))
        fault_model = FaultModel(seed=args.inject_fault_seed,
                                 bitflip_per_gate=args.inject_faults,
                                 stuck_per_array=args.inject_stuck,
                                 n_arrays=n_buckets, spares=n_buckets)

    holder: dict = {"engine": None, "evicted": False}

    def _on_evict(eng, batch_idx):
        if not args.elastic:
            return
        holder["evicted"] = True
        print(f"[serve:engine] watchdog evicted batch {batch_idx}: "
              f"draining for elastic resize", flush=True)
        # request_stop on a separate thread for the same reason as the
        # SIGTERM handler: never take the engine's condition lock from
        # a frame that may already hold it.
        threading.Thread(target=eng.request_stop, daemon=True).start()

    wd_cfg = _watchdog_cfg_from_args(args)
    if args.snapshot_dir and ckpt_lib.latest_step(args.snapshot_dir) \
            is not None:
        engine = ServeEngine.from_snapshot(args.snapshot_dir,
                                           model_shards=args.model_shards,
                                           max_batch=args.batch,
                                           watchdog_cfg=wd_cfg,
                                           on_evict=_on_evict)
        print(f"[serve:engine] warm restart #{engine.restarts} from "
              f"{args.snapshot_dir} "
              f"(lifetime served: {engine.stats(seconds=1)['lifetime']['served']})")
    else:
        engine = ServeEngine(max_batch=args.batch,
                             max_pending=args.max_pending,
                             modulus_bits=args.modulus_bits,
                             model_shards=args.model_shards,
                             verified=args.verify,
                             fault_model=fault_model,
                             watchdog_cfg=wd_cfg,
                             on_evict=_on_evict)
    holder["engine"] = engine
    prev_term = None
    if args.snapshot_dir:
        import signal

        def _on_term(signum, frame):
            # drain-and-snapshot path: stop admitting; run() finishes the
            # admitted backlog and returns, then the snapshot lands below.
            # Installed BEFORE warmup: a preemption during compile still
            # drains (to an empty backlog) and snapshots. request_stop runs
            # on a SEPARATE thread: the handler executes on the main
            # thread's frame, which may be INSIDE the engine's condition
            # lock — taking it from the handler would self-deadlock.
            threading.Thread(target=holder["engine"].request_stop,
                             daemon=True).start()
        prev_term = signal.signal(signal.SIGTERM, _on_term)

    rng = np.random.default_rng(0)
    combos = [(op, n) for op in ops for n in ns]

    def serve_round(engine: ServeEngine, n_requests: int) -> dict:
        """One engine generation: register + warmup, produce, drain,
        verify one result per bucket. Returns the round's stats."""
        for op in ops:
            for n in ns:
                engine.register(op, n)
        _arm_chaos(engine, args)
        engine.warmup()
        kept: dict[tuple[str, int], tuple[int, object]] = {}
        # chaos runs verify EVERY delivered result against the numpy
        # oracle — the "zero incorrect results" half of the chaos pin
        kept_all: dict[int, tuple[str, int, object]] = {}

        def producer():
            try:
                for i in range(n_requests):
                    op, n = combos[i % len(combos)]
                    payload = engine.bound(op, n).random_payload(rng)
                    rid = engine.submit(op, n, payload)
                    if (op, n) not in kept:
                        kept[(op, n)] = (rid, payload)
                    if fault_model is not None:
                        kept_all[rid] = (op, n, payload)
            except EngineStopped:
                pass  # draining toward a snapshot: shed the rest

        th = threading.Thread(target=producer, daemon=True)
        th.start()
        # sync marker for supervisors/tests: warmup done, handler armed
        print(f"[serve:engine] serving {n_requests} requests "
              f"across {len(combos)} buckets", flush=True)
        stats = engine.run(n_requests)
        th.join()
        for (op, n), (rid, payload) in kept.items():
            if rid in engine.results:   # absent only if shed in a drain
                engine.bound(op, n).verify(payload, engine.results[rid])
        for rid, (op, n, payload) in kept_all.items():
            if rid in engine.results:
                engine.bound(op, n).verify(payload, engine.results[rid])
        return stats

    try:
        remaining = args.requests
        while True:
            holder["evicted"] = False
            stats = serve_round(engine, remaining)
            remaining -= stats["served"]
            if args.elastic and holder["evicted"] and remaining > 0:
                new_shards = max(1, engine.ctx.model_shards // 2)
                print(f"[serve:engine] elastic restart: model_shards "
                      f"{engine.ctx.model_shards} -> {new_shards}, "
                      f"{remaining} requests left", flush=True)
                engine = engine.elastic_restart(args.snapshot_dir,
                                                model_shards=new_shards)
                holder["engine"] = engine
                continue
            break
        if fault_model is not None:
            integ = [b["integrity"] for b in stats["buckets"].values()]
            detected = sum(v["corrupted"] for v in integ)
            retried = sum(v["retried"] for v in integ)
            fell_back = sum(v["fell_back"] for v in integ)
            print(f"[serve:engine] chaos: detected={detected} "
                  f"retried={retried} fell_back={fell_back} "
                  f"quarantined={len(fault_model.quarantined)}", flush=True)
            if detected < 1 or retried < 1:
                raise SystemExit(
                    "chaos run produced no detected->retried event: the "
                    "injection settings are not exercising the ABFT "
                    "recovery path (raise --inject-faults or set "
                    "--inject-stuck)")
        if args.snapshot_dir:
            path = engine.snapshot(args.snapshot_dir)
            print(f"[serve:engine] snapshot -> {path}")
    finally:
        if prev_term is not None:
            # the handler closes over the engine holder — leaving it
            # installed would hijack SIGTERM for any later engine in the
            # process (e.g. an in-process warm restart or the test runner)
            import signal
            signal.signal(signal.SIGTERM, prev_term)

    lat = stats["latency_ms"]
    print(f"[serve:engine] buckets={len(stats['buckets'])} "
          f"served={stats['served']} in {stats['seconds']:.2f}s "
          f"-> {stats['throughput_per_s']:.1f} req/s "
          f"(compute-only {stats['compute_throughput_per_s']:.1f} req/s) "
          f"p50={lat['p50']:.2f}ms p90={lat['p90']:.2f}ms "
          f"p99={lat['p99']:.2f}ms")
    for name, b in stats["buckets"].items():
        print(f"[serve:engine]   {name} route={b['route']} "
              f"served={b['served']} batches={b['batches']} "
              f"mean_batch={b['mean_batch']:.1f} "
              f"utilization={b['utilization']:.2f}")
    return stats


# ---------------------------------------------------------------------------
# LM decode service
# ---------------------------------------------------------------------------

def run_lm_service(args) -> dict:
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.scaled_down()
    params = lm.init_params(cfg, jax.random.key(0))
    B, S = args.batch, args.prompt_len
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    t0 = time.time()
    capacity = S + args.gen
    logits, state = lm.prefill(cfg, params, tokens,
                               cache_capacity=capacity)
    decode = jax.jit(lambda p, st, tok, pos: lm.decode_step(
        cfg, p, st, tok, pos))
    out_tokens = []
    tok = jnp.argmax(logits, axis=-1)
    for i in range(args.gen):
        out_tokens.append(np.asarray(tok))
        logits_i, state = decode(params, state, tok, jnp.int32(S + i))
        tok = jnp.argmax(logits_i, axis=-1)
    jax.block_until_ready(tok)
    dt = time.time() - t0
    toks = B * args.gen
    print(f"[serve:lm] arch={cfg.name} batch={B} prompt={S} gen={args.gen} "
          f"-> {toks / dt:.1f} tok/s (incl. prefill, jit warmup)")
    return {"tokens_per_s": toks / dt, "generated": np.stack(out_tokens)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--service", choices=["fft", "engine", "lm"],
                    default="fft")
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=32,
                    help="single-op batch / engine continuous-batching "
                         "block cap (tail batches run at actual size)")
    ap.add_argument("--requests", type=int, default=256)
    # the op surface is DERIVED from the registry: choices, help and
    # knob applicability can never drift from the dispatch table
    ap.add_argument("--op", default="fft", choices=op_registry.op_names(),
                    help=op_registry.cli_help())
    ap.add_argument("--ops", default="fft,rfft,polymul-real",
                    help="engine service: comma-separated op mix "
                         f"(choices: {', '.join(op_registry.op_names())})")
    ap.add_argument("--ns", default=None,
                    help="engine service: comma-separated sequence "
                         "lengths (default: --n)")
    ap.add_argument("--max-pending", type=int, default=1024,
                    help="engine service: bounded admission queue — "
                         "producers block (backpressure) when full")
    ap.add_argument("--snapshot-dir", default=None,
                    help="engine service: preemption-safe state dir — "
                         "SIGTERM drains in-flight buckets and snapshots "
                         "engine stats + bucket config there; a restart "
                         "with the same dir warm-restarts from it "
                         "(docs/fault_tolerance.md)")
    ap.add_argument("--modulus-bits", type=int, default=None,
                    help=op_registry.cli_knob_help(
                        "modulus_bits",
                        "target modulus width; > 30 routes through the "
                        "multi-limb RNS/CRT layer (docs/ntt.md)"))
    ap.add_argument("--model-shards", type=int, default=1,
                    help=op_registry.cli_knob_help(
                        "model_shards",
                        "shard the sequence over this many devices via "
                        "the distributed four-step NTT/FFT tiers"))
    ap.add_argument("--elastic", action="store_true",
                    help="engine service: on a watchdog eviction, drain + "
                         "snapshot + warm-restart with --model-shards "
                         "halved (requires --snapshot-dir)")
    ap.add_argument("--watchdog-threshold", type=float, default=None,
                    help="engine service: straggler threshold (x EWMA)")
    ap.add_argument("--watchdog-evict-after", type=int, default=None,
                    help="engine service: consecutive breaches before "
                         "eviction")
    ap.add_argument("--watchdog-warmup", type=int, default=None,
                    help="engine service: EWMA warmup batches")
    ap.add_argument("--inject-straggler-ms", type=float, default=0.0,
                    help="chaos: sleep this long before each dispatch "
                         "after --inject-straggler-after batches "
                         "(first engine generation only; drives the "
                         "--elastic path deterministically in tests)")
    ap.add_argument("--inject-straggler-after", type=int, default=0,
                    help="chaos: batches served cleanly before the "
                         "injected straggling starts")
    ap.add_argument("--verify", action="store_true",
                    help="engine service: ABFT integrity gate "
                         "(docs/fault_tolerance.md) — every deliverable "
                         "batch passes its op's check before any client "
                         "sees a result; detected corruption triggers "
                         "bounded re-execution, then a circuit-breaker "
                         "re-bind with the PIM backend quarantined")
    ap.add_argument("--inject-faults", type=float, default=0.0,
                    metavar="RATE",
                    help="chaos: per-gate transient bit-flip rate for a "
                         "seeded fault model wrapping each engine bucket; "
                         "delivered rows are corrupted deterministically "
                         "per (seed, array, dispatch) (requires --verify)")
    ap.add_argument("--inject-fault-seed", type=int, default=0,
                    help="chaos: fault model seed (replays exactly)")
    ap.add_argument("--inject-stuck", type=int, default=0,
                    help="chaos: stuck-at cells per simulated array — a "
                         "PERMANENT fault, so the bucket's retries fail "
                         "and the circuit breaker must trip (requires "
                         "--verify)")
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args(argv)
    if args.ns is None:
        args.ns = str(args.n)
    if args.elastic and not args.snapshot_dir:
        ap.error("--elastic requires --snapshot-dir (the eviction path "
                 "is snapshot -> resize -> restore)")
    if (args.inject_faults or args.inject_stuck) and not args.verify:
        ap.error("--inject-faults/--inject-stuck without --verify would "
                 "deliver corrupted results: chaos injection requires "
                 "the ABFT gate")
    setup_compile_cache()
    try:
        if args.service == "fft":
            return run_fft_service(args)
        if args.service == "engine":
            return run_engine_service(args)
    except op_registry.OpConfigError as e:
        # the registry's own validation message, as a clean CLI exit
        # instead of a deep traceback
        ap.error(str(e))
    return run_lm_service(args)


if __name__ == "__main__":
    main()
