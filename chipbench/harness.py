"""One run of one cell: set-up, the measured window, and the check.

A cell is found by its name alone. ``workloads/<cell>.json`` names its
configuration (``configs/<config>.json``), its traffic mix and the loop
that sends it (``traffic/<loop>.py``); every op the mix sends has
``ops/<op>.py`` (inputs, plain reference, control, comparison, work), and
every metric ``BENCHMARK.json`` lists has ``metrics/<metric>.py``. Nothing
here lists cells, configurations, ops, loops or metrics: a later one is a
new file.

The window drives the continuous-batching engine ``repro.launch.serve
--service engine`` runs: requests go in through ``ServeEngine.submit``,
``ServeEngine.run`` serves on the main thread, and ``request_stop`` at the
window's close lets it drain. The benchmark's only hooks are instance
attributes on each bound op (the hook the engine's fault injector uses on
``fn``): a span around ``execute`` (stack, transfer, launch) and around
``to_numpy``, where a batch's results become host arrays and its requests
complete.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import pathlib
import random
import re
import shutil
import threading
import time
from typing import Any

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
BENCHMARK_FILE = ROOT / "BENCHMARK.json"
OUT_DIR = HERE / "out"
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
# payloads drawn per op before the window; requests cycle through them
DEFAULT_POOL = 256
# arrivals an open loop schedules past the window's length, so that the
# sender never runs dry before the close
HORIZON_SPARE_S = 30.0


class BenchError(RuntimeError):
    """The cell cannot run here or is described wrongly: no result."""


def setup_process() -> None:
    """Before JAX is first imported: JAX's persistent compilation cache at
    ``<checkout>/.jax_cache`` (the path ``repro.launch.cache`` uses, fixed,
    inside the checkout, whatever the environment says) and no TPU
    compiler logs outside it."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.launch.cache import setup_compile_cache
    setup_compile_cache()


def read_json(path: pathlib.Path) -> Any:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"{path} not found") from None


def load_module(path: pathlib.Path):
    """Import a file of the benchmark by its path (names may hold '-')."""
    if not path.is_file():
        raise BenchError(f"{path} not found")
    tag = re.sub(r"\W", "_", "_".join(path.with_suffix("").parts[-2:]))
    spec = importlib.util.spec_from_file_location(f"chipbench_{tag}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    """A workload file with its configuration and its BENCHMARK.json rows."""
    name: str
    root: pathlib.Path
    workload: dict
    config: dict
    bench: dict
    _modules: dict = dataclasses.field(default_factory=dict)

    @classmethod
    def load(cls, name: str, root: pathlib.Path = HERE,
             bench_file: pathlib.Path = BENCHMARK_FILE) -> "Cell":
        if not NAME.fullmatch(name):
            raise BenchError(f"bad workload name {name!r}")
        workload = read_json(root / "workloads" / f"{name}.json")
        config = read_json(root / "configs" / f"{workload['config']}.json")
        bench = read_json(bench_file)
        rows = [w for w in bench.get("workloads", []) if w["name"] == name]
        if len(rows) != 1:
            raise BenchError(f"{bench_file.name} lists workload {name!r} "
                             f"{len(rows)} times")
        row = rows[0]
        if (row["config"], row["traffic"], row["chips"]) != (
                workload["config"], workload["traffic"], config["chips"]):
            raise BenchError(f"{bench_file.name}'s row for {name!r} does not "
                             f"match its workload and configuration files")
        unknown = set(workload["ops"]) - set(config["ops"])
        if unknown:
            raise BenchError(f"workload {name!r} sends ops {sorted(unknown)} "
                             f"that configuration {workload['config']!r} "
                             f"does not serve")
        return cls(name, root, workload, config, bench)

    def module(self, kind: str, name: str):
        key = (kind, name)
        if key not in self._modules:
            self._modules[key] = load_module(self.root / kind / f"{name}.py")
        return self._modules[key]

    @property
    def chips(self) -> int:
        return int(self.config["chips"])

    def metric_names(self, trace: bool) -> list[str]:
        """The cell's end-to-end metrics, or with a trace its per-layer
        ones, in BENCHMARK.json's order."""
        rows = self.bench["per_layer" if trace else "end_to_end"]
        return [m["name"] for m in rows
                if self.name in m.get("workloads", [self.name])]

    def metric_units(self) -> dict[str, str]:
        return {m["name"]: m["unit"]
                for m in self.bench["end_to_end"] + self.bench["per_layer"]}


@dataclasses.dataclass(eq=False)
class Request:
    seq: int
    op: str
    index: int                  # payload's place in the op's pool
    t_offer: float              # due (open loop) or call to submit (closed)
    payload: Any
    t_sent: float = math.nan
    t_done: float = math.nan


@dataclasses.dataclass
class Stage:
    """One batch's ``execute`` span on the host clock."""
    t0: float
    t1: float
    rows: int
    op: str


class Reservoir:
    """A uniform sample of k items from a stream, drawn from a seed."""

    def __init__(self, k: int, rng: random.Random):
        self.k, self.rng, self.seen, self.items = k, rng, 0, []

    def offer(self, make) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(make())
        else:
            j = self.rng.randrange(self.seen)
            if j < self.k:
                self.items[j] = make()


class CompileLog:
    """Compilation and compile-cache events, stamped on the host clock."""
    PREFIXES = ("/jax/core/compile", "/jax/compilation_cache/cache_")

    def __init__(self):
        import jax.monitoring
        self.events: list[tuple[float, str]] = []
        self._mon = jax.monitoring
        self._mon.register_event_duration_secs_listener(self._on_duration)
        self._mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        self._on_event(event)

    def _on_event(self, event: str, **_) -> None:
        if event.startswith(self.PREFIXES):
            self.events.append((time.perf_counter(), event))

    def between(self, t0: float, t1: float) -> list[str]:
        return [e for t, e in self.events if t0 <= t <= t1]

    def close(self) -> None:
        self._mon.unregister_event_duration_listener(self._on_duration)
        self._mon.unregister_event_listener(self._on_event)


def make_pools(cell: Cell, pools_ss: np.random.SeedSequence
               ) -> tuple[dict, dict]:
    """(op -> ``ops/<op>.py`` module, op -> payload pool) for the ops the
    cell's traffic sends, each pool from its own child of ``pools_ss``."""
    ops = {op: cell.module("ops", op) for op in cell.workload["ops"]}
    size = int(cell.config.get("pool", DEFAULT_POOL))
    pools = {op: mod.payloads(np.random.default_rng(ss), size, cell.config)
             for (op, mod), ss in zip(ops.items(), pools_ss.spawn(len(ops)))}
    return ops, pools


class Session:
    """The engine with the cell's buckets bound, warmed and wrapped, its
    payload pools, and the record of one window.

    Seeds: ``SeedSequence(seed)`` gives independent streams for the
    payload pools, the order requests take payloads in, the traffic and
    the sample checked, so one seed repeats the same run.
    """

    def __init__(self, cell: Cell, seed: int, *, t_start: float):
        from repro.launch.engine import ServeEngine
        self.cell, self.seed, self.t_start = cell, seed, t_start
        cfg, wl = cell.config, cell.workload
        eng = cfg["engine"]
        self.n = int(cfg["n"])
        self.max_batch = int(eng["max_batch"])
        self.engine = ServeEngine(
            max_batch=self.max_batch, max_pending=int(eng["max_pending"]),
            modulus_bits=eng.get("modulus_bits"),
            model_shards=int(eng.get("model_shards", 1)))
        pools_ss, order_ss, self.traffic_ss, sample_ss = \
            np.random.SeedSequence(seed).spawn(4)
        self.ops, self.pools = make_pools(cell, pools_ss)
        self._order_rng = np.random.default_rng(order_ss)
        self._order = {op: [] for op in self.ops}
        self.pool_size = int(cfg.get("pool", DEFAULT_POOL))
        self.traffic_mod = cell.module("traffic", wl["loop"])
        self.bounds = {op: self.engine.register(op, self.n)
                       for op in self.ops}
        for bound in self.bounds.values():
            for size in self.traffic_mod.batch_sizes(wl, self.max_batch):
                bound.warmup(size)
        sample_rng = random.Random(int(np.random.default_rng(sample_ss)
                                       .integers(1 << 62)))
        self.samples = {op: Reservoir(int(cfg["check_sample"]), sample_rng)
                        for op in self.ops}
        self.compiles = CompileLog()
        self._lock = threading.Lock()
        self._own_lock = threading.Lock()
        self.own_s = 0.0
        self._by_payload: dict[int, Request] = {}
        self._inflight: dict[int, tuple[Any, list[Request]]] = {}
        self._seq = 0
        self.completed = 0
        self.offered: list[Request] = []
        self.done: list[Request] = []
        self.stages: list[Stage] = []
        self.failed = 0
        self.errors: list[str] = []
        self.traffic = None
        self.t_open = self.t_close = self.deadline = math.nan
        for op, bound in self.bounds.items():
            self._wrap(op, bound)

    # -- the hooks on each bound op ----------------------------------------

    def _wrap(self, op: str, bound) -> None:
        import jax
        execute, to_numpy = bound.execute, bound.to_numpy

        def staged(payloads):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("chipbench.stage"):
                out = execute(payloads)
            t1 = time.perf_counter()
            reqs = [self._by_payload.pop(id(p)) for p in payloads]
            self._inflight[id(out)] = (out, reqs)
            self.stages.append(Stage(t0, t1, len(reqs), op))
            self._own(time.perf_counter() - t1)
            return out

        def fetched(out):
            with jax.profiler.TraceAnnotation("chipbench.fetch"):
                arr = to_numpy(out)
            t = time.perf_counter()
            resent_s = self._complete(op, self._inflight.pop(id(out))[1],
                                      arr, t)
            # the client has taken these results: keep the engine's result
            # store from growing over the window
            self.engine.results.clear()
            self._own(time.perf_counter() - t - resent_s)
            return arr

        bound.execute = staged
        bound.to_numpy = fetched

    def _complete(self, op: str, reqs: list[Request], arr, t: float
                  ) -> float:
        """Record a batch's completion; returns the seconds spent handing
        it to the traffic (whose resends count their own time)."""
        self.completed += len(reqs)
        for r in reqs:
            r.t_done = t
        if not math.isnan(self.t_close):
            return 0.0                              # drained after the close
        sample = self.samples[op]
        for j, r in enumerate(reqs):
            self.done.append(r)
            sample.offer(lambda j=j, r=r: (r.index, np.array(arr[j])))
        if t >= self.deadline:
            import jax
            with jax.profiler.TraceAnnotation("chipbench.close"):
                self.t_close = t
            self.engine.request_stop()
            return 0.0
        t0 = time.perf_counter()
        self.traffic.on_complete(reqs)
        return time.perf_counter() - t0

    def _own(self, seconds: float) -> None:
        """Add host time the benchmark's own bookkeeping took (request
        records, sample, span hooks), apart from the engine's calls."""
        with self._own_lock:
            self.own_s += seconds

    # -- the client side ----------------------------------------------------

    def _next_index(self, op: str) -> int:
        order = self._order[op]
        if not order:
            order.extend(self._order_rng.permutation(self.pool_size).tolist())
        return order.pop()

    def submit(self, op: str, t_due: float | None = None, *,
               block: bool = True) -> bool:
        """Offer one request of ``op``; False once the engine has stopped
        admitting (the window has closed) or the request failed."""
        from repro.launch.engine import EngineStopped
        t_in = time.perf_counter()
        if not math.isnan(self.t_close):
            return False
        with self._lock:
            index = self._next_index(op)
            seq = self._seq
            self._seq += 1
        payload = self.ops[op].request(self.pools[op], index)
        now = time.perf_counter()
        req = Request(seq, op, index, now if t_due is None else t_due,
                      payload)
        self._by_payload[id(payload)] = req
        t_engine = time.perf_counter()
        try:
            self.engine.submit(op, self.n, payload, block=block)
        except EngineStopped:
            del self._by_payload[id(payload)]
            return False
        except Exception as e:          # noqa: BLE001 - counted as failed
            del self._by_payload[id(payload)]
            self.failed += 1
            self.errors.append(f"{type(e).__name__}: {e}")
            return False
        t_back = time.perf_counter()
        req.t_sent = now
        self.offered.append(req)
        self._own(t_engine - t_in + time.perf_counter() - t_back)
        return True

    # -- the window ---------------------------------------------------------

    def window(self, seconds: float) -> None:
        """Open the window, send the cell's traffic, and serve until the
        first batch completion after ``seconds``; then stop admitting and
        drain. One window per session: the engine admits nothing after."""
        import jax
        rng = np.random.default_rng(self.traffic_ss.spawn(1)[0])
        self.traffic = self.traffic_mod.Traffic(
            self.cell.workload, self, rng, seconds + HORIZON_SPARE_S)
        self.own_s = 0.0
        with jax.profiler.TraceAnnotation("chipbench.open"):
            self.t_open = time.perf_counter()
        self.deadline = self.t_open + seconds
        self.traffic.start()
        try:
            self.engine.run(1 << 62)
        finally:
            self.traffic.stop()
        if self._by_payload or self._inflight:
            raise RuntimeError(f"{len(self._by_payload)} requests queued and "
                               f"{len(self._inflight)} batches in flight "
                               f"after the drain")

    def memory_peak_bytes(self) -> int | None:
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()[:self.cell.chips]]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None


@dataclasses.dataclass
class RunRecord:
    """What the metric readers read (``metrics/<name>.py``)."""
    t_start: float
    t_open: float
    t_close: float
    done: list[Request]
    stages: list[Stage]
    max_batch: int
    chips: int
    min_s_per_op: dict[str, float]      # least device seconds per request
    trace: dict | None = None

    def latencies_ms(self) -> list[float]:
        return [1e3 * (r.t_done - r.t_offer) for r in self.done]

    def stages_in_window(self) -> list[Stage]:
        return [s for s in self.stages if self.t_open <= s.t0 <= self.t_close]

    def min_device_s(self) -> float | None:
        """Least seconds per chip the completed requests need (None off the
        chip, where no peak prices them)."""
        if not self.min_s_per_op:
            return None
        return sum(self.min_s_per_op[r.op] for r in self.done) / self.chips


def compare_rows(config: dict, ops: dict, pools: dict,
                 rows: dict[str, list]) -> dict[str, dict]:
    """Compare result rows with the plain reference, per op:
    ``{<op>_<check>: {value, limit}}``. ``rows[op]`` holds (payload index,
    result row) pairs; an op with none reads as missing."""
    out = {}
    for op, mod in ops.items():
        items = rows.get(op, [])
        name = f"{op}_{mod.CHECK}"
        limit = float(config["limits"][name])
        if not items:
            out[name] = {"value": None, "limit": limit}
            continue
        idx = [i for i, _ in items]
        got = np.stack([row for _, row in items])
        want = mod.reference(pools[op], idx, config)
        out[name] = {"value": mod.compare(got, want), "limit": limit}
    return out


def check(session: Session) -> dict[str, dict]:
    """The window's sampled results against the plain reference."""
    return compare_rows(session.cell.config, session.ops, session.pools,
                        {op: s.items for op, s in session.samples.items()})


def verdict(checks: dict, failed: int) -> bool:
    """``correct``: every number compared within its limit, none failed."""
    return failed == 0 and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())


def device_info(cell: Cell, *, require_tpu: bool = True) -> dict:
    """The device JAX reports; without a TPU, with too few chips, or with a
    kind missing from the peak table the cell cannot run."""
    import jax
    from chipbench import work
    devices = jax.devices()
    dev = devices[0]
    if require_tpu:
        if dev.platform != "tpu":
            raise BenchError(f"JAX finds no TPU (platform {dev.platform!r})")
        if len(devices) < cell.chips:
            raise BenchError(f"cell {cell.name} needs {cell.chips} chips, "
                             f"JAX finds {len(devices)}")
    try:
        peaks = work.load_peaks(dev.device_kind)
    except work.UnknownDevice as e:
        if require_tpu:
            raise BenchError(str(e)) from None
        peaks = None            # a rehearsal off the chip prices nothing
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "peaks": peaks}


def measure(session: Session, seconds: float,
            trace_dir: pathlib.Path | None = None) -> None:
    """The window, under the profiler when ``trace_dir`` is given (Python
    tracing off: it would slow the host it measures)."""
    import jax
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        session.window(seconds)
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
        session.compiles.close()


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True) -> dict:
    """One run: set-up, window, memory, check, metrics. Returns the result
    line's object, ``checks`` last."""
    from chipbench import work
    dev = device_info(cell, require_tpu=require_tpu)
    session = Session(cell, seed, t_start=t_start)
    trace_dir = OUT_DIR / f"trace-{cell.name}-{seed}"
    measure(session, seconds, trace_dir if trace else None)
    memory = session.memory_peak_bytes()
    in_window = session.compiles.between(session.t_open, session.t_close)
    reduced = None
    if trace:
        from chipbench import trace as trace_mod
        devices, spans = trace_mod.read_events(
            trace_mod.find_xplane(trace_dir))
        reduced = trace_mod.reduce(devices, spans, cell.chips)
        shutil.rmtree(trace_dir, ignore_errors=True)
    bounds = {} if dev["peaks"] is None else {
        op: work.request_min_s(mod, session.n, mod.limbs(cell.config),
                               dev["peaks"])
        for op, mod in session.ops.items()}
    min_s = {op: b[0] for op, b in bounds.items()}
    record = RunRecord(t_start, session.t_open, session.t_close,
                       session.done, session.stages, session.max_batch,
                       cell.chips, min_s, reduced)
    units = cell.metric_units()
    metrics = {}
    for name in cell.metric_names(trace):
        value = cell.module("metrics", name).read(record)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    checks = check(session)
    correct = verdict(checks, session.failed)
    device = {k: dev[k] for k in ("platform", "kind", "count")}
    device["memory_peak_bytes"] = memory
    result = {"correct": correct, "attempted": len(session.done)
              + session.failed, "failed": session.failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    lateness = [r.t_sent - r.t_offer for r in session.offered]
    result["notes"] = {
        "compiles_in_window": in_window,
        "offered": len(session.offered),
        "completed_after_close": sum(1 for r in session.offered
                                     if r.t_done > session.t_close),
        "sender_late_ms_p95": (1e3 * float(np.percentile(lateness, 95))
                               if lateness else None),
        "bound_by": {op: b[1] for op, b in bounds.items()},
        # the benchmark's own host time per request (request records,
        # sample, span hooks), apart from the engine's calls
        "wrapper_us_per_request": (1e6 * session.own_s
                                   / len(session.offered)
                                   if session.offered else None),
        "errors": session.errors[:5],
    }
    result["checks"] = checks
    return result
