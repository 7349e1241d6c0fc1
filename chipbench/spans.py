"""The program's own spans and counters over one window of a cell.

    python chipbench/spans.py --workload <cell> --seed <n> --seconds <s> --trace <0|1> [--out <file>]

The engine records spans and counters of its own (``repro.telemetry``,
``ServeEngine.telemetry``: the ``serve.*`` and ``rns.*`` spans and the
``queue_wait`` counter). This tool runs one window of a cell as ``run.py``
does, with the same ``harness.Session`` and ``harness.measure``, reads the
engine's recorder as the window opens and again where the window closes
(the ``request_stop`` the benchmark calls there), and prints one JSON
object:

- ``recorded``: the difference of the two readings, ``{"spans": {name:
  {count, total_s, self_s}}, "counters": {name: {count, total_s}}}``;
- ``quantities``: the per-layer numbers ``QUANTITIES`` reads from it;
- ``metrics``: the benchmark's own readers of the same window
  (``throughput``, ``stage_ms_per_batch``, traced ``device_idle_share``),
  and ``stages``, the batches its span around ``execute`` saw;
- traced: ``idle_by_span``, device 0's idle seconds in the window by the
  innermost program span around them, and ``trace_bytes``.

``--out`` keeps the trace file (``tests/data/paper-spans.xplane.pb.gz`` is
one, gzipped). The benchmark's run line carries none of this: its harness
takes no reading of the engine's recorder.
"""
from __future__ import annotations

import argparse
import heapq
import json
import pathlib
import shutil
import sys
import time

T_START = time.perf_counter()
HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, trace  # noqa: E402

PROGRAM_PREFIXES = ("serve.", "rns.")
OTHER = "host:other"
REST = "(rest)"

# quantity -> (kind, name, part, scale): mean ``part`` per event of the
# span or counter ``name`` in the window, times ``scale``
QUANTITIES = {
    # ``ServeEngine.submit``'s own work per request (registry lookup, the
    # finite check, the queue lock, the append), its wait for queue space
    # (``serve.backpressure``) left out; microseconds
    "admit_us_per_request": ("spans", "serve.admit", "self_s", 1e6),
    # admission to the pop that puts a request in a batch, per request; ms
    "queue_wait_ms": ("counters", "queue_wait", "total_s", 1e3),
    # the ``jnp.asarray`` calls of a stacked batch's operands; the call may
    # return before the copy ends (the RNS route has no such span); ms
    "h2d_ms_per_batch": ("spans", "serve.h2d", "total_s", 1e3),
    # ``np.asarray`` of a batch's result in ``BoundOp.to_numpy``; ms
    "d2h_ms_per_batch": ("spans", "serve.d2h", "total_s", 1e3),
    # ``to_rns`` of both operands in ``rns_polymul``; ms
    "rns_split_ms_per_batch": ("spans", "rns.split", "total_s", 1e3),
    # ``crt_to_modulus`` in ``rns_polymul``; ms
    "crt_ms_per_batch": ("spans", "rns.crt", "total_s", 1e3),
}


def delta(before: dict | None, after: dict | None) -> dict | None:
    """Two ``Recorder.snapshot()`` readings -> what happened between them,
    names with no event between left out; None without both readings."""
    if before is None or after is None:
        return None
    out = {}
    for kind, keys in (("spans", ("count", "total_s", "self_s")),
                       ("counters", ("count", "total_s"))):
        out[kind] = {}
        for name, now in after.get(kind, {}).items():
            then = before.get(kind, {}).get(name, (0,) * len(keys))
            if now[0] > then[0]:
                out[kind][name] = {k: a - b
                                   for k, a, b in zip(keys, now, then)}
    return out


def quantities(recorded: dict | None) -> dict[str, float | None]:
    """Each of ``QUANTITIES`` from a ``delta``; None where nothing of it
    was recorded."""
    out = {}
    for name, (kind, key, part, scale) in QUANTITIES.items():
        agg = (recorded or {}).get(kind, {}).get(key)
        out[name] = scale * agg[part] / agg["count"] if agg else None
    return out


def read_all(path: pathlib.Path):
    """``trace.read_events``'s two results, then the program's spans
    [(start_ns, end_ns, name)] (``PROGRAM_PREFIXES``), from one parse."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices: dict[int, list] = {}
    spans: list = []
    program: list = []
    for plane in pd.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            ident = plane.name[len(trace.DEVICE_PREFIX):]
            if not ident.isdigit():
                continue
            ops = devices.setdefault(int(ident), [])
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name) for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(trace.HOST_PREFIX):
                        into = spans
                    elif ev.name.startswith(PROGRAM_PREFIXES):
                        into = program
                    else:
                        continue
                    into.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name))
    return devices, spans, program


def window(spans) -> tuple[float, float]:
    """(start of ``chipbench.open``, end of ``chipbench.close``) in ns."""
    opens = [s for s, _, name in spans if name == trace.OPEN]
    closes = [e for _, e, name in spans if name == trace.CLOSE]
    if not opens or not closes:
        raise ValueError("the trace holds no chipbench.open/close window")
    return min(opens), max(closes)


def innermost(program, lo: float, hi: float) -> list[tuple]:
    """Disjoint (start, end, name) segments that cover [lo, hi], each named
    by the shortest program span around it (the innermost, where spans
    nest), else ``host:other``."""
    events = []
    for i, (s, e, _) in enumerate(program):
        s, e = max(s, lo), min(e, hi)
        if e > s:
            events.append((s, 1, i))
            events.append((e, 0, i))      # ends sort before starts at a tie
    events.sort()
    open_spans: list = []                 # heap of (duration, -start, i)
    ended: set[int] = set()
    out: list[list] = []
    t = lo
    for at, starts, i in events:
        if at > t:
            while open_spans and open_spans[0][2] in ended:
                ended.discard(heapq.heappop(open_spans)[2])
            name = program[open_spans[0][2]][2] if open_spans else OTHER
            if out and out[-1][2] == name and out[-1][1] == t:
                out[-1][1] = at
            else:
                out.append([t, at, name])
            t = at
        if starts:
            s, e, _ = program[i]
            heapq.heappush(open_spans, (e - s, -s, i))
        else:
            ended.add(i)
    if hi > t:
        out.append([t, hi, OTHER])
    return [tuple(seg) for seg in out]


def idle_by_span(devices: dict, spans: list, program: list,
                 top: int = trace.TOP) -> dict[str, float]:
    """Device 0's idle seconds in the window, divided among the innermost
    program span at each instant, else ``host:other``: the ``top`` names
    with most, largest first, then ``(rest)`` for the others."""
    lo, hi = window(spans)
    dev0 = trace.clip(trace.union((s, e) for s, e, _ in devices.get(0, [])),
                      lo, hi)
    segments = innermost(program, lo, hi)
    ns: dict[str, float] = {}
    j = 0
    for a, b in trace.gaps(dev0, lo, hi):
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            s, e, name = segments[k]
            overlap = min(e, b) - max(s, a)
            if overlap > 0:
                ns[name] = ns.get(name, 0.0) + overlap
            k += 1
    ranked = sorted(ns.items(), key=lambda kv: -kv[1])
    out = {name: v / 1e9 for name, v in ranked[:top]}
    rest = sum(v for _, v in ranked[top:])
    if rest:
        out[REST] = rest / 1e9
    return out


def recorder(engine):
    """The engine's own recorder; None on an engine without one."""
    return getattr(engine, "telemetry", None)


def run_window(cell: harness.Cell, *, seed: int, seconds: float,
               trace_on: bool, t_start: float, out: pathlib.Path | None = None,
               require_tpu: bool = True) -> dict:
    """One window of ``cell`` with the engine's recorder read at its open
    and close; returns the object the tool prints."""
    harness.device_info(cell, require_tpu=require_tpu)
    session = harness.Session(cell, seed, t_start=t_start)
    rec = recorder(session.engine)
    readings = []

    def read() -> None:
        readings.append(None if rec is None else rec.snapshot())

    request_stop = session.engine.request_stop

    def stop_at_close() -> None:
        if len(readings) == 1:
            read()
        request_stop()

    session.engine.request_stop = stop_at_close
    trace_dir = harness.OUT_DIR / f"spans-{cell.name}-{seed}"
    read()
    harness.measure(session, seconds, trace_dir if trace_on else None)
    reduced = None
    result: dict = {"workload": cell.name, "seed": seed}
    if trace_on:
        xplane = trace.find_xplane(trace_dir)
        result["trace_bytes"] = xplane.stat().st_size
        devices, spans, program = read_all(xplane)
        reduced = trace.reduce(devices, spans, cell.chips)
        result["idle_by_span"] = idle_by_span(devices, spans, program)
        if out is not None:
            out.parent.mkdir(parents=True, exist_ok=True)
            shutil.copyfile(xplane, out)
        shutil.rmtree(trace_dir, ignore_errors=True)
    record = harness.RunRecord(t_start, session.t_open, session.t_close,
                               session.done, session.stages,
                               session.max_batch, cell.chips, {}, reduced)
    names = ["throughput", "stage_ms_per_batch"]
    if trace_on:
        names.append("device_idle_share")
    result["metrics"] = {name: cell.module("metrics", name).read(record)
                         for name in names}
    result["stages"] = len(record.stages_in_window())
    recorded = delta(*readings) if len(readings) == 2 else None
    result["quantities"] = quantities(recorded)
    result["recorded"] = recorded
    result["correct"] = harness.verdict(harness.check(session),
                                        session.failed)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", type=pathlib.Path)
    args = ap.parse_args(argv)
    harness.setup_process()
    try:
        cell = harness.Cell.load(args.workload)
        result = run_window(cell, seed=args.seed, seconds=args.seconds,
                            trace_on=bool(args.trace), t_start=T_START,
                            out=args.out)
    except harness.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
