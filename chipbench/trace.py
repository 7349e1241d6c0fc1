"""Reduce a profiler trace of one window to device busy time, the
operations that took it, and the idle gaps with what the host was doing.

The JAX profiler writes one ``.xplane.pb`` under
``<dir>/plugins/profile/<time>/``; ``jax.profiler.ProfileData`` reads it.
A device is a plane named ``/device:TPU:<id>``, and its operations are the
events of that plane's ``XLA Ops`` line. The window runs from the start of
the host event ``chipbench.open`` to the end of ``chipbench.close``; the
benchmark's other host spans (``chipbench.*``) label the gaps.
"""
from __future__ import annotations

import pathlib

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "chipbench."
OPEN, CLOSE = "chipbench.open", "chipbench.close"
TOP = 10


def find_xplane(trace_dir: pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(found)}")
    return found[0]


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The complement of sorted disjoint ``busy`` within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(spans, t: float) -> str:
    """The innermost host span around time t, else ``host:other``."""
    around = [(e - s, name) for s, e, name in spans if s <= t <= e]
    return min(around)[1] if around else "host:other"


def read_events(path: pathlib.Path):
    """(device id -> [(start_ns, end_ns, op name)], host spans
    [(start_ns, end_ns, name)]) from one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices: dict[int, list] = {}
    spans: list = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ident = plane.name[len(DEVICE_PREFIX):]
            if not ident.isdigit():
                continue
            ops = devices.setdefault(int(ident), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name) for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                              ev.name) for ev in line.events
                             if ev.name.startswith(HOST_PREFIX))
    return devices, spans


def reduce(devices: dict, spans: list, chips: int) -> dict:
    """Busy and window seconds (busy averaged over devices 0..chips-1), the
    operations that took most device time (seconds per chip, by the HLO
    name before ' = '), and the longest idle gaps of device 0 labelled by
    the host span around them."""
    opens = [s for s, _, name in spans if name == OPEN]
    closes = [e for _, e, name in spans if name == CLOSE]
    if not opens or not closes:
        raise ValueError("the trace holds no chipbench.open/close window")
    lo, hi = min(opens), max(closes)
    busy_ns, op_ns = [], {}
    for dev in range(chips):
        events = devices.get(dev, [])
        for s, e, name in events:
            overlap = min(e, hi) - max(s, lo)
            if overlap > 0:
                op = name.split(" = ", 1)[0]        # HLO text: '%op = ...'
                op_ns[op] = op_ns.get(op, 0.0) + overlap
        busy = clip(union((s, e) for s, e, _ in events), lo, hi)
        busy_ns.append(sum(e - s for s, e in busy))
    dev0 = clip(union((s, e) for s, e, _ in devices.get(0, [])), lo, hi)
    idle = sorted(gaps(dev0, lo, hi), key=lambda g: g[0] - g[1])[:TOP]
    host = [sp for sp in spans if sp[2] not in (OPEN, CLOSE)]
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[name, ns / chips / 1e9] for name, ns in top_ops],
        "idle_gaps": [[label(host, (s + e) / 2), (e - s) / 1e9]
                      for s, e in idle],
        "device_events": sum(len(devices.get(d, [])) for d in range(chips)),
    }
