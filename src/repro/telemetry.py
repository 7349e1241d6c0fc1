"""Host spans and counters, kept in memory and on the profiler's clock.

``span(name)`` times a block of host code two ways at once:

  * it enters ``jax.profiler.TraceAnnotation(name)``, so a profiled run
    shows the span in the profiler's host plane, on the same clock as the
    device's ``XLA Ops`` (nothing is recorded while no profiler runs);
  * it adds the block's ``perf_counter`` duration to a :class:`Recorder`'s
    aggregates ``{name: count, total_s, self_s}``. Self time is the
    duration less the time its child spans on the same thread cover.

A recorder reaches code that does not know its owner (``BoundOp``, the
RNS route in ``core/``) by being made current on a thread:
``with recorder.current(): ...``; ``span`` records into the recorder
current on the calling thread, or only annotates when there is none. A
component that owns a recorder, such as ``ServeEngine``, calls
``recorder.span`` directly. Counters (``Recorder.count``) add up waits
that no single block of code covers, such as a request's time in a queue,
and events, such as the batches a route ran; ``count`` adds to the
recorder current on the calling thread.

Importing this module does not import jax; the first span does.
"""
from __future__ import annotations

import contextlib
import threading
from time import perf_counter
from typing import Iterator

# per thread: ``recorder`` (the current Recorder) and ``stack`` (seconds
# covered by the children of each open span, innermost last)
_local = threading.local()
_annotation = None


def _trace_annotation(name: str):
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation(name)


def _stack() -> list[float]:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class Span:
    """One timed block: a context manager made by ``span`` or
    ``Recorder.span``."""
    __slots__ = ("name", "recorder", "_annotation", "_t0")

    def __init__(self, name: str, recorder: "Recorder | None"):
        self.name = name
        self.recorder = recorder

    def __enter__(self) -> "Span":
        self._annotation = _trace_annotation(self.name)
        self._annotation.__enter__()
        _stack().append(0.0)
        self._t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        seconds = perf_counter() - self._t0
        stack = _local.stack
        children = stack.pop()
        if stack:
            stack[-1] += seconds
        if self.recorder is not None:
            self.recorder._add(self.name, seconds, seconds - children)
        self._annotation.__exit__(*exc)


class Recorder:
    """Aggregates of spans and counters for one owner; thread-safe."""

    def __init__(self):
        self._lock = threading.Lock()
        self._spans: dict[str, list] = {}       # name -> [count, total, self]
        self._counters: dict[str, list] = {}    # name -> [count, total]

    def span(self, name: str) -> Span:
        return Span(name, self)

    def _add(self, name: str, seconds: float, self_s: float) -> None:
        with self._lock:
            agg = self._spans.get(name)
            if agg is None:
                self._spans[name] = [1, seconds, self_s]
            else:
                agg[0] += 1
                agg[1] += seconds
                agg[2] += self_s

    def count(self, name: str, seconds: float, n: int = 1) -> None:
        """Add ``n`` events that took ``seconds`` in all to counter
        ``name``."""
        with self._lock:
            agg = self._counters.get(name)
            if agg is None:
                self._counters[name] = [n, seconds]
            else:
                agg[0] += n
                agg[1] += seconds

    @contextlib.contextmanager
    def current(self) -> Iterator["Recorder"]:
        """Make this the recorder ``span`` records into on this thread."""
        prev = getattr(_local, "recorder", None)
        _local.recorder = self
        try:
            yield self
        finally:
            _local.recorder = prev

    def snapshot(self) -> dict:
        """``{"spans": {name: (count, total_s, self_s)}, "counters":
        {name: (count, total_s)}}`` as they stand."""
        with self._lock:
            return {"spans": {k: tuple(v) for k, v in self._spans.items()},
                    "counters": {k: tuple(v)
                                 for k, v in self._counters.items()}}


def span(name: str) -> Span:
    """A span recorded into the recorder current on this thread, if any."""
    return Span(name, getattr(_local, "recorder", None))


def count(name: str, seconds: float = 0.0, n: int = 1) -> None:
    """Add ``n`` events to counter ``name`` of the recorder current on
    this thread, if any."""
    recorder = getattr(_local, "recorder", None)
    if recorder is not None:
        recorder.count(name, seconds, n)
