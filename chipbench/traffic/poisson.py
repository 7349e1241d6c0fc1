"""Open loop: Poisson arrivals at a fixed rate, sent on schedule.

``rate_per_s`` arrivals per second, each request's op drawn in the
proportions of ``ops``. Every seed gets the same multiset of gaps (the
stratified quantiles of the exponential distribution) and the same op
counts, in another order, so the seed changes the order of the work and
not its amount. One thread sends; a request is due at its scheduled time,
and its latency counts from then, however late the sender ran.
"""
from __future__ import annotations

import math
import threading
import time

import numpy as np


def batch_sizes(workload: dict, max_batch: int) -> list[int]:
    return list(range(1, max_batch + 1))


def schedule(workload: dict, rng: np.random.Generator, count: int
             ) -> tuple[np.ndarray, list[str]]:
    """(gaps in seconds, ops) of ``count`` arrivals."""
    rate = float(workload["rate_per_s"])
    q = (np.arange(count) + 0.5) / count
    gaps = rng.permutation(-np.log1p(-q) / rate)
    weights = workload["ops"]
    total = sum(weights.values())
    ops = [op for op, w in weights.items()
           for _ in range(math.ceil(count * w / total))][:count]
    order = rng.permutation(len(ops))
    return gaps, [ops[i] for i in order]


class Traffic:
    def __init__(self, workload: dict, client, rng, horizon_s: float):
        count = max(1, math.ceil(float(workload["rate_per_s"]) * horizon_s))
        self.gaps, self.ops = schedule(workload, rng, count)
        self.count = count
        self.client = client
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._send, daemon=True,
                                        name="chipbench-poisson")

    def _send(self) -> None:
        due = self.client.t_open
        for gap, op in zip(self.gaps, self.ops):
            due += gap
            wait = due - time.perf_counter()
            if wait > 0 and self._stop.wait(wait):
                return
            if not self.client.submit(op, t_due=due):
                return

    def start(self) -> None:
        self._thread.start()

    def on_complete(self, reqs) -> None:
        pass

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=60)
        if self._thread.is_alive():
            raise RuntimeError("the Poisson sender did not stop")
