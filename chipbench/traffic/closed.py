"""Closed loop: a fixed number of requests outstanding.

The workload's ``outstanding`` requests are split over its ops in the
proportions of ``ops``; each op's callers send that op again the moment a
result comes back. The resend happens in the completion callback, before
the engine forms its next batch, so every bucket always holds at least one
full batch and every dispatch runs at ``max_batch``: the only shape warmed.
A request is offered at the call to ``submit``.
"""
from __future__ import annotations


def batch_sizes(workload: dict, max_batch: int) -> list[int]:
    return [max_batch]


class Traffic:
    def __init__(self, workload: dict, client, rng, horizon_s: float):
        weights = workload["ops"]
        total = workload["outstanding"]
        unit = total // sum(weights.values())
        self.counts = {op: w * unit for op, w in weights.items()}
        if sum(self.counts.values()) != total:
            raise ValueError(f"outstanding={total} does not split over the "
                             f"op weights {weights}")
        low = [op for op, k in self.counts.items()
               if k < 2 * client.max_batch]
        if low:
            raise ValueError(f"ops {low} keep fewer than two full batches "
                             f"outstanding: tail batches would form")
        self.client = client

    def start(self) -> None:
        for op, k in self.counts.items():
            for _ in range(k):
                self.client.submit(op)

    def on_complete(self, reqs) -> None:
        for r in reqs:
            self.client.submit(r.op, block=False)

    def stop(self) -> None:
        pass
