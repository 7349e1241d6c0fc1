"""Host cost of staging a batch: a fresh ``np.stack`` array or a reused slot.

    python benchmarks/stage_slot_bench.py [--runs 20] [--seed 0]

``BoundOp.stack`` writes each batch of a numeric route into a slot of a
two-slot ring the bound op owns (``launch/ops.py`` ``_SlotRing``): arrays
allocated and written once, so no page of them is first touched inside a
batch. It takes the slot it took last where the batch that read it is
done, else the other one. Before, every batch went into a fresh array,
and what that costs depends on glibc's allocator. An array of 32 MiB or
more is always mapped anew from the kernel and unmapped on free, so every
batch pays a page fault per 4 KiB. A smaller one is mapped anew until
glibc's thresholds have risen past its size; after that it comes from the
heap, but the heap's top is handed back to the kernel whenever it grows
past the trim threshold, which two 4 MiB operands do when the thresholds
rose no further than 4 MiB. Which of these holds follows the process's
earlier allocations.

This times the three ways with the program's own code
(``_stack_operands`` into fresh arrays; ``BoundOp._stage`` into a slot)
on payloads drawn as the routes draw them:

- ``fresh``: a fresh array per operand;
- ``slot``: the slot taken last, its batch done (paper's engine loop,
  where the two buckets take turns);
- ``slot_alt``: the other slot each time, the last one's batch still in
  flight (gw's loop: one bucket, one batch in flight);

at three shapes:

- ``gw``: polymul-real, n = 2^19, 16 rows, two float32 operands of 32 MiB;
- ``paper-fft``: fft, n = 2^14, 64 rows, one complex64 operand of 8 MiB;
- ``paper-polymul-real``: polymul-real, n = 2^14, 64 rows, two float32
  operands of 4 MiB.

Each way runs ``--runs`` times in a row after one warm-up, so one way's
writes do not evict another's pages from the caches between its runs;
then a batch of each slot way is checked equal to a fresh one. It runs
on the host alone and sends nothing to a device; it is a pre-check of
the staging step, not a yardstick. The last line of output is one JSON
object with every run.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro.launch import ops as op_registry  # noqa: E402

SHAPES = {
    "gw": ("polymul-real", 1 << 19, 16),
    "paper-fft": ("fft", 1 << 14, 64),
    "paper-polymul-real": ("polymul-real", 1 << 14, 64),
}
WAYS = ("fresh", "slot", "slot_alt")


class _InFlight:
    """A reader never done: the slot it reads stays busy, as the slot of
    a batch in flight does, so the next batch takes the other slot."""

    def is_ready(self) -> bool:
        return False

    def block_until_ready(self):
        return self


def _ms(fn):
    t0 = time.perf_counter()
    out = fn()
    return (time.perf_counter() - t0) * 1e3, out


def run_shape(op: str, n: int, batch: int, runs: int,
              rng: np.random.Generator) -> dict:
    spec = op_registry.get_op(op)
    fresh_op, slot_op, alt_op = (spec.bind(n) for _ in WAYS)
    payloads = [fresh_op.random_payload(rng) for _ in range(batch)]
    nbytes = batch * spec.arity * n * np.dtype(fresh_op.payload_dtype
                                               ).itemsize

    def alt():
        cols = alt_op._stage(payloads)
        alt_op._slots.read_by(_InFlight())
        return cols

    ways = {"fresh": lambda: op_registry._stack_operands(fresh_op, payloads),
            "slot": lambda: slot_op._stage(payloads),
            "slot_alt": alt}
    times = {}
    for way, fn in ways.items():
        fn()
        times[way] = []
        for _ in range(runs):
            t, got = _ms(fn)
            times[way].append(t)
            del got
    want = ways["fresh"]()
    equal = all(np.array_equal(g, w) for way in WAYS[1:]
                for g, w in zip(ways[way](), want))
    med = {k: statistics.median(v) for k, v in times.items()}
    return {"op": op, "n": n, "batch": batch, "bytes": nbytes,
            "equal": equal, "ms": times, "median_ms": med,
            "median_gb_per_s": {k: nbytes / v / 1e6 for k, v in med.items()}}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--runs", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    rng = np.random.default_rng(args.seed)
    shapes = {}
    for name, (op, n, batch) in SHAPES.items():
        r = shapes[name] = run_shape(op, n, batch, args.runs, rng)
        times = "  ".join(f"{way} {r['median_ms'][way]:7.2f} ms"
                          for way in WAYS)
        print(f"{name:20s} {r['bytes'] / 2**20:3.0f} MiB  {times}  "
              f"equal {r['equal']}", flush=True)
    summary = {"runs": args.runs, "seed": args.seed, "shapes": shapes}
    print(json.dumps(summary))
    if not all(r["equal"] for r in shapes.values()):
        raise SystemExit("a slot batch differs from its fresh np.stack")
    return summary


if __name__ == "__main__":
    main()
