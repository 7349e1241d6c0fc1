"""Perf-trajectory ratchet over BENCH_fourier.json.

BENCH_fourier.json used to be a SNAPSHOT: every smoke run overwrote it and
the only protection was the absolute gates (cycle ratio <= 0.65, byte
ratio <= 0.6) — a 20% regression that stayed under an absolute gate landed
invisibly. This module turns the file into a TRAJECTORY:

* the previous run's file is committed at the repo root (the baseline);
* ``compare(prev, new)`` ratchets every DETERMINISTIC metric against it —
  closed-form PIM cycle ratios, throughput and interconnect-byte ratios
  may drift at most ``RATCHET_SLACK`` (2%) in the losing direction per
  run, independent of how much absolute-gate headroom remains;
* wall-clock metrics (interpreter timings, serve p50/p99) are recorded in
  the history but NOT ratcheted — shared CI runners make them noisy;
* ``extend_history`` appends one summary record per run, so the artifact
  carries the whole measured trajectory, not just the latest point.

``benchmarks/run.py --smoke`` compares BEFORE overwriting the file and
fails on a violation (the verdict is written into the artifact first, so a
failing run still uploads an honest file). CI re-checks independently:
``python -m benchmarks.trajectory --baseline-git HEAD`` diffs the fresh
file against the committed baseline.

Accepting a deliberate trade (e.g. a feature that costs 1% of cycle
ratio) is explicit: commit the new BENCH_fourier.json in the same PR —
the ratchet then measures from the new baseline. What it forbids is the
SILENT version of the same drift.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

RATCHET_SLACK = 0.02    # max losing-direction drift per run, deterministic
HISTORY_CAP = 100       # entries kept in the artifact's history list


def load(path: str) -> dict | None:
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def load_git(ref: str, path: str = "BENCH_fourier.json",
             cwd: str | None = None) -> dict | None:
    """The baseline as committed at ``ref`` (None if absent there)."""
    res = subprocess.run(["git", "show", f"{ref}:{path}"],
                         capture_output=True, text=True, cwd=cwd)
    if res.returncode != 0:
        return None
    return json.loads(res.stdout)


def deterministic_metrics(bench: dict) -> dict[str, tuple[float, str]]:
    """name -> (value, direction): every closed-form metric the ratchet
    guards. direction 'min' = lower is better (ratios), 'max' = higher is
    better (throughput). Wall-clock numbers are deliberately absent."""
    out: dict[str, tuple[float, str]] = {}
    for n, v in (bench.get("real_complex_cycle_ratio") or {}).items():
        out[f"real_complex_cycle_ratio/n={n}"] = (float(v), "min")
    for op, v in (bench.get("dist_real_complex_byte_ratio") or {}).items():
        out[f"dist_real_complex_byte_ratio/{op}"] = (float(v), "min")
    for key, v in (bench.get("abft_overhead_ratio") or {}).items():
        # simulated ABFT check cycles / verified transform cycles: a rise
        # means integrity got more expensive relative to the work it guards
        out[f"abft_overhead_ratio/{key}"] = (float(v), "min")
    sa = bench.get("static_analysis") or {}
    if "rule_count" in sa:
        # active invariant-linter rules (repro.analysis): rules may be
        # added but never silently dropped — with 2% slack, losing even
        # one rule from a set of <= 50 trips the ratchet.
        out["static_analysis_rule_count"] = (float(sa["rule_count"]),
                                             "max")
    for rec in bench.get("records", []):
        op = rec.get("op")
        # closed-form PIM model outputs: deterministic per commit
        if op in ("polymul", "polymul-real", "rfft") \
                and "throughput_per_s" in rec:
            out[f"pim_throughput/{op}/n={rec['n']}"] = (
                float(rec["throughput_per_s"]), "max")
        if op in ("polymul", "polymul-real") and "pim_cycles" in rec:
            out[f"pim_cycles/{op}/n={rec['n']}"] = (
                float(rec["pim_cycles"]), "min")
    return out


def compare(prev: dict, new: dict,
            slack: float = RATCHET_SLACK) -> list[str]:
    """Ratchet violations of ``new`` against the ``prev`` baseline.

    A metric present in prev but missing from new is itself a violation
    (dropping a measurement is how regressions hide); new metrics with no
    baseline pass freely and enter the ratchet on the next commit.
    """
    prev_m = deterministic_metrics(prev)
    new_m = deterministic_metrics(new)
    violations = []
    for name, (pv, direction) in sorted(prev_m.items()):
        if name not in new_m:
            violations.append(f"{name}: measured in baseline ({pv:.6g}) "
                              f"but missing from this run")
            continue
        nv, _ = new_m[name]
        if direction == "min":
            bound = pv * (1.0 + slack)
            if nv > bound:
                violations.append(
                    f"{name}: {nv:.6g} > ratchet {bound:.6g} "
                    f"(baseline {pv:.6g}, slack {slack:.0%})")
        else:
            bound = pv * (1.0 - slack)
            if nv < bound:
                violations.append(
                    f"{name}: {nv:.6g} < ratchet {bound:.6g} "
                    f"(baseline {pv:.6g}, slack {slack:.0%})")
    return violations


def history_entry(bench: dict) -> dict:
    """One per-run trajectory record: the deterministic metrics plus the
    (noisy, informational) serve latencies and gate verdicts."""
    serve = bench.get("serve", {})
    return {
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "metrics": {k: v for k, (v, _) in
                    deterministic_metrics(bench).items()},
        "serve_ms": {k: serve.get(k) for k in ("p50_ms", "p99_ms")},
        "gate_pass": bench.get("gate", {}).get("pass"),
    }


def extend_history(prev: dict | None, new: dict) -> list[dict]:
    """prev's history + one entry for the new run (bounded length)."""
    hist = list((prev or {}).get("history", []))
    hist.append(history_entry(new))
    return hist[-HISTORY_CAP:]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="ratchet-check a fresh BENCH_fourier.json against the "
                    "committed baseline")
    ap.add_argument("--current", default="BENCH_fourier.json")
    ap.add_argument("--baseline", default=None,
                    help="baseline file path (default: --baseline-git)")
    ap.add_argument("--baseline-git", default="HEAD", metavar="REF",
                    help="read the baseline from this git ref "
                         "(default HEAD)")
    ap.add_argument("--slack", type=float, default=RATCHET_SLACK)
    args = ap.parse_args(argv)
    new = load(args.current)
    if new is None:
        print(f"[trajectory] FAIL: {args.current} does not exist "
              f"(run benchmarks/run.py --smoke first)")
        return 1
    prev = load(args.baseline) if args.baseline \
        else load_git(args.baseline_git, args.current)
    if prev is None:
        print("[trajectory] no committed baseline — nothing to ratchet "
              "(first run passes; commit the artifact to arm the ratchet)")
        return 0
    violations = compare(prev, new, slack=args.slack)
    n_hist = len(new.get("history", []))
    if violations:
        print(f"[trajectory] RATCHET VIOLATION "
              f"({len(violations)} metric(s), history={n_hist}):")
        for v in violations:
            print(f"  - {v}")
        print("  (a deliberate trade must commit the new "
              "BENCH_fourier.json in the same PR)")
        return 1
    print(f"[trajectory] ok: {len(deterministic_metrics(new))} "
          f"deterministic metrics within {args.slack:.0%} of the "
          f"committed baseline (history={n_hist})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
