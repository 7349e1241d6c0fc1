"""Invariant-linter tier (repro.analysis, docs/static_analysis.md).

Three layers:
  * fixture pairs — for EVERY registered rule, one snippet it MUST flag
    and one near-miss it MUST pass; the meta-test makes the pairing a
    closed loop (a rule without fixtures cannot be registered, a fixture
    without a rule is dead weight) and requires each rule's docstring to
    name the PR/bug it encodes;
  * engine mechanics — suppression placement (same line / comment-only
    line above, nothing further), mandatory reasons, unused-noqa,
    non-suppressible meta rules, reporters, CLI exit codes (0/1/2);
  * the repo gate itself — ``analyze_paths(src tests benchmarks)`` must
    be clean with every suppression carrying a reason: the same
    assertion CI's static-analysis job enforces, pinned here so a plain
    ``pytest`` run catches a violation before push.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from repro import analysis

ROOT = Path(__file__).resolve().parent.parent

# ---------------------------------------------------------------------------
# Fixture pairs: rule id -> {flag: (source, path), ok: (source, path)}
# Paths are virtual — placement rules match on suffix, so fixtures can
# claim to live anywhere in the tree.
# ---------------------------------------------------------------------------

_P = "src/repro/somepkg/snippet.py"
_P_FT = "src/repro/ft/snippet.py"

FIXTURES: dict[str, dict[str, tuple[str, str]]] = {
    "tracer-leak": {
        "flag": ("""\
import functools
import jax.numpy as jnp


@functools.lru_cache(maxsize=8)
def tables(n):
    return jnp.arange(n)
""", _P),
        "ok": ("""\
import functools
import numpy as np


@functools.lru_cache(maxsize=8)
def tables(n):
    return np.arange(n)
""", _P),
    },
    "fp32-phase": {
        "flag": ("""\
import numpy as np


def twiddles(n):
    return np.exp(2j * np.pi * np.arange(n).astype(np.float32) / n)
""", _P),
        # f64 trig, rounded ONCE after — the PR-5 fix shape
        "ok": ("""\
import numpy as np


def twiddles(n):
    return np.exp(2j * np.pi * np.arange(n) / n).astype(np.complex64)
""", _P),
    },
    "mutable-default": {
        "flag": ("""\
def make_watchdog(cfg=WatchdogConfig()):
    return StepWatchdog(cfg)
""", _P),
        # None sentinel — the PR-7 fix shape; frozen non-Config dataclass
        # defaults stay legal (launch/ops.py OpContext)
        "ok": ("""\
def make_watchdog(cfg=None):
    return StepWatchdog(WatchdogConfig() if cfg is None else cfg)


def bind(n, ctx=OpContext()):
    return ctx
""", _P),
    },
    "raw-collective": {
        "flag": ("""\
import jax


def reduce_grads(x):
    return jax.lax.psum(x, "data")
""", _P),
        "ok": ("""\
from repro.dist import collectives


def reduce_grads(x):
    return collectives.psum(x, "data")
""", _P),
    },
    "dispatch-ladder": {
        # renamed variable: the old "elif op ==" string grep missed this
        "flag": ("""\
def dispatch(o, x):
    if o == "fft":
        return run_fft(x)
    elif o == "polymul":
        return run_polymul(x)
    raise ValueError(o)
""", _P),
        # single op comparison + registry hand-off: not a ladder
        "ok": ("""\
def dispatch(o, x):
    if o == "fft":
        return run_fft(x)
    return registry.bind(o).fn(x)
""", _P),
    },
    "signal-lock": {
        "flag": ("""\
import signal


def install(engine):
    def _on_term(signum, frame):
        engine.request_stop()
    signal.signal(signal.SIGTERM, _on_term)
""", _P),
        # thread hand-off, locky call inside a NESTED def that runs on
        # the spawned thread — the PR-7 fix shape
        "ok": ("""\
import signal
import threading


def install(engine):
    def _on_term(signum, frame):
        def _stop():
            engine.request_stop()
        threading.Thread(target=_stop, daemon=True).start()
    signal.signal(signal.SIGTERM, _on_term)
""", _P),
    },
    "durable-write": {
        "flag": ("""\
import json


def write_state(path, state):
    with open(path, "w") as f:
        json.dump(state, f)
""", _P_FT),
        # reads are fine; and the same raw write OUTSIDE ft/ is out of
        # scope for this rule (checked via the flag snippet's path)
        "ok": ("""\
def read_state(path):
    with open(path) as f:
        return f.read()
""", _P_FT),
    },
    "bare-plan-literal": {
        "flag": ("""\
def forced_plan():
    return FFTPlan(tier="distributed", block_b=1, seq_shards=2)
""", _P),
        "ok": ("""\
from repro.core.fft.planner import plan


def forced_plan(n):
    return plan(n, 8, force_distributed=True)
""", _P),
    },
    "noqa-reason": {
        "flag": ("""\
import jax


def reduce_grads(x):
    return jax.lax.psum(x, "data")  # repro: noqa[raw-collective]
""", _P),
        "ok": ("""\
import jax


def reduce_grads(x):
    return jax.lax.psum(x, "data")  # repro: noqa[raw-collective]: fixture exercising the raw call
""", _P),
    },
    "unused-noqa": {
        "flag": ("""\
def clean():
    return 1  # repro: noqa[raw-collective]: nothing here needs excusing
""", _P),
        "ok": ("""\
import jax


def reduce_grads(x):
    return jax.lax.psum(x, "data")  # repro: noqa[raw-collective]: fixture exercising the raw call
""", _P),
    },
}


@pytest.mark.parametrize("rule_id", analysis.RULE_IDS)
def test_rule_fixture_pair(rule_id):
    """Each rule flags its must-flag snippet (message naming the
    historical PR) and stays silent on its near-miss."""
    flag_src, flag_path = FIXTURES[rule_id]["flag"]
    res = analysis.analyze_source(flag_src, flag_path)
    hits = [f for f in res.findings if f.rule == rule_id]
    assert hits, (f"rule {rule_id} missed its must-flag fixture; got "
                  f"{[f.format() for f in res.findings]}")
    assert re.search(r"PR \d|noqa", hits[0].message), \
        f"finding message must name the historical bug: {hits[0].message}"
    ok_src, ok_path = FIXTURES[rule_id]["ok"]
    res_ok = analysis.analyze_source(ok_src, ok_path)
    assert res_ok.findings == [], \
        (f"rule {rule_id}'s near-miss fixture must pass every rule; got "
         f"{[f.format() for f in res_ok.findings]}")


def test_meta_every_rule_has_fixtures_and_docstring():
    """The closed loop the ISSUE demands: >= 8 rules, unique ids, every
    registered rule carries BOTH fixtures and a docstring naming the
    PR/bug it encodes; no orphan fixtures."""
    ids = list(analysis.RULE_IDS)
    assert len(ids) == len(set(ids)), "duplicate rule ids"
    assert len(ids) >= 8, f"need >= 8 active rules, have {len(ids)}"
    for rule in analysis.RULES:
        assert rule.id in FIXTURES, f"rule {rule.id} has no fixture pair"
        assert {"flag", "ok"} <= set(FIXTURES[rule.id]), \
            f"rule {rule.id} needs both a must-flag and a must-pass fixture"
        doc = type(rule).__doc__ or ""
        assert re.search(r"PR \d", doc), \
            f"rule {rule.id} docstring must name the PR/bug it encodes"
        assert rule.summary, f"rule {rule.id} has no summary line"
    assert set(FIXTURES) == set(ids), \
        f"orphan fixtures: {set(FIXTURES) - set(ids)}"


def test_op_name_set_matches_registry():
    """The ladder rule's literal op-name set cannot drift from the
    launch/ops.py registry (the analyzer stays importable without jax, so
    it carries the set as data; this pin keeps the two in sync)."""
    from repro.launch import ops as op_registry
    assert set(analysis.OP_NAMES) == set(op_registry.op_names())


# ---------------------------------------------------------------------------
# Suppression mechanics
# ---------------------------------------------------------------------------

_RAW = 'import jax\n\n\ndef f(x):\n    return jax.lax.psum(x, "d")'


def test_noqa_same_line_suppresses_and_keeps_reason():
    src = _RAW + "  # repro: noqa[raw-collective]: byte accounting pinned elsewhere\n"
    res = analysis.analyze_source(src, _P)
    assert res.findings == []
    assert len(res.suppressed) == 1
    assert res.suppressed[0]["reason"] == "byte accounting pinned elsewhere"
    assert res.suppressed[0]["rule"] == "raw-collective"


def test_noqa_standalone_line_above_suppresses():
    src = ('import jax\n\n\ndef f(x):\n'
           '    # repro: noqa[raw-collective]: pinned elsewhere\n'
           '    return jax.lax.psum(x, "d")\n')
    res = analysis.analyze_source(src, _P)
    assert res.findings == []


def test_noqa_two_lines_above_does_not_reach():
    src = ('import jax\n\n\ndef f(x):\n'
           '    # repro: noqa[raw-collective]: too far away\n'
           '    y = x\n'
           '    return jax.lax.psum(y, "d")\n')
    res = analysis.analyze_source(src, _P)
    rules = sorted(f.rule for f in res.findings)
    # the finding survives AND the stranded noqa is reported
    assert rules == ["raw-collective", "unused-noqa"]


def test_noqa_wrong_rule_id_does_not_suppress():
    src = _RAW + "  # repro: noqa[tracer-leak]: mismatched excuse\n"
    res = analysis.analyze_source(src, _P)
    rules = sorted(f.rule for f in res.findings)
    assert rules == ["raw-collective", "unused-noqa"]


def test_noqa_unknown_rule_id_reported():
    src = "x = 1  # repro: noqa[not-a-rule]: whatever\n"
    res = analysis.analyze_source(src, _P)
    assert [f.rule for f in res.findings] == ["noqa-reason"]
    assert "unknown rule id" in res.findings[0].message


def test_meta_rules_cannot_be_suppressed():
    src = "x = 1  # repro: noqa[unused-noqa]: trying to silence the police\n"
    res = analysis.analyze_source(src, _P)
    assert [f.rule for f in res.findings] == ["noqa-reason"]
    assert "cannot itself be suppressed" in res.findings[0].message


def test_parse_error_is_a_finding():
    res = analysis.analyze_source("def broken(:\n", _P)
    assert [f.rule for f in res.findings] == ["parse-error"]


def test_json_report_shape():
    res = analysis.analyze_source(_RAW + "\n", _P)
    rep = analysis.to_json(res)
    assert rep["schema"] == "repro.analysis/v1"
    assert rep["rule_count"] == len(analysis.RULES)
    assert rep["ok"] is False
    assert {r["id"] for r in rep["rules"]} == set(analysis.RULE_IDS)
    f = rep["findings"][0]
    assert {"path", "line", "col", "rule", "message"} <= set(f)
    json.dumps(rep)    # serializable


# ---------------------------------------------------------------------------
# CLI exit codes + repo gate
# ---------------------------------------------------------------------------

def _run_cli(*argv, cwd=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, "-m", "repro.analysis", *argv],
                          capture_output=True, text=True, env=env, cwd=cwd)


def test_cli_exit_codes_and_json(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(FIXTURES["raw-collective"]["flag"][0])
    good = tmp_path / "good.py"
    good.write_text(FIXTURES["raw-collective"]["ok"][0])

    res = _run_cli(str(good))
    assert res.returncode == 0, res.stdout + res.stderr
    res = _run_cli(str(bad))
    assert res.returncode == 1
    assert "[raw-collective]" in res.stdout

    res = _run_cli(str(bad), "--format", "json")
    assert res.returncode == 1
    rep = json.loads(res.stdout)
    assert rep["ok"] is False and len(rep["findings"]) == 1

    assert _run_cli().returncode == 2                      # no paths
    assert _run_cli(str(tmp_path / "nope")).returncode == 2  # missing path
    assert _run_cli("--list-rules").returncode == 0


def test_repo_tree_is_clean_and_suppressions_carry_reasons():
    """The CI gate, as a test: zero findings over src/tests/benchmarks and
    every suppression in the tree states why the historical bug does not
    apply at its site."""
    res = analysis.analyze_paths([str(ROOT / "src"), str(ROOT / "tests"),
                                  str(ROOT / "benchmarks")])
    assert res.ok, "invariant linter findings:\n" + \
        "\n".join(f.format() for f in res.findings)
    assert res.n_files > 50
    for s in res.suppressed:
        assert s["reason"].strip(), f"reasonless suppression at {s}"


def test_seeded_bug_fails_gate_naming_rule_and_origin(tmp_path):
    """Acceptance pin: re-shipping a historical bug (here PR 3's jnp
    lru_cache, in a file laid out like kernels/) turns the gate red with a
    message naming the rule and the original bug."""
    pkg = tmp_path / "src" / "repro" / "kernels"
    pkg.mkdir(parents=True)
    (pkg / "seeded.py").write_text(FIXTURES["tracer-leak"]["flag"][0])
    res = _run_cli(str(tmp_path / "src"))
    assert res.returncode == 1
    assert "[tracer-leak]" in res.stdout and "PR 3" in res.stdout
