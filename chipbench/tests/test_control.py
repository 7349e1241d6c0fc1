"""Each cell's control comes out not correct by the run's own decision: the
plain reference one step below the stated precision (``high`` for f32 at
``HIGHEST``) or without the stated exactness (float64 for exact big-int
arithmetic), put in place of the program's results and judged by
``harness.compare_rows`` and ``harness.verdict``. The chip runs of the same,
at the cells' own sizes, are ``control.py``'s."""
import json

import numpy as np
import pytest

from chipbench import harness

CELLS = ["paper-n16384-closed", "polyreal-n16384-open", "he128-n8192-closed"]


def _setup(config_name, op):
    cfg = json.loads((harness.HERE / "configs" / f"{config_name}.json")
                     .read_text())
    mod = harness.load_module(harness.HERE / "ops" / f"{op}.py")
    return cfg, mod


def _judge(cfg, op, mod, pool, rows):
    checks = harness.compare_rows(cfg, {op: mod}, {op: pool},
                                  {op: list(enumerate(rows))})
    return harness.verdict(checks, 0), checks


@pytest.mark.parametrize("op", ["fft", "polymul-real"])
def test_float_control_fails_its_limit(op):
    cfg, mod = _setup("fourierpim-fp32-n16384", op)
    pool = mod.payloads(np.random.default_rng(2**31 + 11), 4, cfg)
    idx = [0, 1, 2, 3]
    want = mod.reference(pool, idx, cfg)
    assert _judge(cfg, op, mod, pool, want)[0]
    correct, checks = _judge(cfg, op, mod, pool, mod.control(pool, idx, cfg))
    assert correct is False, checks


def test_exact_control_fails_its_limit():
    op = "polymul-mod"
    cfg, mod = _setup("he-std128-n8192-logq218", op)
    pool = mod.payloads(np.random.default_rng(2**31 + 12), 1, cfg)
    want = mod.reference(pool, [0], cfg)
    assert _judge(cfg, op, mod, pool, want)[0]
    correct, checks = _judge(cfg, op, mod, pool, mod.control(pool, [0], cfg))
    assert correct is False, checks


@pytest.mark.parametrize("name", CELLS)
def test_control_in_the_programs_place_is_not_correct(tiny_root, monkeypatch,
                                                      name):
    """A whole run on the CPU whose delivered results are the control's,
    computed for the very requests each batch served."""
    complete = harness.Session._complete

    def with_control(self, op, reqs, arr, t):
        rows = self.ops[op].control(self.pools[op], [r.index for r in reqs],
                                    self.cell.config)
        return complete(self, op, reqs, rows, t)

    monkeypatch.setattr(harness.Session, "_complete", with_control)
    cell = harness.Cell.load(name, root=tiny_root,
                             bench_file=tiny_root / "BENCHMARK.json")
    res = harness.run_cell(cell, seed=2**31 + 5, seconds=1.0, trace=False,
                           t_start=0.0, require_tpu=False)
    assert res["correct"] is False, res["checks"]
