"""A whole run on the CPU, past the look for a chip, with the timed path
broken underneath: ``correct`` comes out false for each fault the cells
can have, and true with nothing broken."""
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness

CELLS = ["paper-n16384-closed", "polyreal-n16384-open", "he128-n8192-closed"]


def _run(root, name, seconds=1.0):
    cell = harness.Cell.load(name, root=root,
                             bench_file=root / "BENCHMARK.json")
    return harness.run_cell(cell, seed=2**31 + 3, seconds=seconds,
                            trace=False, t_start=0.0, require_tpu=False)


def _break_float(monkeypatch, how):
    from repro.core import fft as fft_core

    def broken(fn):
        def wrapped(*a, **kw):
            out = fn(*a, **kw)
            if how == "alter":          # one element of every answer
                scale = jnp.max(jnp.abs(out), axis=-1, keepdims=True)
                return out.at[..., :1].add(1e-4 * scale[..., :1])
            half = out.shape[0] // 2    # the batch's second half never made
            return out.at[half:].set(0)
        return wrapped
    monkeypatch.setattr(fft_core, "fft", broken(fft_core.fft))
    monkeypatch.setattr(fft_core, "polymul_real",
                        broken(fft_core.polymul_real))


def _break_exact(monkeypatch, how):
    from repro.core.ntt import rns
    orig = rns.crt_to_modulus

    def broken(residues, params):
        out = orig(residues, params)
        if how == "alter":
            out[..., 0] = (out[..., 0] + 1) % params.modulus
        else:
            out[out.shape[0] // 2:] = 0
        return out
    monkeypatch.setattr(rns, "crt_to_modulus", broken)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(tiny_root, name):
    res = _run(tiny_root, name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("how", ["alter", "half"])
@pytest.mark.parametrize("name", CELLS)
def test_broken_path_is_not_correct(tiny_root, monkeypatch, name, how):
    if name.startswith("he128"):
        _break_exact(monkeypatch, how)
    else:
        _break_float(monkeypatch, how)
    res = _run(tiny_root, name)
    assert not res["correct"], res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
    assert np.isfinite(res["metrics"]["throughput"]["value"])
