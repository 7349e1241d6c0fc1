"""The peak table and the work each request needs."""
import json

import numpy as np
import pytest

from chipbench import harness, work


def _op(name):
    return harness.load_module(harness.HERE / "ops" / f"{name}.py")


def test_peak_table_has_v5e_with_its_source():
    table = json.loads(work.PEAKS_FILE.read_text())
    assert "cloud.google.com/tpu/docs/v5e" in table["source"]
    v5e = work.load_peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12


def test_unknown_device_kind_is_an_error():
    with pytest.raises(work.UnknownDevice):
        work.load_peaks("TPU v9 imaginary")


@pytest.mark.parametrize("op,per_point", [("fft", 16), ("polymul-real", 12),
                                          ("polymul-mod", 12)])
def test_request_bytes_read_inputs_once_and_write_once(op, per_point):
    assert work.request_bytes(_op(op), 16384, 1) == per_point * 16384
    assert work.request_bytes(_op(op), 8192, 15) == per_point * 8192 * 15


def test_rns_limbs_follow_the_crt_bound():
    cfg = json.loads((harness.HERE / "configs" /
                      "he-std128-n8192-logq218.json").read_text())
    mod = _op("polymul-mod")
    assert mod.limbs(cfg) == cfg["limbs"] == 15
    q = mod.modulus(cfg["n"], cfg["engine"]["modulus_bits"])
    assert q.bit_length() == 210 <= 218


def test_the_hbm_term_bounds_the_float_ops():
    peaks = work.load_peaks("TPU v5 lite")
    for op in ("fft", "polymul-real"):
        t, term = work.request_min_s(_op(op), 16384, 1, peaks)
        assert term == "hbm"
        assert t == pytest.approx(work.request_bytes(_op(op), 16384, 1)
                                  / 819e9)
    t, term = work.request_min_s(_op("polymul-mod"), 8192, 15, peaks)
    assert term == "hbm" and t == pytest.approx(12 * 8192 * 15 / 819e9)


def test_fft_compute_term_is_far_below_its_hbm_term():
    mod = _op("fft")
    ratio = (mod.flops(16384) / 197e12) / (16 * 16384 / 819e9)
    assert ratio < 1 / 40
    assert np.isfinite(ratio)
