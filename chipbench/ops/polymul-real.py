"""polymul-real: the real circular product of two float32 rows.

Inputs are standard normal. The plain reference is numpy's float64
``irfft(rfft(a) * rfft(b))``; the comparison is each row's largest error
relative to the row's largest reference value, worst over the rows checked.
"""
from __future__ import annotations

import math

import numpy as np

CHECK = "rel_err"
BYTES_PER_POINT = 12            # read two float32 rows, write one


def flops(n: int) -> float:
    # three real transforms (half a complex one each) and the half-spectrum
    # pointwise complex product
    return 3 * 2.5 * n * math.log2(n) + 3.0 * n


def limbs(config: dict) -> int:
    return 1


def payloads(rng: np.random.Generator, count: int, config: dict):
    return rng.standard_normal((count, 2, config["n"]), dtype=np.float32)


def request(pool, i: int):
    return (pool[i, 0], pool[i, 1])


def reference(pool, idx, config: dict) -> np.ndarray:
    a = pool[idx, 0].astype(np.float64)
    b = pool[idx, 1].astype(np.float64)
    n = a.shape[-1]
    return np.fft.irfft(np.fft.rfft(a) * np.fft.rfft(b), n)


def control(pool, idx, config: dict) -> np.ndarray:
    from chipbench.highdft import circular_real_high
    return circular_real_high(pool[idx, 0], pool[idx, 1])


def compare(got, want) -> float:
    got = np.asarray(got, np.float64)
    err = np.abs(got - want).max(axis=-1) / np.abs(want).max(axis=-1)
    return float(err.max())
