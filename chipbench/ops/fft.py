"""fft: the batched complex transform, complex64 in and out.

Inputs are standard normal in both planes. The plain reference is numpy's
float64 FFT; the comparison is each row's largest error relative to the
row's largest reference value, worst over the rows checked.
"""
from __future__ import annotations

import math

import numpy as np

CHECK = "rel_err"
BYTES_PER_POINT = 16            # read complex64, write complex64


def flops(n: int) -> float:
    return 5.0 * n * math.log2(n)


def limbs(config: dict) -> int:
    return 1


def payloads(rng: np.random.Generator, count: int, config: dict):
    x = rng.standard_normal((count, 2, config["n"]), dtype=np.float32)
    return (x[:, 0] + 1j * x[:, 1]).astype(np.complex64)


def request(pool, i: int):
    return pool[i]


def reference(pool, idx, config: dict) -> np.ndarray:
    return np.fft.fft(pool[idx].astype(np.complex128), axis=-1)


def control(pool, idx, config: dict) -> np.ndarray:
    from chipbench.highdft import fft_high
    return fft_high(pool[idx])


def compare(got, want) -> float:
    got = np.asarray(got, np.complex128)
    err = np.abs(got - want).max(axis=-1) / np.abs(want).max(axis=-1)
    return float(err.max())
