"""polymul-mod: the exact negacyclic product in Z_Q[x]/(x^N + 1).

Q is the deployment's: the product of the largest primes q < 2^30 with
q = 1 (mod 2N), taken from the top down until Q has ``modulus_bits`` bits
(an RLWE modulus chain of NTT-friendly primes). Inputs are uniform in
[0, Q). The plain reference multiplies the two polynomials as big integers
(Kronecker substitution: one Python integer product per request) and folds
the top half back with the negacyclic sign. The comparison counts the
coefficients that differ from it; the limit is 0.

The control breaks the exactness the configuration states, as a
recombination in floating point would: the reference's integer coefficients
rounded to float64, then reduced mod Q. (A lift over too few limbs would
not do: the limb set begins with Q's own primes, so Q divides any product
of them and the result mod Q stays exact.)
"""
from __future__ import annotations

import functools

import numpy as np

CHECK = "mismatched"
BYTES_PER_POINT = 12            # per limb: read two uint32 residues, write one
LIMB_BITS = 30


def flops(n: int):
    return None                 # no published 32-bit integer peak


def _is_prime(m: int) -> bool:
    if m < 2:
        return False
    for p in (2, 3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37):
        if m % p == 0:
            return m == p
    d, s = m - 1, 0
    while d % 2 == 0:
        d, s = d // 2, s + 1
    for a in (2, 3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37):
        x = pow(a, d, m)
        if x in (1, m - 1):
            continue
        for _ in range(s - 1):
            x = x * x % m
            if x == m - 1:
                break
        else:
            return False
    return True


def _primes(n: int):
    """Primes q < 2^30 with q = 1 (mod 2n), largest first."""
    step = 2 * n
    q = ((1 << LIMB_BITS) - 2) // step * step + 1
    while q > step:
        if _is_prime(q):
            yield q
        q -= step


@functools.lru_cache(maxsize=8)
def modulus(n: int, modulus_bits: int) -> int:
    q = 1
    for p in _primes(n):
        q *= p
        if q.bit_length() >= modulus_bits:
            return q
    raise ValueError(f"no {modulus_bits}-bit modulus for N={n}")


def limbs(config: dict) -> int:
    """The least number of 30-bit limbs whose product exceeds 2 N Q^2:
    the residue planes the RNS route moves per request."""
    n = config["n"]
    bound = 2 * n * modulus(n, config["engine"]["modulus_bits"]) ** 2
    m = 1
    for k, p in enumerate(_primes(n), start=1):
        m *= p
        if m > bound:
            return k
    raise ValueError(f"not enough primes for N={n}")


def payloads(rng: np.random.Generator, count: int, config: dict):
    """(a, b): two (count, N) object arrays of Python ints in [0, Q)."""
    n = config["n"]
    q = modulus(n, config["engine"]["modulus_bits"])
    chunks = (q.bit_length() + 64 + 29) // 30      # 64 spare bits: bias < 2^-64
    draws = rng.integers(0, 1 << 30, size=(chunks, 2, count, n),
                         dtype=np.int64)
    val = np.zeros((2, count, n), object)
    for c in range(chunks):
        val = (val << 30) | draws[c].astype(object)
    val %= q
    return val[0], val[1]


def request(pool, i: int):
    return (pool[0][i], pool[1][i])


def _signed_product(a, b, n: int, q: int) -> list[int]:
    """Exact coefficients of a * b mod (x^n + 1), before reduction mod Q."""
    width = (2 * q.bit_length() + n.bit_length() + 8) // 8   # bytes per slot
    pack = [int.from_bytes(b"".join(int(v).to_bytes(width, "little")
                                    for v in poly), "little")
            for poly in (a, b)]
    full = (pack[0] * pack[1]).to_bytes(2 * n * width, "little")
    c = [int.from_bytes(full[i * width:(i + 1) * width], "little")
         for i in range(2 * n)]
    return [c[k] - c[k + n] for k in range(n)]


def _rows(pool, idx, config: dict, lift):
    n = config["n"]
    q = modulus(n, config["engine"]["modulus_bits"])
    out = np.empty((len(idx), n), object)
    for r, i in enumerate(idx):
        out[r] = [lift(v) % q for v in
                  _signed_product(pool[0][i], pool[1][i], n, q)]
    return out


def reference(pool, idx, config: dict) -> np.ndarray:
    return _rows(pool, idx, config, lambda v: v)


def control(pool, idx, config: dict) -> np.ndarray:
    return _rows(pool, idx, config, lambda v: int(float(v)))


def compare(got, want) -> float:
    return float(np.sum(np.asarray(got, object) != want))
