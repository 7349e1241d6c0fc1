"""Pallas TPU kernel: fused flash attention (causal + sliding window).

The attention analogue of the FourierPIM adaptation used for the FFT kernel:
keep the whole online-softmax state resident in VMEM while streaming KV
blocks, so the (Sq x Sk) score matrix never exists in HBM — one HBM read of
Q/K/V and one write of O per (head, q-block).

Grid = (heads, q_blocks, kv_blocks); the kv axis is innermost and sequential
on TPU, so VMEM scratch (m, l, acc) carries the running max / normalizer /
accumulator across kv steps: initialized at j == 0, folded every step,
normalized and stored at j == nK - 1.

The model layers use the pure-JAX blockwise formulation (same dataflow, XLA
lowers the scan) for portability; this kernel is the TPU-native hot-spot
implementation, validated against kernels/ref-style oracles in
tests/test_kernels_attention.py (interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fft import resolve_interpret

NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, n_k: int, seq_len: int, window: int,
                  causal: bool):
    h, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                 # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                 # (bk, hd)
    v = v_ref[0].astype(jnp.float32)                 # (bk, hd)
    s = q @ k.T * (q.shape[-1] ** -0.5)              # (bq, bk)

    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = kpos < seq_len
    if causal:
        valid &= kpos <= qpos
        valid &= kpos > qpos - window
    s = jnp.where(valid, s, NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + p @ v
    m_ref[...] = m_new

    @pl.when(j == n_k - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-20)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "causal", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    window: int = 1 << 30, causal: bool = True,
                    bq: int = 128, bk: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """q, k, v: (H, S, hd) (fold batch/GQA groups into H upstream).

    Returns (H, S, hd). Blocks padded to bq/bk internally.
    """
    interpret = resolve_interpret(interpret)
    H, S, hd = q.shape
    bq = min(bq, S)
    bk = min(bk, S)
    pad_q = (-S) % bq
    pad_k = (-S) % bk
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0))) if pad_k else v
    n_q = qp.shape[1] // bq
    n_k = kp.shape[1] // bk
    kern = functools.partial(_flash_kernel, bq=bq, bk=bk, n_k=n_k,
                             seq_len=S, window=window, causal=causal)
    out = pl.pallas_call(
        kern,
        grid=(H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),      # running max
            pltpu.VMEM((bq, 1), jnp.float32),      # running normalizer
            pltpu.VMEM((bq, hd), jnp.float32),     # output accumulator
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :S]


def attention_ref(q, k, v, *, window: int = 1 << 30, causal: bool = True):
    """Naive oracle: full score matrix, masked softmax."""
    H, S, hd = q.shape
    s = jnp.einsum("hqd,hkd->hqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * hd ** -0.5
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    valid = jnp.ones((S, S), bool)
    if causal:
        valid = (kpos <= qpos) & (kpos > qpos - window)
    s = jnp.where(valid[None], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p, v.astype(jnp.float32)).astype(q.dtype)
