"""Causal/sliding-window GQA flash attention — Pallas TPU kernel.

TPU-native adaptation (vs the CUDA flash-attention the GPU world uses):
tiles are (block_q × block_k) MXU-aligned (multiples of 128 on the lane
dim), the online-softmax accumulators live in VMEM scratch and persist
across the sequential innermost grid dimension (the TPU grid is a sequential
scan over `k` blocks, not a thread block), and the GQA group dim G rides
inside the tile so K/V tiles are loaded once per q tile regardless of the
group size.

Layouts (folded in ops.py):  q: (BK, S, G, D);  k, v: (BK, T, D) where
BK = batch × kv_heads.  Output: (BK, S, G, D).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  block_q: int, block_k: int, n_k: int, causal: bool,
                  window: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)          # (block_q, G, D)
    k = k_ref[0].astype(jnp.float32)          # (block_k, D)
    v = v_ref[0].astype(jnp.float32)          # (block_k, D)

    s = jax.lax.dot_general(
        q, k, (((2,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)   # (block_q, G, block_k)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1, block_k), 2)
    mask = jnp.ones((block_q, 1, block_k), jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                        # (block_q, G)
    m_new = jnp.maximum(m_prev, s.max(axis=2))
    p = jnp.exp(s - m_new[..., None])          # (block_q, G, block_k)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=2)
    pv = jax.lax.dot_general(
        p, v, (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)    # (block_q, G, D)
    acc_ref[...] = acc_ref[...] * corr[..., None] + pv
    m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finish():
        lse = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / lse[..., None]).astype(o_ref.dtype)


def flash_attention_folded(q, k, v, *, causal: bool = True, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """q: (BK, S, G, D) pre-scaled by 1/sqrt(D); k, v: (BK, T, D)."""
    BK, S, G, D = q.shape
    T = k.shape[1]
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0
    n_q, n_k = S // block_q, T // block_k

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, n_k=n_k,
        causal=causal, window=window)

    # fp32 accumulators in VMEM, persisting across the sequential k grid dim
    scratch_shapes = [
        pltpu.VMEM((block_q, G, D), jnp.float32),
        pltpu.VMEM((block_q, G), jnp.float32),
        pltpu.VMEM((block_q, G), jnp.float32),
    ]

    return pl.pallas_call(
        kernel,
        grid=(BK, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, G, D), lambda b, qi, ki: (b, qi, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, G, D),
                               lambda b, qi, ki: (b, qi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BK, S, G, D), q.dtype),
        scratch_shapes=scratch_shapes,
        interpret=interpret,
    )(q, k, v)
