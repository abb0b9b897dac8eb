"""RWKV-6 recurrence — chunked Pallas TPU kernel.

The per-token rank-1 state update
    S_t = diag(w_t) S_{t-1} + k_t ⊗ v_t,   o_t = r_t·(S_{t-1} + u⊙k_t ⊗ v_t)
is re-expressed per chunk of L tokens as three MXU matmuls (the standard
chunked linear-attention form, adapted from the paper's CUDA kernel):

    P_t   = ∏_{s<t} w_s                (exclusive cumprod, in-chunk)
    r̃_t  = r_t ⊙ P_t ,  k̃_s = k_s / P_{s+1}
    o     = r̃ @ S₀  +  ((r̃ @ k̃ᵀ) ⊙ strict_lower + diag(r·(u⊙k))) @ v
    S_L   = diag(P_L) S₀ + (k̃ ⊙ P_L)ᵀ @ v

The chunk state S (hs × hs) persists in VMEM scratch across the sequential
chunk grid dimension.  Layouts (folded in ops.py): r,k,v,w: (BH, T, hs);
u: (BH, hs) broadcast per head, carried into the kernel as (BH, 1, hs) so
that its block's last two dims are the array's own (Mosaic refuses a
(1, hs) block of a (BH, hs) array: its second-to-last dim is neither a
multiple of 8 nor the full dim).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _cumsum_rows(x):
    """Inclusive prefix sum over axis 0 by Hillis-Steele doubling
    (log2(L) shifted adds): Mosaic has no lowering for ``cumsum``."""
    L = x.shape[0]
    off = 1
    while off < L:
        x = x + jnp.pad(x, ((off, 0), (0, 0)))[:L]
        off *= 2
    return x


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, sout_ref, s_ref,
                 *, block_t: int, n_t: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)       # (L, hs)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)       # decay in (0, 1)
    u = u_ref[0].astype(jnp.float32)       # (1, hs) bonus

    # exclusive cumulative product of decays (log-space for stability)
    logw = jnp.log(jnp.maximum(w, 1e-38))
    cum = _cumsum_rows(logw)               # inclusive
    p_incl = jnp.exp(cum)                  # P_{t+1} = ∏_{s<=t} w_s
    p_excl = jnp.exp(cum - logw)           # P_t     = ∏_{s<t}  w_s

    r_t = r * p_excl                       # r̃
    k_t = k / jnp.maximum(p_incl, 1e-38)   # k̃

    s0 = s_ref[...]                        # (hs, hs)
    inter = jax.lax.dot_general(
        r_t, s0, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # (L, hs)
    scores = jax.lax.dot_general(
        r_t, k_t, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # (L, L)
    L = r.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    scores = jnp.where(rows > cols, scores, 0.0)       # strict lower
    diag = (r * u * k).sum(axis=1)                     # (L,)
    intra = jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o = inter + intra + diag[:, None] * v
    o_ref[0] = o.astype(o_ref.dtype)

    p_last = p_incl[L - 1:]                            # (1, hs)
    kp = k_t * p_last
    s_new = p_last.T * s0 + jax.lax.dot_general(
        kp, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_ref[...] = s_new

    @pl.when(ti == n_t - 1)
    def _emit_state():
        sout_ref[0] = s_new


def wkv6_folded(r, k, v, w, u, *, block_t: int = 64,
                interpret: bool = False):
    """r,k,v,w: (BH, T, hs); u: (BH, hs).  Returns (o (BH,T,hs) fp32,
    final state (BH, hs, hs) fp32)."""
    BH, T, hs = r.shape
    block_t = min(block_t, T)
    assert T % block_t == 0
    n_t = T // block_t
    kernel = functools.partial(_wkv6_kernel, block_t=block_t, n_t=n_t)
    o, s_out = pl.pallas_call(
        kernel,
        grid=(BH, n_t),
        in_specs=[
            pl.BlockSpec((1, block_t, hs), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, block_t, hs), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, block_t, hs), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, block_t, hs), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, 1, hs), lambda b, t: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, hs), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, hs, hs), lambda b, t: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, hs), jnp.float32),
            jax.ShapeDtypeStruct((BH, hs, hs), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hs, hs), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u.reshape(BH, 1, hs))
    return o, s_out
