"""RG-LRU diagonal linear recurrence  h_t = a_t ⊙ h_{t-1} + b_t — Pallas.

TPU adaptation: the time axis is chunked over a sequential grid dimension;
the cross-chunk carry h lives in VMEM scratch (persists across grid steps),
and the in-chunk inclusive scan is a Hillis-Steele doubling network
(log₂(block_t) vector steps on (block_t, D) tiles — VPU-friendly, no
sequential loop over tokens).

Layouts: a, b: (B, T, D) fp32 → out h: (B, T, D) fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_block(a, b):
    """Inclusive scan of h_t = a_t h_{t-1} + b_t within a (T, D) block via
    Hillis-Steele doubling: log2(T) steps."""
    T = a.shape[0]
    off = 1
    while off < T:
        a_sh = jnp.pad(a, ((off, 0), (0, 0)), constant_values=1.0)[:T]
        b_sh = jnp.pad(b, ((off, 0), (0, 0)))[:T]
        b = a * b_sh + b
        a = a * a_sh
        off *= 2
    return a, b      # a = cumulative products, b = scanned h


def _rglru_kernel(a_ref, b_ref, o_ref, h_ref, *, n_t: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0]                       # (block_t, D) fp32
    b = b_ref[0]
    prod, h = _scan_block(a, b)
    h = h + prod * h_ref[...]          # fold in carry from previous chunk
    o_ref[0] = h
    h_ref[...] = h[-1:]                # (1, D) carry


def rglru_scan(a, b, *, block_t: int = 128, interpret: bool = False):
    """a, b: (B, T, D) fp32; returns inclusive scan h (B, T, D) fp32."""
    B, T, D = a.shape
    block_t = min(block_t, T)
    assert T % block_t == 0
    n_t = T // block_t
    kernel = functools.partial(_rglru_kernel, n_t=n_t)
    return pl.pallas_call(
        kernel,
        grid=(B, n_t),
        in_specs=[
            pl.BlockSpec((1, block_t, D), lambda b_, t: (b_, t, 0)),
            pl.BlockSpec((1, block_t, D), lambda b_, t: (b_, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_t, D), lambda b_, t: (b_, t, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, D), jnp.float32)],
        interpret=interpret,
    )(a.astype(jnp.float32), b.astype(jnp.float32))
