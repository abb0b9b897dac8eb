"""RWKV-6 one-token step on a pooled, packed state — Pallas TPU kernel.

The serving pool holds every layer's state as one leaf (L, B, H, R, N):
each head's (hs, hs) state row-major in R rows of N lanes, N = 128 when
hs divides 128 (``models.rwkv6.pack_state``), so lane n of row p holds
S[p·g + n // hs, n % hs] with g = N / hs.  One launch advances layer
``l``'s rows by one token, in place (``input_output_aliases``), ``l``
arriving by scalar prefetch for the index map:

    y[n]  = Σ_p r̂[p, n] · S[p, n]          (the caller folds the g lane
                                           groups: o_j = Σ_i r_i S_ij)
    S'    = ŵ ⊙ S + k̂ ⊙ v̂

where x̂[p, n] = x[p·g + n // hs] (one value per row and lane group,
broadcast over the group's hs lanes) and v̂[n] = v[n % hs].  The rank-1
bonus v_j · Σ_i r_i u_i k_i needs no state and is added by the caller.

Layouts (arranged by ``_arrange``): r, k, w: (B, H / bh, R, bh·g), the bh·g
lanes of a row holding each head's g values; v: (B, H / bh, bh, N), v
tiled over the g lane groups; y: as v.  Grid: (B / bb, H / bh).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(layer_ref, s_ref, r_ref, k_ref, w_ref, v_ref, y_ref, so_ref, *,
            hs: int, g: int, block_h: int):
    del layer_ref                      # used by the index maps only
    rows_, lanes = s_ref.shape[-2:]
    group = jax.lax.broadcasted_iota(jnp.int32, (rows_, lanes), 1) // hs
    in_group = [group == q for q in range(g - 1)]

    def spread(x, h):
        """(R, bh·g) values → (R, N): head h's g values of each row, each
        over its group's hs lanes."""
        out = jnp.broadcast_to(x[:, h * g + g - 1:h * g + g], (rows_, lanes))
        for q in range(g - 2, -1, -1):
            out = jnp.where(in_group[q], x[:, h * g + q:h * g + q + 1], out)
        return out

    def row(b, carry):
        r, k, w = r_ref[b], k_ref[b], w_ref[b]
        for h in range(block_h):
            s = s_ref[b, h]
            y_ref[b, pl.ds(h, 1), :] = jnp.sum(spread(r, h) * s, axis=0,
                                               keepdims=True)
            so_ref[b, h] = (spread(w, h) * s
                            + spread(k, h) * v_ref[b, pl.ds(h, 1), :])
        return carry

    jax.lax.fori_loop(0, s_ref.shape[0], row, 0)


def _arrange(r, k, v, w, lanes: int, block_h: int):
    """(B, H, hs) inputs → the kernel's layouts, in fp32."""
    B, H, hs = r.shape
    g = lanes // hs
    nh = H // block_h

    def rows(x):
        x = x.astype(jnp.float32).reshape(B, nh, block_h, hs // g, g)
        return x.transpose(0, 1, 3, 2, 4).reshape(B, nh, hs // g,
                                                  block_h * g)

    vt = jnp.tile(v.astype(jnp.float32), (1, 1, g))
    return rows(r), rows(k), rows(w), vt.reshape(B, nh, block_h, lanes)


def wkv6_decode_pooled(state, layer, r, k, v, w, *, block_b: int,
                       block_h: int, interpret: bool = False):
    """state: (L, B, H, R, N) fp32; layer: int32 scalar; r, k, v, w:
    (B, H, hs).  Returns (y (B, H, N) fp32, state with layer's rows
    advanced), the state aliased to its input."""
    L, B, H, R, N = state.shape
    hs = r.shape[-1]
    g = N // hs
    nh = H // block_h
    rr, kk, ww, vt = _arrange(r, k, v, w, N, block_h)
    kernel = functools.partial(_kernel, hs=hs, g=g, block_h=block_h)
    small = pl.BlockSpec((block_b, None, R, block_h * g),
                         lambda i, j, l: (i, j, 0, 0))
    per_head = pl.BlockSpec((block_b, None, block_h, N),
                            lambda i, j, l: (i, j, 0, 0))
    pooled = pl.BlockSpec((None, block_b, block_h, R, N),
                          lambda i, j, l: (l[0], i, j, 0, 0))
    y, state = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B // block_b, nh),
            in_specs=[pooled, small, small, small, per_head],
            out_specs=[per_head, pooled]),
        out_shape=[jax.ShapeDtypeStruct((B, nh, block_h, N), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={1: 1},
        name="wkv6_decode",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), state, rr, kk, ww, vt)
    return y.reshape(B, H, N), state
