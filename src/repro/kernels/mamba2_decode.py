"""Mamba-2 one-token step on the pooled SSM state — Pallas TPU kernel.

The serving pool holds every Mamba-2 block's state as one leaf (L, B, H,
P, N) fp32, batch-major with the state size N in the lanes.  One launch
advances block ``l``'s rows by one token in place
(``input_output_aliases``), ``l`` arriving by scalar prefetch for the
index map, so each state tile is read once and written once:

    h'[p, n] = decay · h[p, n] + u[p] · B_g[n]
    y[p]     = Σ_n h'[p, n] · C_g[n]

for every row and head, head h reading group g = h // (H / G) of B and C.
The D term and the gated norm are the caller's.  Everything is fp32 on
the vector units: the products, the sum over n and the state.

Layouts (arranged by ``_arrange``): decay: (B, H / bh, 1, bh), one lane
per head; u: (B, H / bh, P, bh), P down the sublanes, so that a head's
column broadcasts over the lanes; B, C: (B, H / bh, gt, N), the gt groups
that head tile uses.  y leaves lane-dense as (B, H·P / N, N): k = N / P
heads' products, stacked to an (N, N) square, are transposed so that the
sum over n runs down the sublanes and lands as one row of k heads × P
lanes.  Grid: (B / bb, H / bh).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(layer_ref, s_ref, d_ref, u_ref, b_ref, c_ref, y_ref, so_ref, *,
            block_h: int, k: int):
    del layer_ref                      # used by the index maps only
    P, N = s_ref.shape[-2:]
    gt = b_ref.shape[-2]

    def row(b, carry):
        d, u, bg, cg = d_ref[b], u_ref[b], b_ref[b], c_ref[b]
        for q in range(block_h // k):
            prods = []
            for h in range(q * k, (q + 1) * k):
                g = h * gt // block_h
                s = (jnp.broadcast_to(d[:, h:h + 1], (P, N)) * s_ref[b, h]
                     + jnp.broadcast_to(u[:, h:h + 1], (P, N))
                     * bg[g:g + 1, :])
                so_ref[b, h] = s
                prods.append(s * cg[g:g + 1, :])
            y_ref[b, pl.ds(q, 1), :] = jnp.sum(
                jnp.concatenate(prods, axis=0).T, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, s_ref.shape[0], row, 0)


def _arrange(decay, u, b, c, block_h: int):
    """(B, H) decay, (B, H, P) u and (B, G, N) B, C → the kernel's
    layouts, in fp32."""
    B, H, P = u.shape
    G, N = b.shape[1:]
    nh = H // block_h
    hpg = H // G
    gt = max(block_h // hpg, 1)

    def per_tile(t):
        # a tile within one group gets that group's row
        return jnp.repeat(t.astype(jnp.float32), max(hpg // block_h, 1),
                          axis=1).reshape(B, nh, gt, N)

    dd = decay.astype(jnp.float32).reshape(B, nh, 1, block_h)
    uu = u.astype(jnp.float32).reshape(B, nh, block_h, P).transpose(
        0, 1, 3, 2)
    return dd, uu, per_tile(b), per_tile(c)


def mamba2_decode_pooled(state, layer, decay, u, b, c, *, block_b: int,
                         block_h: int, interpret: bool = False):
    """state: (L, B, H, P, N) fp32; layer: int32 scalar; decay: (B, H);
    u: (B, H, P); b, c: (B, G, N).  Returns (y (B, H, P) fp32, state with
    layer's rows advanced), the state aliased to its input."""
    L, B, H, P, N = state.shape
    G = b.shape[1]
    k = N // P
    assert k * P == N and block_h % k == 0, (P, N, block_h)
    assert block_h % (H // G) == 0 or (H // G) % block_h == 0, (H, G,
                                                                 block_h)
    nh = H // block_h
    dd, uu, bt, ct = _arrange(decay, u, b, c, block_h)
    gt = bt.shape[2]
    kernel = functools.partial(_kernel, block_h=block_h, k=k)

    def tile(rows, lanes):
        return pl.BlockSpec((block_b, None, rows, lanes),
                            lambda i, j, l: (i, j, 0, 0))

    pooled = pl.BlockSpec((None, block_b, block_h, P, N),
                          lambda i, j, l: (l[0], i, j, 0, 0))
    y_spec = pl.BlockSpec((block_b, block_h // k, N),
                          lambda i, j, l: (i, j, 0))
    tile_bytes = 4 * block_b * block_h * P * N
    y, state = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B // block_b, nh),
            in_specs=[pooled, tile(1, block_h), tile(P, block_h),
                      tile(gt, N), tile(gt, N)],
            out_specs=[y_spec, pooled]),
        out_shape=[jax.ShapeDtypeStruct((B, H // k, N), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={1: 1},
        name="mamba2_decode",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # the state tile in and out, each double-buffered, and room
            # for the small operands and the kernel's own values
            vmem_limit_bytes=4 * tile_bytes + (8 << 20)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), state, dd, uu, bt, ct)
    return y.reshape(B, H, P), state
