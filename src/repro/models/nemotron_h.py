"""Nemotron-H stack (``layer_pattern="nemotron_h"``): Mamba-2, sparse
expert and GQA attention blocks in ``cfg.block_pattern`` order, one mixer
per block under a pre-RMSNorm and a residual, each under a
``jax.named_scope`` of its kind.  A Python loop runs the blocks.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from .attention import (attn_apply, attn_decode_pooled, attn_spec,
                        attn_with_cache, init_kv_cache)
from .layers import P, rms_norm
from .mamba2 import (init_mamba2_cache, mamba2_apply, mamba2_spec,
                     mamba2_step)
from .moe import moe_held_apply, moe_spec

__all__ = ["EXACT_PROMPTS", "spec", "forward", "init_cache", "decode"]

EXACT_PROMPTS = True


def spec(cfg) -> Dict[str, Any]:
    mixers = {"mamba2": mamba2_spec, "moe": moe_spec, "attn": attn_spec}
    return {"blocks": [{"norm": P((cfg.d_model,), ("embed",), init="ones"),
                        kind: mixers[kind](cfg)}
                       for kind in cfg.layer_kinds()]}


def forward(params, x, cfg, positions, policy, *, collect=False, max_seq=0,
            **_):
    """Every block in pattern order; the caches stacked per kind:
    {ssm, conv} of the Mamba-2 blocks and {kv: k, v} of the attention
    blocks."""
    got: Dict[str, list] = {"mamba2": [], "attn": []}
    for kind, lp in zip(cfg.layer_kinds(), params["blocks"]):
        with jax.named_scope(kind):
            xn = rms_norm(x, lp["norm"], cfg.norm_eps)
            if kind == "mamba2":
                o, c = mamba2_apply(lp["mamba2"], xn, cfg, collect=collect)
            elif kind == "moe":
                o, c = moe_held_apply(lp["moe"], xn, cfg)[0], {}
            elif collect:
                # slot t of the full cache holds position t: no "pos" leaf
                o, c = attn_with_cache(lp["attn"], xn, cfg, positions, 0,
                                       max_seq)
                c = {"k": c["k"], "v": c["v"]}
            else:
                o, c = attn_apply(lp["attn"], xn, cfg, positions), {}
        x = x + o
        if c:
            got[kind].append(c)
    if not collect:
        return x, 0.0, {}

    def stack(cs):
        return jax.tree.map(lambda *t: jnp.stack(t), *cs)
    return x, 0.0, dict(stack(got["mamba2"]), kv=stack(got["attn"]))


def init_cache(cfg, batch, max_seq, dtype, **_):
    kinds = cfg.layer_kinds()
    cache = init_mamba2_cache(cfg, kinds.count("mamba2"), batch, dtype)
    kv = init_kv_cache(cfg, batch, max_seq, kinds.count("attn"), dtype)
    cache["kv"] = {"k": kv["k"], "v": kv["v"]}
    return cache


def decode(params, x, cfg, pool, pos, policy):
    """One token through every block, the pooled cache threaded through
    them: each block writes its rows of the pool back in place."""
    n = {"mamba2": 0, "attn": 0}
    for kind, lp in zip(cfg.layer_kinds(), params["blocks"]):
        i = n.get(kind, 0)
        with jax.named_scope(kind):
            xn = rms_norm(x, lp["norm"], cfg.norm_eps)
            if kind == "mamba2":
                o, ssm, win = mamba2_step(lp["mamba2"], xn, cfg,
                                          pool["ssm"], i, pool["conv"][i])
                pool = dict(pool, ssm=ssm,
                            conv=jax.lax.dynamic_update_index_in_dim(
                                pool["conv"], win.astype(pool["conv"].dtype),
                                i, 0))
            elif kind == "moe":
                o, _ = moe_held_apply(lp["moe"], xn, cfg)
            else:
                o, kv = attn_decode_pooled(lp["attn"], xn, cfg, pool["kv"],
                                           i, pos)
                pool = dict(pool, kv=kv)
        n[kind] = i + 1
        x = x + o
    return x, pool
