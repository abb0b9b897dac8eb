"""Dense attention stack (``layer_pattern="full"``): GQA self-attention
and an FFN, or sparse experts, in every layer; a full KV cache per layer.
Right-padded prompts are exact: causality keeps a request's positions
independent of the padding, and decode masks the padded slots until it
overwrites them."""
from __future__ import annotations

from typing import Any, Dict

import jax

from .attention import (attn_apply, attn_decode, attn_spec, attn_with_cache,
                        init_kv_cache)
from .layers import P, ffn_apply, ffn_spec, rms_norm
from .moe import moe_apply, moe_apply_ep, moe_spec

__all__ = ["EXACT_PROMPTS", "spec", "forward", "init_cache", "decode",
           "layer_spec", "attn_block", "decode_layer"]

EXACT_PROMPTS = False


def layer_spec(cfg, n: int) -> Dict[str, Any]:
    spec = {
        "ln1": P((n, cfg.d_model), ("layers", "embed"), init="ones"),
        "ln2": P((n, cfg.d_model), ("layers", "embed"), init="ones"),
        "attn": attn_spec(cfg, (n,), ("layers",)),
    }
    if cfg.is_moe:
        spec["moe"] = moe_spec(cfg, (n,), ("layers",))
    else:
        spec["ffn"] = ffn_spec(cfg.d_model, cfg.d_ff, cfg.activation,
                               (n,), ("layers",))
    return spec


def spec(cfg) -> Dict[str, Any]:
    return {"layers": layer_spec(cfg, cfg.n_layers)}


def attn_block(lp, x, cfg, positions, policy, window, use_pallas,
               collect=False, max_seq=0, moe_ep=False):
    """One attention layer.  Returns (x, aux, cache); the cache is {}
    unless ``collect`` (prefill) is set."""
    xn = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if policy is not None:
        xn = policy.acts(xn, "block_in")
    if collect:
        attn_out, cache = attn_with_cache(lp["attn"], xn, cfg, positions,
                                          window, max_seq)
    else:
        attn_out = attn_apply(lp["attn"], xn, cfg, positions, policy=policy,
                              window=window, use_pallas=use_pallas)
        cache = {}
    h = x + attn_out
    hn = rms_norm(h, lp["ln2"], cfg.norm_eps)
    if policy is not None:
        hn = policy.acts(hn, "block_in")
    if cfg.is_moe:
        if moe_ep and policy is not None and hasattr(policy, "rules"):
            f, aux = moe_apply_ep(lp["moe"], hn, cfg, policy.rules.mesh,
                                  policy=policy)
        else:
            f, aux = moe_apply(lp["moe"], hn, cfg, policy=policy)
    else:
        f, aux = ffn_apply(lp["ffn"], hn, cfg.activation,
                           policy=policy), 0.0
    out = h + f
    if policy is not None:
        out = policy.acts(out, "embeds")
    return out, aux, cache


def forward(params, x, cfg, positions, policy, *, collect=False, max_seq=0,
            use_pallas=False, moe_ep=False, **_):
    def layer_body(carry, lp):
        x, aux = carry
        x, a, cache = attn_block(lp, x, cfg, positions, policy, 0,
                                 use_pallas, collect, max_seq, moe_ep=moe_ep)
        return (x, aux + a), cache

    (x, aux), caches = jax.lax.scan(
        jax.checkpoint(layer_body), (x, 0.0), params["layers"])
    return x, aux, caches


def init_cache(cfg, batch, max_seq, dtype, *, kv_quant=False, **_):
    return init_kv_cache(cfg, batch, max_seq, cfg.n_layers, dtype,
                         quant=kv_quant)


def decode_layer(lp, x, cfg, cache, pos, policy, window=0):
    """One token through one attention layer.  Returns (x, its cache)."""
    xn = rms_norm(x, lp["ln1"], cfg.norm_eps)
    o, nc = attn_decode(lp["attn"], xn, cfg, cache, pos, policy=policy,
                        window=window)
    h = x + o
    hn = rms_norm(h, lp["ln2"], cfg.norm_eps)
    if cfg.is_moe:
        f, _ = moe_apply(lp["moe"], hn, cfg, policy=policy)
    else:
        f = ffn_apply(lp["ffn"], hn, cfg.activation, policy=policy)
    return h + f, nc


def decode(params, x, cfg, cache, pos, policy):
    def body(x, xs):
        lp, c = xs
        return decode_layer(lp, x, cfg, c, pos, policy)
    return jax.lax.scan(body, x, (params["layers"], cache))
