"""Attention: GQA with RoPE, blockwise online-softmax (flash algorithm in
jnp — no S×S materialization, so 32k prefill fits), sliding-window local
attention, and sequence-shardable decode against a KV cache.

On real TPU the blockwise path is replaced by the Pallas flash kernel
(``repro.kernels.flash_attention``) via ``use_pallas=True``; both are
validated against the same oracle in tests.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from .layers import P, Policy, apply_rope, rms_norm

__all__ = ["attn_spec", "attn_apply", "attn_with_cache", "attn_decode",
           "attn_decode_pooled", "init_kv_cache", "blockwise_attention",
           "decode_attention"]

NEG_INF = -1e30


def attn_spec(cfg, prefix_shape=(), prefix_names=()) -> Dict[str, P]:
    pa, pn = tuple(prefix_shape), tuple(prefix_names)
    d, q = cfg.d_model, cfg.n_heads * cfg.d_head
    spec = {
        "w_q": P(pa + (d, cfg.n_heads, cfg.d_head),
                 pn + ("embed", "heads", "head_dim")),
        "w_k": P(pa + (d, cfg.n_kv_heads, cfg.d_head),
                 pn + ("embed", "kv_heads", "head_dim")),
        "w_v": P(pa + (d, cfg.n_kv_heads, cfg.d_head),
                 pn + ("embed", "kv_heads", "head_dim")),
        "w_o": P(pa + (cfg.n_heads, cfg.d_head, d),
                 pn + ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        spec["b_q"] = P(pa + (cfg.n_heads, cfg.d_head),
                        pn + ("heads", "head_dim"), init="zeros")
        spec["b_k"] = P(pa + (cfg.n_kv_heads, cfg.d_head),
                        pn + ("kv_heads", "head_dim"), init="zeros")
        spec["b_v"] = P(pa + (cfg.n_kv_heads, cfg.d_head),
                        pn + ("kv_heads", "head_dim"), init="zeros")
    if cfg.qk_norm:
        spec["qnorm"] = P(pa + (cfg.d_head,), pn + ("head_dim",),
                          init="ones")
        spec["knorm"] = P(pa + (cfg.d_head,), pn + ("head_dim",),
                          init="ones")
    return spec


def _project_qkv(params, x, cfg, positions, policy=None):
    def hint(w, kind):
        if policy is None:
            return w
        return policy.acts(w, kind)
    q = jnp.einsum("bsd,dhk->bshk", x, hint(params["w_q"], "w_attn_q"))
    k = jnp.einsum("bsd,dhk->bshk", x, hint(params["w_k"], "w_attn_kv"))
    v = jnp.einsum("bsd,dhk->bshk", x, hint(params["w_v"], "w_attn_kv"))
    if "b_q" in params:
        q = q + params["b_q"]
        k = k + params["b_k"]
        v = v + params["b_v"]
    if "qnorm" in params:
        q = rms_norm(q, params["qnorm"])
        k = rms_norm(k, params["knorm"])
    if cfg.rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def blockwise_attention(q, k, v, *, causal: bool = True, window: int = 0,
                        q_chunk: int = 512, kv_chunk: int = 512,
                        q_offset: int = 0):
    """Flash-style attention without S×S materialization.

    q: (B, S, K, G, D) — G query heads per KV head; k, v: (B, T, K, D).
    Online softmax over KV chunks (inner scan), mapped over Q chunks.
    """
    B, S, K, G, D = q.shape
    T = k.shape[1]
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, T)
    assert S % q_chunk == 0 and T % kv_chunk == 0, (S, q_chunk, T, kv_chunk)
    nq, nk = S // q_chunk, T // kv_chunk
    scale = 1.0 / (D ** 0.5)
    qf = (q * scale).reshape(B, nq, q_chunk, K, G, D)
    kf = k.reshape(B, nk, kv_chunk, K, D)
    vf = v.reshape(B, nk, kv_chunk, K, D)
    out_dtype = q.dtype

    def one_q_block(args):
        qi, qblk = args            # qblk: (B, qc, K, G, D)
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, kv):
            o, m, lse = carry
            ki, kblk, vblk = kv
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bqkgd,btkd->bkgqt",
                           qblk.astype(jnp.float32),
                           kblk.astype(jnp.float32))
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window:
                mask &= (q_pos[:, None] - k_pos[None, :]) < window
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = lse * corr + p.sum(axis=-1)
            pv = jnp.einsum("bkgqt,btkd->bkgqd", p,
                            vblk.astype(jnp.float32))
            o_new = o * corr[..., None] + pv
            return (o_new, m_new, l_new), None

        o0 = jnp.zeros((B, K, G, q_chunk, D), jnp.float32)
        m0 = jnp.full((B, K, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, q_chunk), jnp.float32)
        (o, m, lse), _ = jax.lax.scan(
            kv_step, (o0, m0, l0),
            (jnp.arange(nk), jnp.moveaxis(kf, 1, 0),
             jnp.moveaxis(vf, 1, 0)))
        o = o / jnp.maximum(lse[..., None], 1e-30)
        return jnp.moveaxis(o, 3, 1)           # (B, qc, K, G, D)

    o = jax.lax.map(one_q_block,
                    (jnp.arange(nq), jnp.moveaxis(qf, 1, 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, K, G, D)
    return o.astype(out_dtype)


def attn_apply(params, x, cfg, positions, *,
               policy: Optional[Policy] = None, window: int = 0,
               use_pallas: bool = False):
    """Training / prefill self-attention.  x: (B, S, d_model)."""
    B, S, _ = x.shape
    K, G = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    q, k, v = _project_qkv(params, x, cfg, positions, policy=policy)
    q = q.reshape(B, S, K, G, cfg.d_head)
    if policy is not None:
        q = policy.acts(q, "q5")
        k = policy.acts(k, "kv4")
        v = policy.acts(v, "kv4")
    if use_pallas:
        from repro.kernels import ops as kops
        o = kops.flash_attention(q, k, v, causal=True, window=window)
    else:
        o = blockwise_attention(q, k, v, causal=True, window=window)
    o = o.reshape(B, S, cfg.n_heads, cfg.d_head)
    w_o = params["w_o"] if policy is None else policy.acts(
        params["w_o"], "w_attn_out")
    return jnp.einsum("bshk,hkd->bsd", o, w_o)


def _ring_cache_from_kv(k, v, window: int):
    """Pack the last ``window`` (roped) k/v into a ring buffer laid out by
    absolute-position % window (matching the decode-side slot rule)."""
    B, S, K, D = k.shape
    W = min(window, S)
    pos = jnp.arange(S - W, S)
    slot = pos % window if S >= window else pos
    ck = jnp.zeros((B, window, K, D), k.dtype).at[:, slot].set(k[:, -W:])
    cv = jnp.zeros((B, window, K, D), v.dtype).at[:, slot].set(v[:, -W:])
    cpos = (jnp.zeros((B, window), jnp.int32) - 1).at[:, slot].set(
        jnp.broadcast_to(pos, (B, W)))
    return {"k": ck, "v": cv, "pos": cpos}


def _full_cache_from_kv(k, v, max_seq: int):
    B, S, K, D = k.shape
    ck = jnp.zeros((B, max_seq, K, D), k.dtype).at[:, :S].set(k)
    cv = jnp.zeros((B, max_seq, K, D), v.dtype).at[:, :S].set(v)
    cpos = (jnp.zeros((B, max_seq), jnp.int32) - 1).at[:, :S].set(
        jnp.arange(S))
    return {"k": ck, "v": cv, "pos": cpos}


def attn_with_cache(p, xn, cfg, positions, window, max_seq):
    """Causal self-attention over the prompt and the cache it leaves: a
    ring buffer of ``window`` positions, or the full cache of
    ``max_seq``."""
    B, S, _ = xn.shape
    K, G = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    q, k, v = _project_qkv(p, xn, cfg, positions)
    qr = q.reshape(B, S, K, G, cfg.d_head)
    o = blockwise_attention(qr, k, v, causal=True, window=window)
    o = o.reshape(B, S, cfg.n_heads, cfg.d_head)
    attn_out = jnp.einsum("bshk,hkd->bsd", o, p["w_o"])
    cache = (_ring_cache_from_kv(k, v, window) if window
             else _full_cache_from_kv(k, v, max_seq))
    return attn_out, cache


# ---------------------------------------------------------------------------
# Decode path: single-token step against a KV cache.
# ---------------------------------------------------------------------------

def init_kv_cache(cfg, batch: int, max_seq: int, n_attn_layers: int,
                  dtype=jnp.bfloat16, window: int = 0,
                  quant: bool = False):
    """Full cache (B, T, K, D) per layer — or ring buffer of ``window``.

    ``quant``: int8 storage with per-(token, head) scales (KIVI-style) —
    halves the decode step's dominant HBM term (§Perf iteration 'kvq8');
    dequantization happens inside the attention fp32 einsum."""
    T = min(max_seq, window) if window else max_seq
    shape = (n_attn_layers, batch, T, cfg.n_kv_heads, cfg.d_head)
    if quant:
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:-1], jnp.float32),
            "v_scale": jnp.zeros(shape[:-1], jnp.float32),
            "pos": jnp.zeros((n_attn_layers, batch, T), jnp.int32) - 1,
        }
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "pos": jnp.zeros((n_attn_layers, batch, T), jnp.int32) - 1,
    }


def _quantize_kv(x):
    """x: (B, K, D) one token → (int8, scale (B, K))."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def decode_attention(q, k_cache, v_cache, cache_pos, pos, *, window: int = 0):
    """q: (B, 1, K, G, D); caches: (B, T, K, D); cache_pos: (B, T) absolute
    positions stored in each cache slot (-1 = empty); pos: (B,) current
    position.  Full-length masked attention — T is static, the validity
    mask handles both causal order and (for ring buffers) the window."""
    B, _, K, G, D = q.shape
    scale = 1.0 / (D ** 0.5)
    s = jnp.einsum("bqkgd,btkd->bkgqt", (q * scale).astype(jnp.float32),
                   k_cache.astype(jnp.float32))      # (B,K,G,1,T)
    valid = (cache_pos >= 0) & (cache_pos <= pos[:, None])
    if window:
        valid &= cache_pos > (pos[:, None] - window)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqt,btkd->bqkgd", p, v_cache.astype(jnp.float32))
    return o.astype(q.dtype)


def attn_decode(params, x, cfg, cache, pos, *,
                policy: Optional[Policy] = None, window: int = 0):
    """One decode step.  x: (B, 1, d_model); pos: (B,) int32 current index.
    cache: dict(k, v[, k_scale, v_scale], pos) for THIS layer.
    Returns (out (B,1,d), new_cache)."""
    B = x.shape[0]
    K, G = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    q, k, v = _project_qkv(params, x, cfg, pos[:, None])
    T = cache["k"].shape[1]
    quant = "k_scale" in cache
    slot = (pos % T) if window else pos             # ring buffer for local
    b_idx = jnp.arange(B)
    new_cache = {}
    if quant:
        kq, ks = _quantize_kv(k[:, 0])
        vq, vs = _quantize_kv(v[:, 0])
        new_k = cache["k"].at[b_idx, slot].set(kq)
        new_v = cache["v"].at[b_idx, slot].set(vq)
        new_ks = cache["k_scale"].at[b_idx, slot].set(ks)
        new_vs = cache["v_scale"].at[b_idx, slot].set(vs)
        new_cache["k_scale"], new_cache["v_scale"] = new_ks, new_vs
        att_k = new_k.astype(jnp.float32) * new_ks[..., None]
        att_v = new_v.astype(jnp.float32) * new_vs[..., None]
    else:
        new_k = cache["k"].at[b_idx, slot].set(k[:, 0])
        new_v = cache["v"].at[b_idx, slot].set(v[:, 0])
        att_k, att_v = new_k, new_v
    new_cpos = cache["pos"].at[b_idx, slot].set(pos)
    if policy is not None:
        new_k = policy.acts(new_k, "kvcache")
        new_v = policy.acts(new_v, "kvcache")
        att_k = policy.acts(att_k, "kvcache")
        att_v = policy.acts(att_v, "kvcache")
    q = q.reshape(B, 1, K, G, cfg.d_head)
    o = decode_attention(q, att_k, att_v, new_cpos, pos, window=window)
    o = o.reshape(B, 1, cfg.n_heads, cfg.d_head)
    out = jnp.einsum("bshk,hkd->bsd", o, params["w_o"])
    new_cache.update({"k": new_k, "v": new_v, "pos": new_cpos})
    return out, new_cache


def attn_decode_pooled(params, x, cfg, pool, layer: int, pos):
    """One decode step of attention layer ``layer`` against the pooled
    full cache {k, v} of every attention layer, (layers, B, T, K, D), in
    which slot t holds position t.  The new key and value are scattered
    into the pool at (layer, row, pos), so a donated pool is updated in
    place and no layer-sized copy is made; positions up to ``pos`` are
    valid.  Returns (out (B, 1, d), new pool)."""
    B = x.shape[0]
    K, G = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    q, k, v = _project_qkv(params, x, cfg, pos[:, None])
    b_idx = jnp.arange(B)
    pool = {"k": pool["k"].at[layer, b_idx, pos].set(k[:, 0]),
            "v": pool["v"].at[layer, b_idx, pos].set(v[:, 0])}
    T = pool["k"].shape[2]
    q = q.reshape(B, 1, K, G, cfg.d_head)
    o = decode_attention(q, pool["k"][layer], pool["v"][layer],
                         jnp.broadcast_to(jnp.arange(T), (B, T)), pos)
    o = o.reshape(B, 1, cfg.n_heads, cfg.d_head)
    return jnp.einsum("bshk,hkd->bsd", o, params["w_o"]), pool
