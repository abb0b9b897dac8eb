"""Decoder assembly for all assigned architectures.

Layer stacking uses ``lax.scan`` over stacked parameters (one traced layer
body regardless of depth → small HLO, fast multi-pod compiles) with
per-layer ``jax.checkpoint`` remat.  The Griffin hybrid (R,R,A pattern)
scans over *periods* — a period body applies two RG-LRU layers and one
local-attention layer from separate stacked trees, so no parameter padding
is wasted (26 layers = 8 periods + 2 tail recurrent layers).  The
Nemotron-H hybrid follows its irregular block pattern (Mamba-2, MoE,
attention; one mixer per block) with a Python loop over per-block
parameters, each mixer under a ``jax.named_scope`` of its kind.

Three entry points:
  * ``loss``        — training objective (chunked CE; never materializes
                      (B, S, vocab)),
  * ``prefill``     — forward pass that also builds the serving cache
                      (KV / ring-buffer / recurrent state per layer kind),
  * ``decode_step`` — one-token step against the cache.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from .attention import (attn_apply, attn_decode, attn_decode_pooled,
                        attn_spec, init_kv_cache)
from .layers import (P, Policy, abstract_tree, axes_tree, cross_entropy,
                     ffn_apply, ffn_spec, init_tree, rms_norm)
from .mamba2 import (init_mamba2_cache, mamba2_apply, mamba2_spec,
                     mamba2_step)
from .moe import moe_apply, moe_held_apply, moe_spec
from .rglru import init_rglru_cache, rglru_decode, rglru_spec
from .rwkv6 import (init_rwkv_cache, pack_state, rwkv6_channel_mix,
                    rwkv6_spec, rwkv6_time_mix, rwkv6_time_mix_step)

__all__ = ["Transformer", "model_spec"]

LOSS_CHUNK = 512


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------

def _attn_layer_spec(cfg, n: int) -> Dict[str, Any]:
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


def _rec_layer_spec(cfg, shape_prefix, name_prefix) -> Dict[str, Any]:
    pa, pn = tuple(shape_prefix), tuple(name_prefix)
    return {
        "ln1": P(pa + (cfg.d_model,), pn + ("embed",), init="ones"),
        "ln2": P(pa + (cfg.d_model,), pn + ("embed",), init="ones"),
        "rglru": rglru_spec(cfg, pa, pn),
        "ffn": ffn_spec(cfg.d_model, cfg.d_ff, cfg.activation, pa, pn),
    }


def _rwkv_layer_spec(cfg, n: int) -> Dict[str, Any]:
    return {
        "ln1": P((n, cfg.d_model), ("layers", "embed"), init="ones"),
        "ln2": P((n, cfg.d_model), ("layers", "embed"), init="ones"),
        "rwkv": rwkv6_spec(cfg, (n,), ("layers",)),
    }


def _nemotron_blocks_spec(cfg):
    mixers = {"mamba2": mamba2_spec, "moe": moe_spec, "attn": attn_spec}
    return [{"norm": P((cfg.d_model,), ("embed",), init="ones"),
             kind: mixers[kind](cfg)} for kind in cfg.layer_kinds()]


def model_spec(cfg) -> Dict[str, Any]:
    d, v = cfg.d_model, cfg.vocab
    spec: Dict[str, Any] = {
        "final_norm": P((d,), ("embed",), init="ones"),
    }
    if cfg.input_embeds:
        spec["in_proj"] = P((d, d), ("embed", "embed_out"))
    else:
        spec["embed"] = P((v, d), ("vocab", "embed"))
    n_out = max(cfg.n_codebooks, 1) * v
    spec["head"] = P((d, n_out), ("embed", "vocab"))

    if cfg.layer_pattern == "rwkv":
        spec["layers"] = _rwkv_layer_spec(cfg, cfg.n_layers)
    elif cfg.layer_pattern == "nemotron_h":
        spec["blocks"] = _nemotron_blocks_spec(cfg)
    elif cfg.layer_pattern == "griffin":
        n_periods, tail = divmod(cfg.n_layers, 3)
        spec["periods"] = {
            "rec": _rec_layer_spec(cfg, (n_periods, 2), ("layers", None)),
            "attn": {
                "ln1": P((n_periods, d), ("layers", "embed"), init="ones"),
                "ln2": P((n_periods, d), ("layers", "embed"), init="ones"),
                "attn": attn_spec(cfg, (n_periods,), ("layers",)),
                "ffn": ffn_spec(d, cfg.d_ff, cfg.activation,
                                (n_periods,), ("layers",)),
            },
        }
        if tail:
            spec["tail"] = _rec_layer_spec(cfg, (tail,), ("layers",))
    else:
        spec["layers"] = _attn_layer_spec(cfg, cfg.n_layers)
    return spec


# ---------------------------------------------------------------------------
# Layer bodies.  Each returns (x, aux, cache_out); cache_out is {} unless
# ``collect`` (prefill) is set.
# ---------------------------------------------------------------------------

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


def _attn_with_cache(p, xn, cfg, positions, window, max_seq):
    """Causal self-attention over the prompt and the cache it leaves: a
    ring buffer of ``window`` positions, or the full cache of
    ``max_seq``."""
    from .attention import _project_qkv, blockwise_attention
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


def _attn_block(lp, x, cfg, positions, policy, window, use_pallas,
                collect=False, max_seq=0, moe_ep=False):
    xn = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if policy is not None:
        xn = policy.acts(xn, "block_in")
    if collect:
        attn_out, cache = _attn_with_cache(lp["attn"], xn, cfg, positions,
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
            from .moe import moe_apply_ep
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


def _rec_block(lp, x, cfg, policy, use_pallas, collect=False):
    from .rglru import _conv1d, _gates, rglru_scan_ref
    xn = rms_norm(x, lp["ln1"], cfg.norm_eps)
    rp = lp["rglru"]
    u = xn @ rp["w_x"]
    u, conv_state = _conv1d(rp, u, cfg.rglru_conv_width)
    a, b = _gates(rp, u, xn)
    if use_pallas and not collect:
        from repro.kernels import ops as kops
        hseq = kops.rglru_scan(a, b)
    else:
        hseq = rglru_scan_ref(a, b)
    gate = jax.nn.gelu(xn @ rp["w_gate"])
    o = (gate * hseq.astype(x.dtype)) @ rp["w_out"]
    h = x + o
    h = h + ffn_apply(lp["ffn"], rms_norm(h, lp["ln2"], cfg.norm_eps),
                      cfg.activation, policy=policy)
    if policy is not None:
        h = policy.acts(h, "embeds")
    cache = ({"h": hseq[:, -1].astype(jnp.float32), "conv": conv_state}
             if collect else {})
    return h, cache


def _rwkv_block(lp, x, cfg, policy, use_pallas, collect=False):
    o, (tm_x, state) = rwkv6_time_mix(
        lp["rwkv"]["tm"], rms_norm(x, lp["ln1"], cfg.norm_eps), cfg,
        policy=policy, use_pallas=use_pallas and not collect)
    h = x + o
    o2, cm_x = rwkv6_channel_mix(
        lp["rwkv"]["cm"], rms_norm(h, lp["ln2"], cfg.norm_eps), cfg)
    out = h + o2
    if policy is not None:
        out = policy.acts(out, "embeds")
    # the state in the pool's packed form (one relayout per request)
    cache = ({"tm_x": tm_x, "cm_x": cm_x, "state": pack_state(state)}
             if collect else {})
    return out, cache


def _nemotron_backbone(params, x, cfg, positions, collect, max_seq):
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
                o, c = _attn_with_cache(lp["attn"], xn, cfg, positions, 0,
                                        max_seq)
                c = {"k": c["k"], "v": c["v"]}
            else:
                o, c = attn_apply(lp["attn"], xn, cfg, positions), {}
        x = x + o
        if c:
            got[kind].append(c)
    if not collect:
        return x, {}

    def stack(cs):
        return jax.tree.map(lambda *t: jnp.stack(t), *cs)
    return x, dict(stack(got["mamba2"]), kv=stack(got["attn"]))


def _nemotron_decode(params, x, cfg, pool, pos):
    """One token through every block.  The pooled cache is threaded
    through the blocks and each block's rows are written back in place:
    the Mamba-2 state and conv window of block i with
    ``dynamic_update_index_in_dim``, the new key and value by a scatter
    into the KV pool."""
    n = {"mamba2": 0, "attn": 0}
    for kind, lp in zip(cfg.layer_kinds(), params["blocks"]):
        i = n.get(kind, 0)
        with jax.named_scope(kind):
            xn = rms_norm(x, lp["norm"], cfg.norm_eps)
            if kind == "mamba2":
                o, ssm, win = mamba2_step(lp["mamba2"], xn, cfg,
                                          pool["ssm"][i], pool["conv"][i])
                pool = dict(pool, ssm=jax.lax.dynamic_update_index_in_dim(
                    pool["ssm"], ssm, i, 0),
                    conv=jax.lax.dynamic_update_index_in_dim(
                        pool["conv"], win.astype(pool["conv"].dtype), i, 0))
            elif kind == "moe":
                o, _ = moe_held_apply(lp["moe"], xn, cfg)
            else:
                o, kv = attn_decode_pooled(lp["attn"], xn, cfg, pool["kv"],
                                           i, pos)
                pool = dict(pool, kv=kv)
        n[kind] = i + 1
        x = x + o
    return x, pool


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Transformer:
    cfg: Any
    use_pallas: bool = False
    moe_ep: bool = False   # expert-parallel shard_map MoE (train/prefill)
    kv_quant: bool = False  # int8 KV cache (decode)

    # ---- params ----------------------------------------------------------
    def spec(self):
        return model_spec(self.cfg)

    def init(self, key, dtype=None):
        dt = dtype or jnp.dtype(self.cfg.dtype)
        return init_tree(self.spec(), key, dt)

    def abstract_params(self, dtype=None):
        dt = dtype or jnp.dtype(self.cfg.dtype)
        return abstract_tree(self.spec(), dt)

    def logical_axes(self):
        return axes_tree(self.spec())

    # ---- embedding -------------------------------------------------------
    def _embed(self, params, batch, policy):
        cfg = self.cfg
        if cfg.input_embeds:
            x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
            x = x @ params["in_proj"]
        else:
            x = params["embed"][batch["tokens"]]
        if policy is not None:
            x = policy.acts(x, "embeds")
        return x

    def _backbone(self, params, x, positions, policy, *,
                  collect=False, max_seq=0):
        """Run all layers.  Returns (hidden, aux_loss, caches)."""
        cfg = self.cfg
        use_pallas = self.use_pallas

        if cfg.layer_pattern == "nemotron_h":
            x, caches = _nemotron_backbone(params, x, cfg, positions,
                                           collect, max_seq)
            return x, 0.0, caches

        if cfg.layer_pattern == "rwkv":
            def body(carry, lp):
                x, aux = carry
                x, cache = _rwkv_block(lp, x, cfg, policy, use_pallas,
                                       collect)
                return (x, aux), cache
            (x, aux), caches = jax.lax.scan(
                jax.checkpoint(body), (x, 0.0), params["layers"])
            return x, aux, caches

        if cfg.layer_pattern == "griffin":
            window = cfg.local_window

            def period_body(carry, lp):
                x, aux = carry
                rec, att = lp["rec"], lp["attn"]
                rc = []
                for i in range(2):
                    x, c = _rec_block(jax.tree.map(lambda t: t[i], rec), x,
                                      cfg, policy, use_pallas, collect)
                    rc.append(c)
                x, a, ac = _attn_block(att, x, cfg, positions, policy,
                                       window, use_pallas, collect, max_seq)
                cache = {"rec": (jax.tree.map(lambda p, q: jnp.stack([p, q]),
                                              *rc) if collect else {}),
                         "attn": ac}
                return (x, aux + a), cache

            (x, aux), caches = jax.lax.scan(
                jax.checkpoint(period_body), (x, 0.0), params["periods"])
            tail_caches = None
            if "tail" in params:
                def tail_body(carry, lp):
                    x, c = _rec_block(lp, carry, cfg, policy, use_pallas,
                                      collect)
                    return x, c
                x, tail_caches = jax.lax.scan(jax.checkpoint(tail_body), x,
                                              params["tail"])
            if collect:
                out = {"rec": caches["rec"], "attn": caches["attn"]}
                if tail_caches is not None:
                    out["tail"] = tail_caches
                caches = out
            return x, aux, caches

        def layer_body(carry, lp):
            x, aux = carry
            x, a, cache = _attn_block(lp, x, cfg, positions, policy, 0,
                                      use_pallas, collect, max_seq,
                                      moe_ep=self.moe_ep)
            return (x, aux + a), cache

        (x, aux), caches = jax.lax.scan(
            jax.checkpoint(layer_body), (x, 0.0), params["layers"])
        return x, aux, caches

    # ---- training --------------------------------------------------------
    def loss(self, params, batch, policy: Optional[Policy] = None):
        """batch: tokens (B,S) [or embeds (B,S,d)] + labels
        (B,S) or (B,S,n_codebooks).  Returns (loss, metrics)."""
        cfg = self.cfg
        x = self._embed(params, batch, policy)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        h, aux, _ = self._backbone(params, x, positions, policy)
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)

        labels = batch["labels"]
        n_chunks = max(S // LOSS_CHUNK, 1)
        hs = h.reshape(B, n_chunks, S // n_chunks, cfg.d_model)
        ls = labels.reshape((B, n_chunks, S // n_chunks) + labels.shape[2:])

        def chunk_loss(carry, xs):
            hc, lc = xs            # (B, C, d), (B, C[, cb])
            # cast AFTER the matmul: the convert's transpose casts the
            # cotangent back to bf16, keeping the whole backward pass (and
            # its collectives) in bf16 instead of fp32
            logits = (hc @ params["head"]).astype(jnp.float32)
            if cfg.n_codebooks:
                logits = logits.reshape(hc.shape[:2] +
                                        (cfg.n_codebooks, cfg.vocab))
            return carry + cross_entropy(logits, lc), None

        total, _ = jax.lax.scan(
            chunk_loss, 0.0,
            (jnp.moveaxis(hs, 1, 0), jnp.moveaxis(ls, 1, 0)))
        ce = total / n_chunks
        loss = ce + cfg.router_aux_weight * aux if cfg.is_moe else ce
        return loss, {"ce": ce, "aux": aux}

    # ---- serving ---------------------------------------------------------
    def prefill(self, params, batch, max_seq: int,
                policy: Optional[Policy] = None, last_pos=None):
        """Forward over the prompt; returns (last-token logits, caches).

        ``last_pos`` ((B,) int32, optional) selects the position whose
        logits are returned instead of ``S - 1`` — the serving engine
        right-pads prompts to a shape bucket and needs the logits of each
        request's REAL last token.  Causality keeps hidden states at
        positions ``<= last_pos`` independent of the padding suffix, and
        the decode-side validity mask (``cache_pos <= pos``) hides the
        padded KV entries until decode overwrites them in place."""
        cfg = self.cfg
        x = self._embed(params, batch, policy)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        h, _, caches = self._backbone(params, x, positions, policy,
                                      collect=True, max_seq=max_seq)
        hl = h[:, -1] if last_pos is None else h[jnp.arange(B), last_pos]
        h = rms_norm(hl, params["final_norm"], cfg.norm_eps)
        logits = h @ params["head"]
        if cfg.n_codebooks:
            logits = logits.reshape(B, cfg.n_codebooks, cfg.vocab)
        return logits, caches

    def init_cache(self, batch: int, max_seq: int, dtype=None):
        cfg = self.cfg
        dt = dtype or jnp.dtype(cfg.dtype)
        if cfg.layer_pattern == "rwkv":
            return init_rwkv_cache(cfg, cfg.n_layers, batch, dt)
        if cfg.layer_pattern == "nemotron_h":
            kinds = cfg.layer_kinds()
            cache = init_mamba2_cache(cfg, kinds.count("mamba2"), batch, dt)
            kv = init_kv_cache(cfg, batch, max_seq, kinds.count("attn"), dt)
            cache["kv"] = {"k": kv["k"], "v": kv["v"]}
            return cache
        if cfg.layer_pattern == "griffin":
            n_periods, tail = divmod(cfg.n_layers, 3)
            rec = init_rglru_cache(cfg, n_periods * 2, batch, dt)
            cache = {
                "rec": jax.tree.map(
                    lambda t: t.reshape((n_periods, 2) + t.shape[1:]), rec),
                "attn": init_kv_cache(cfg, batch, max_seq, n_periods, dt,
                                      window=cfg.local_window,
                                      quant=self.kv_quant),
            }
            if tail:
                cache["tail"] = init_rglru_cache(cfg, tail, batch, dt)
            return cache
        return init_kv_cache(cfg, batch, max_seq, cfg.n_layers, dt,
                             quant=self.kv_quant)

    def decode_step(self, params, cache, batch, pos,
                    policy: Optional[Policy] = None):
        """One token for the whole stack.
        batch: tokens (B,) [or embeds (B, d)]; pos: (B,) int32.
        Returns (logits (B, vocab[, cb]), new_cache)."""
        cfg = self.cfg
        if cfg.input_embeds:
            x = batch["embeds"][:, None].astype(jnp.dtype(cfg.dtype))
            x = x @ params["in_proj"]
        else:
            x = params["embed"][batch["tokens"][:, None]]
        if policy is not None:
            x = policy.acts(x, "embeds_dec")

        if cfg.layer_pattern == "nemotron_h":
            x, new_cache = _nemotron_decode(params, x, cfg, cache, pos)

        elif cfg.layer_pattern == "rwkv":
            # The pool rides in the carry and layer l's rows are updated
            # in place: scanned as xs/ys it would be stacked into a second
            # pool and copied back whole every step.
            def body(carry, xs):
                x, pool = carry
                l, lp = xs
                c = {k: jax.lax.dynamic_index_in_dim(pool[k], l, 0,
                                                     keepdims=False)
                     for k in ("tm_x", "cm_x")}
                xn = rms_norm(x, lp["ln1"], cfg.norm_eps)
                o, (tm_x, state) = rwkv6_time_mix_step(
                    lp["rwkv"]["tm"], xn, cfg, pool["state"], l,
                    x_prev=c["tm_x"], policy=policy)
                h = x + o
                hn = rms_norm(h, lp["ln2"], cfg.norm_eps)
                o2, cm_x = rwkv6_channel_mix(lp["rwkv"]["cm"], hn, cfg,
                                             x_prev=c["cm_x"])
                new = {"tm_x": tm_x, "cm_x": cm_x}
                pool = {k: jax.lax.dynamic_update_index_in_dim(
                            pool[k], new[k].astype(pool[k].dtype), l, 0)
                        for k in new} | {"state": state}
                return (h + o2, pool), None
            n_layers = cache["state"].shape[0]
            (x, new_cache), _ = jax.lax.scan(
                body, (x, cache), (jnp.arange(n_layers), params["layers"]))

        elif cfg.layer_pattern == "griffin":
            def rec_step(lp, x, c):
                xn = rms_norm(x, lp["ln1"], cfg.norm_eps)
                o, nc = rglru_decode(lp["rglru"], xn, cfg, c, policy=policy)
                x = x + o
                x = x + ffn_apply(lp["ffn"],
                                  rms_norm(x, lp["ln2"], cfg.norm_eps),
                                  cfg.activation, policy=policy)
                return x, nc

            def period(x, xs):
                lp, c = xs
                ncs = []
                for i in range(2):
                    rp = jax.tree.map(lambda t: t[i], lp["rec"])
                    rc = jax.tree.map(lambda t: t[i], c["rec"])
                    x, nc = rec_step(rp, x, rc)
                    ncs.append(nc)
                ap = lp["attn"]
                xn = rms_norm(x, ap["ln1"], cfg.norm_eps)
                o, ac = attn_decode(ap["attn"], xn, cfg, c["attn"], pos,
                                    policy=policy, window=cfg.local_window)
                x = x + o
                x = x + ffn_apply(ap["ffn"],
                                  rms_norm(x, ap["ln2"], cfg.norm_eps),
                                  cfg.activation, policy=policy)
                new_c = {"rec": jax.tree.map(
                    lambda p, q: jnp.stack([p, q]), *ncs), "attn": ac}
                return x, new_c

            x, new_p = jax.lax.scan(
                period, x, (params["periods"],
                            {"rec": cache["rec"], "attn": cache["attn"]}))
            new_cache = {"rec": new_p["rec"], "attn": new_p["attn"]}
            if "tail" in params:
                def tail_body(x, xs):
                    lp, c = xs
                    return rec_step(lp, x, c)
                x, new_tail = jax.lax.scan(tail_body, x,
                                           (params["tail"], cache["tail"]))
                new_cache["tail"] = new_tail

        else:
            def body(x, xs):
                lp, c = xs
                xn = rms_norm(x, lp["ln1"], cfg.norm_eps)
                o, nc = attn_decode(lp["attn"], xn, cfg, c, pos,
                                    policy=policy)
                h = x + o
                hn = rms_norm(h, lp["ln2"], cfg.norm_eps)
                if cfg.is_moe:
                    f, _ = moe_apply(lp["moe"], hn, cfg, policy=policy)
                else:
                    f = ffn_apply(lp["ffn"], hn, cfg.activation,
                                  policy=policy)
                return h + f, nc
            x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))

        h = rms_norm(x[:, 0], params["final_norm"], cfg.norm_eps)
        logits = h @ params["head"]
        if cfg.n_codebooks:
            logits = logits.reshape(h.shape[0], cfg.n_codebooks, cfg.vocab)
        return logits, new_cache
