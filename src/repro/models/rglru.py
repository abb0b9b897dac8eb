"""Griffin stack (``layer_pattern="griffin"``, RecurrentGemma): the
recurrent block, temporal conv + RG-LRU gated linear recurrence
[arXiv:2402.19427], in an R,R,A layer pattern with local attention.

The RG-LRU diagonal recurrence
    h_t = a_t ⊙ h_{t-1} + sqrt(1 - a_t²) ⊙ (i_t ⊙ x_t)
    a_t = exp(-c · softplus(Λ) ⊙ σ(W_a x_t))
is associative → training uses ``jax.lax.associative_scan`` (parallel,
O(log T) depth); decode is a single-step update carrying (h, conv window).
The full Griffin block is the gated variant:
    out = W_out ( GeLU(W_gate x) ⊙ RG-LRU(conv1d(W_x x)) ).
On real TPU the scan is the Pallas kernel ``repro.kernels.rglru_scan``.

The stack scans over periods of two recurrent layers and one attention
layer, stacked apart (26 layers = 8 periods + a tail of 2 recurrent).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from .attention import init_kv_cache
from .dense import attn_block, decode_layer, layer_spec
from .layers import P, Policy, ffn_apply, ffn_spec, rms_norm

__all__ = ["EXACT_PROMPTS", "spec", "forward", "init_cache", "decode",
           "rglru_spec", "rglru_decode", "init_rglru_cache",
           "rglru_scan_ref", "RGLRU_C"]

EXACT_PROMPTS = True
RGLRU_C = 8.0


def rglru_spec(cfg, prefix_shape=(), prefix_names=()) -> Dict[str, Any]:
    pa, pn = tuple(prefix_shape), tuple(prefix_names)
    d = cfg.d_model
    w = cfg.rglru_conv_width
    return {
        "w_x":    P(pa + (d, d), pn + ("embed", "rnn")),
        "w_gate": P(pa + (d, d), pn + ("embed", "rnn")),
        "w_out":  P(pa + (d, d), pn + ("rnn", "embed")),
        "conv_w": P(pa + (w, d), pn + (None, "rnn"), init="zeros"),
        "conv_b": P(pa + (d,), pn + ("rnn",), init="zeros"),
        "w_a":    P(pa + (d, d), pn + ("embed", "rnn")),
        "w_i":    P(pa + (d, d), pn + ("embed", "rnn")),
        "lam":    P(pa + (d,), pn + ("rnn",), init="ones"),
    }


def _gates(params, u, x):
    """u: conv output (..., d) drives the recurrence input; x: raw block
    input drives the gates (a_t, i_t)."""
    a = jnp.exp(-RGLRU_C * jax.nn.softplus(params["lam"]).astype(jnp.float32)
                * jax.nn.sigmoid(x @ params["w_a"]).astype(jnp.float32))
    i = jax.nn.sigmoid(x @ params["w_i"]).astype(jnp.float32)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.square(a), 1e-12)) * \
        (i * u.astype(jnp.float32))
    return a, b


def rglru_scan_ref(a, b, h0=None):
    """h_t = a_t h_{t-1} + b_t along axis 1 (time).  a, b: (B, T, D)."""
    if h0 is not None:
        b = b.at[:, 0].add(a[:, 0] * h0)

    def combine(lhs, rhs):
        a_l, b_l = lhs
        a_r, b_r = rhs
        return a_l * a_r, a_r * b_l + b_r

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h


def _conv1d(params, x, width: int, state=None):
    """Causal depthwise temporal conv.  x: (B, T, d).  ``state``: (B, w-1, d)
    previous inputs for decode continuity."""
    if state is None:
        pad = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * params["conv_w"][i]
              for i in range(width))
    return out + params["conv_b"], xp[:, -(width - 1):]


def init_rglru_cache(cfg, n_layers: int, batch: int, dtype=jnp.bfloat16):
    d, w = cfg.d_model, cfg.rglru_conv_width
    return {
        "h": jnp.zeros((n_layers, batch, d), jnp.float32),
        "conv": jnp.zeros((n_layers, batch, w - 1, d), dtype),
    }


def rglru_decode(params, x, cfg, cache, *,
                 policy: Optional[Policy] = None):
    """One step.  x: (B, 1, d); cache: dict(h (B,d), conv (B,w-1,d))."""
    u = x @ params["w_x"]
    u, conv_state = _conv1d(params, u, cfg.rglru_conv_width,
                            state=cache["conv"])
    a, b = _gates(params, u, x)
    h = a[:, 0] * cache["h"] + b[:, 0]                 # (B, d) fp32
    gate = jax.nn.gelu(x @ params["w_gate"])
    out = (gate * h[:, None].astype(x.dtype)) @ params["w_out"]
    return out, {"h": h, "conv": conv_state}


def _rec_layer_spec(cfg, shape_prefix, name_prefix) -> Dict[str, Any]:
    pa, pn = tuple(shape_prefix), tuple(name_prefix)
    return {
        "ln1": P(pa + (cfg.d_model,), pn + ("embed",), init="ones"),
        "ln2": P(pa + (cfg.d_model,), pn + ("embed",), init="ones"),
        "rglru": rglru_spec(cfg, pa, pn),
        "ffn": ffn_spec(cfg.d_model, cfg.d_ff, cfg.activation, pa, pn),
    }


def spec(cfg) -> Dict[str, Any]:
    n_periods, tail = divmod(cfg.n_layers, 3)
    out = {"periods": {
        "rec": _rec_layer_spec(cfg, (n_periods, 2), ("layers", None)),
        "attn": layer_spec(cfg, n_periods),
    }}
    if tail:
        out["tail"] = _rec_layer_spec(cfg, (tail,), ("layers",))
    return out


def _rec_block(lp, x, cfg, policy, use_pallas, collect=False):
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


def forward(params, x, cfg, positions, policy, *, collect=False, max_seq=0,
            use_pallas=False, **_):
    window = cfg.local_window

    def period_body(carry, lp):
        x, aux = carry
        rec, att = lp["rec"], lp["attn"]
        rc = []
        for i in range(2):
            x, c = _rec_block(jax.tree.map(lambda t: t[i], rec), x,
                              cfg, policy, use_pallas, collect)
            rc.append(c)
        x, a, ac = attn_block(att, x, cfg, positions, policy,
                              window, use_pallas, collect, max_seq)
        cache = {"rec": (jax.tree.map(lambda p, q: jnp.stack([p, q]),
                                      *rc) if collect else {}),
                 "attn": ac}
        return (x, aux + a), cache

    (x, aux), caches = jax.lax.scan(
        jax.checkpoint(period_body), (x, 0.0), params["periods"])
    if "tail" in params:
        def tail_body(carry, lp):
            return _rec_block(lp, carry, cfg, policy, use_pallas, collect)
        x, tail_caches = jax.lax.scan(jax.checkpoint(tail_body), x,
                                      params["tail"])
        if collect:
            caches = dict(caches, tail=tail_caches)
    return x, aux, caches


def init_cache(cfg, batch, max_seq, dtype, *, kv_quant=False, **_):
    n_periods, tail = divmod(cfg.n_layers, 3)
    rec = init_rglru_cache(cfg, n_periods * 2, batch, dtype)
    cache = {
        "rec": jax.tree.map(
            lambda t: t.reshape((n_periods, 2) + t.shape[1:]), rec),
        "attn": init_kv_cache(cfg, batch, max_seq, n_periods, dtype,
                              window=cfg.local_window, quant=kv_quant),
    }
    if tail:
        cache["tail"] = init_rglru_cache(cfg, tail, batch, dtype)
    return cache


def decode(params, x, cfg, cache, pos, policy):
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
        x, ac = decode_layer(lp["attn"], x, cfg, c["attn"], pos, policy,
                             window=cfg.local_window)
        new_c = {"rec": jax.tree.map(
            lambda p, q: jnp.stack([p, q]), *ncs), "attn": ac}
        return x, new_c

    x, new_cache = jax.lax.scan(period, x, (params["periods"], {
        "rec": cache["rec"], "attn": cache["attn"]}))
    if "tail" in params:
        def tail_body(x, xs):
            return rec_step(xs[0], x, xs[1])
        x, new_tail = jax.lax.scan(tail_body, x,
                                   (params["tail"], cache["tail"]))
        new_cache["tail"] = new_tail
    return x, new_cache
