"""RWKV-6 "Finch" block [arXiv:2404.05892]: attention-free time-mix with
data-dependent per-channel decay + squared-ReLU channel-mix.

Per head (head size hs), with state S ∈ R^{hs×hs}:
    o_t[j] = Σ_i r_t[i] · (S_{t-1}[i,j] + u[i]·k_t[i]·v_t[j])
    S_t    = diag(w_t) · S_{t-1} + k_t ⊗ v_t
where w_t = exp(-exp(w0 + lora_w(x̃_t))) is the data-dependent decay (the
paper's headline novelty over RWKV-5) and the x̃ inputs are ddlerp token
shifts.  Training uses a time scan (Pallas chunked kernel on real TPU:
``repro.kernels.wkv6``); decode carries (S, x_prev) per layer, S in the
serving pool's lane-dense form (``state_shape``), stepped on a TPU by
``repro.kernels.wkv6_decode``.

The stack of ``layer_pattern="rwkv"``: pre-norm time-mix and channel-mix
layers, one ``lax.scan`` over stacked parameters.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from .layers import P, Policy, rms_norm

__all__ = ["EXACT_PROMPTS", "spec", "forward", "init_cache", "decode",
           "rwkv6_spec", "rwkv6_time_mix", "rwkv6_time_mix_step",
           "rwkv6_channel_mix", "state_shape", "pack_state", "unpack_state",
           "wkv6_scan_ref"]

EXACT_PROMPTS = True

LORA_R = 32
_MIX = ("w", "k", "v", "r", "g")


def rwkv6_spec(cfg, prefix_shape=(), prefix_names=()) -> Dict[str, Any]:
    pa, pn = tuple(prefix_shape), tuple(prefix_names)
    d, f = cfg.d_model, cfg.d_ff
    tm: Dict[str, Any] = {
        "mu_x": P(pa + (d,), pn + ("embed",), init="zeros"),
        "w0":   P(pa + (d,), pn + ("embed",), init="zeros"),
        "u":    P(pa + (d,), pn + ("embed",), init="zeros"),
        "ln_x": P(pa + (d,), pn + ("embed",), init="ones"),
        "w_out": P(pa + (d, d), pn + ("heads", "embed")),
    }
    for z in _MIX:
        tm[f"mu_{z}"] = P(pa + (d,), pn + ("embed",), init="zeros")
        tm[f"lora_a_{z}"] = P(pa + (d, LORA_R), pn + ("embed", None))
        tm[f"lora_b_{z}"] = P(pa + (LORA_R, d), pn + (None, "embed"),
                              init="zeros")
        if z != "w":
            tm[f"w_{z}"] = P(pa + (d, d), pn + ("embed", "heads"))
    cm = {
        "mu_k": P(pa + (d,), pn + ("embed",), init="zeros"),
        "mu_r": P(pa + (d,), pn + ("embed",), init="zeros"),
        "w_k": P(pa + (d, f), pn + ("embed", "ffn")),
        "w_v": P(pa + (f, d), pn + ("ffn", "embed")),
        "w_r": P(pa + (d, d), pn + ("embed", "embed_out")),
    }
    return {"tm": tm, "cm": cm}


def _token_shift(x, x_prev):
    """x: (B, T, d); x_prev: (B, d) last token of the previous segment.
    Returns the previous-token tensor aligned with x."""
    return jnp.concatenate([x_prev[:, None], x[:, :-1]], axis=1)


def _ddlerp(p, x, sx, z: str):
    """Data-dependent lerp (RWKV-6): mix x with shifted sx."""
    xx = sx - x
    inner = x + xx * p["mu_x"]
    lora = jnp.tanh(inner @ p[f"lora_a_{z}"]) @ p[f"lora_b_{z}"]
    return x + xx * (p[f"mu_{z}"] + lora)


def wkv6_scan_ref(r, k, v, w, u):
    """Sequential oracle.  r,k,v,w: (B, T, H, hs); u: (H, hs) bonus.
    Returns (o (B,T,H,hs), final state (B,H,hs,hs))."""
    B, T, H, hs = r.shape
    return _wkv_with_state(r, k, v, w, u,
                           jnp.zeros((B, H, hs, hs), jnp.float32))


def _time_mix_inputs(p, x, sx, cfg):
    """r, k, v, w (B, T, H, hs), the gate g (B, T, d) and the bonus u
    (H, hs) of the time mix of x against its shifted sx."""
    B, T, d = x.shape
    hs = cfg.rwkv_head_size
    H = d // hs
    xw = _ddlerp(p, x, sx, "w")
    xk = _ddlerp(p, x, sx, "k")
    xv = _ddlerp(p, x, sx, "v")
    xr = _ddlerp(p, x, sx, "r")
    xg = _ddlerp(p, x, sx, "g")

    r = (xr @ p["w_r"]).reshape(B, T, H, hs)
    k = (xk @ p["w_k"]).reshape(B, T, H, hs)
    v = (xv @ p["w_v"]).reshape(B, T, H, hs)
    g = jax.nn.silu(xg @ p["w_g"])
    dec = p["w0"] + jnp.tanh(xw @ p["lora_a_w"]) @ p["lora_b_w"]
    w = jnp.exp(-jnp.exp(dec.astype(jnp.float32))).reshape(B, T, H, hs)
    return r, k, v, w, g, p["u"].reshape(H, hs)


def _time_mix_out(p, o, g, x, policy):
    """Per-head norm of the wkv output o (B, T, H, hs), gated and
    projected."""
    B, T, d = x.shape
    o = rms_norm(o.astype(x.dtype), jnp.ones((o.shape[-1],), x.dtype)
                 ).reshape(B, T, d) * p["ln_x"]
    if policy is not None:
        o = policy.acts(o, "embeds")
    return (o * g) @ p["w_out"]


def rwkv6_time_mix(p, x, cfg, *, policy: Optional[Policy] = None,
                   use_pallas: bool = False):
    """x: (B, T, d), from a zero state.  Returns (out, (new_x_prev,
    final state (B, H, hs, hs)))."""
    B, T, d = x.shape
    x_prev = jnp.zeros((B, d), x.dtype)
    r, k, v, w, g, u = _time_mix_inputs(p, x, _token_shift(x, x_prev), cfg)
    if use_pallas:
        from repro.kernels import ops as kops
        o, new_state = kops.wkv6(r, k, v, w, u)
    else:
        o, new_state = wkv6_scan_ref(r, k, v, w, u)
    return _time_mix_out(p, o, g, x, policy), (x[:, -1], new_state)


def rwkv6_time_mix_step(p, x, cfg, pool, layer, *, x_prev,
                        policy: Optional[Policy] = None):
    """One token, x: (B, 1, d), against layer ``layer`` of the pooled
    state leaf ``pool`` (L, B, H, *state_shape(hs)), which is updated in
    place.  Returns (out, (x[:, -1], pool))."""
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref
    r, k, v, w, g, u = _time_mix_inputs(p, x, x_prev[:, None], cfg)
    # the kernel is chosen by the compile target, not by the process's
    # default backend: a CPU process may compile for a TPU
    o, pool = jax.lax.platform_dependent(
        pool, layer, r[:, 0], k[:, 0], v[:, 0], w[:, 0], u,
        tpu=kops.wkv6_decode, default=kref.wkv6_decode_ref)
    return _time_mix_out(p, o[:, None], g, x, policy), (x[:, -1], pool)


def _wkv_with_state(r, k, v, w, u, s0):
    """The recurrence token by token from the state s0 (B, H, hs, hs)."""

    def step(s, inp):
        r_t, k_t, v_t, w_t = inp
        kv = k_t[..., :, None] * v_t[..., None, :]
        o = jnp.einsum("bhi,bhij->bhj", r_t, s + u[..., :, None] * kv)
        s = w_t[..., :, None] * s + kv
        return s, o

    rr, kk, vv, ww = (jnp.moveaxis(t.astype(jnp.float32), 1, 0)
                      for t in (r, k, v, w))
    s, o = jax.lax.scan(step, s0.astype(jnp.float32), (rr, kk, vv, ww))
    return jnp.moveaxis(o, 0, 1), s


def rwkv6_channel_mix(p, x, cfg, *, x_prev=None):
    """Squared-ReLU channel mix with simple token-shift lerp."""
    B, T, d = x.shape
    if x_prev is None:
        x_prev = jnp.zeros((B, d), x.dtype)
    sx = _token_shift(x, x_prev)
    xx = sx - x
    xk = x + xx * p["mu_k"]
    xr = x + xx * p["mu_r"]
    kk = jnp.square(jax.nn.relu(xk @ p["w_k"]))
    return jax.nn.sigmoid(xr @ p["w_r"]) * (kk @ p["w_v"]), x[:, -1]


def state_shape(hs: int):
    """How a head's (hs, hs) state is stored in the serving pool.  Where
    hs divides 128 it is packed row-major into hs·hs/128 rows of 128
    lanes, so the minor axis fills the TPU's lanes and the pool is laid
    out batch-major: one request's state is then a contiguous row of the
    pool, and inserting it writes only that row.  A minor axis of 64
    would instead put the batch axis in the lanes."""
    if hs < 128 and 128 % hs == 0:
        return (hs * hs // 128, 128)
    return (hs, hs)


def pack_state(s):
    """(..., hs, hs) → (..., *state_shape(hs)): a row-major reshape, so
    lane n of row p holds S[p·(128/hs) + n // hs, n % hs]."""
    return s.reshape(s.shape[:-2] + state_shape(s.shape[-1]))


def unpack_state(s, hs: int):
    return s.reshape(s.shape[:-2] + (hs, hs))


def spec(cfg) -> Dict[str, Any]:
    n = cfg.n_layers
    return {"layers": {
        "ln1": P((n, cfg.d_model), ("layers", "embed"), init="ones"),
        "ln2": P((n, cfg.d_model), ("layers", "embed"), init="ones"),
        "rwkv": rwkv6_spec(cfg, (n,), ("layers",)),
    }}


def forward(params, x, cfg, positions, policy, *, collect=False,
            use_pallas=False, **_):
    def body(carry, lp):
        x, aux = carry
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
        return (out, aux), cache
    (x, aux), caches = jax.lax.scan(
        jax.checkpoint(body), (x, 0.0), params["layers"])
    return x, aux, caches


def init_cache(cfg, batch: int, max_seq: int, dtype=jnp.bfloat16, **_):
    d = cfg.d_model
    hs = cfg.rwkv_head_size
    H, n = d // hs, cfg.n_layers
    return {
        "tm_x": jnp.zeros((n, batch, d), dtype),
        "cm_x": jnp.zeros((n, batch, d), dtype),
        "state": jnp.zeros((n, batch, H) + state_shape(hs), jnp.float32),
    }


def decode(params, x, cfg, cache, pos, policy):
    # The pool rides in the carry and layer l's rows are updated in place:
    # scanned as xs/ys it would be stacked into a second pool and copied
    # back whole every step.
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
    return x, new_cache
