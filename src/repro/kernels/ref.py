"""Pure-jnp oracles for every Pallas kernel (the ``assert_allclose``
ground truth in tests — naive, readable, obviously-correct)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["flash_attention_ref", "rglru_scan_ref", "wkv6_ref",
           "wkv6_decode_ref", "mamba2_decode_ref", "rmsnorm_ref"]


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0):
    """q: (B, S, K, G, D) — NOT pre-scaled; k, v: (B, T, K, D)."""
    B, S, K, G, D = q.shape
    T = k.shape[1]
    s = jnp.einsum("bqkgd,btkd->bkgqt", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (D ** 0.5)
    q_pos = jnp.arange(S)[:, None]
    k_pos = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqt,btkd->bqkgd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def rglru_scan_ref(a, b):
    """h_t = a_t h_{t-1} + b_t, sequential scan (axis 1)."""
    def step(h, ab):
        a_t, b_t = ab
        h = a_t * h + b_t
        return h, h
    a32 = a.astype(jnp.float32)
    b32 = b.astype(jnp.float32)
    h0 = jnp.zeros((a.shape[0], a.shape[2]), jnp.float32)
    _, h = jax.lax.scan(step,
                        h0,
                        (jnp.moveaxis(a32, 1, 0), jnp.moveaxis(b32, 1, 0)))
    return jnp.moveaxis(h, 0, 1)


def wkv6_ref(r, k, v, w, u):
    """Sequential RWKV-6.  r,k,v,w: (BH, T, hs); u: (BH, hs).
    Returns (o fp32, final state fp32)."""
    BH, T, hs = r.shape
    s0 = jnp.zeros((BH, hs, hs), jnp.float32)

    def step(s, inp):
        r_t, k_t, v_t, w_t = (x.astype(jnp.float32) for x in inp)
        kv = k_t[..., :, None] * v_t[..., None, :]
        o = jnp.einsum("bi,bij->bj", r_t,
                       s + u.astype(jnp.float32)[..., None] * kv)
        s = w_t.astype(jnp.float32)[..., None] * s + kv
        return s, o

    s, o = jax.lax.scan(step, s0, tuple(jnp.moveaxis(t, 1, 0)
                                        for t in (r, k, v, w)))
    return jnp.moveaxis(o, 0, 1), s


def wkv6_decode_ref(state, layer, r, k, v, w, u):
    """One RWKV-6 token against layer ``layer`` of a pooled state
    (L, B, H, R, N): each head's (hs, hs) state row-major in R rows of N
    lanes (``models.rwkv6.pack_state``), so the update is written on the
    unpacked view.  r, k, v, w: (B, H, hs); u: (H, hs).  Returns
    (o (B, H, hs) fp32, the pool with the layer's rows advanced)."""
    B, H, hs = r.shape
    s = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    packed = s.shape
    s = s.reshape(B, H, hs, hs)
    r, k, v, w, u = (t.astype(jnp.float32) for t in (r, k, v, w, u))
    kv = k[..., :, None] * v[..., None, :]
    o = jnp.einsum("bhi,bhij->bhj", r, s + u[..., :, None] * kv)
    s = w[..., :, None] * s + kv
    return o, jax.lax.dynamic_update_index_in_dim(
        state, s.reshape(packed), layer, 0)


def mamba2_decode_ref(state, layer, decay, u, b, c):
    """One Mamba-2 token against block ``layer`` of a pooled state (L, B,
    H, P, N) fp32: h' = decay·h + u ⊗ B, y = h'·C, head h reading group
    h // (H / G) of b, c (B, G, N); decay: (B, H); u: (B, H, P).  Returns
    (y (B, H, P) fp32 without the D term, the pool with the block's rows
    advanced)."""
    H = u.shape[1]
    s = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    b, c = (jnp.repeat(t, H // t.shape[1], axis=1) for t in (b, c))
    s = decay[..., None, None] * s + u[..., None] * b[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", s, c, precision="highest")
    return y, jax.lax.dynamic_update_index_in_dim(state, s, layer, 0)


def rmsnorm_ref(x, w, *, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w
