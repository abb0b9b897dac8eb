"""Mamba-2 mixer as Nemotron-H uses it [arXiv:2405.21060].

    z, xBC, dt   = x W_in                       (widths di, di + 2GN, H)
    xBC          = silu(conv1d(xBC) + b)        depthwise, causal, kernel w
    x, B, C      = split(xBC)                   B, C: G groups of N, each
                                                shared by H/G heads
    dt           = softplus(dt + dt_bias)       no clamp
    per head h:  h_t = exp(dt_t A) h_{t-1} + dt_t x_t ⊗ B_t,   A = -exp(A_log)
                 y_t = C_t · h_t + D x_t
    out          = rmsnorm_grouped(y ⊙ silu(z)) W_out   groups of di/G

The state h is (heads, head_dim, state) per sequence, kept in float32.
Prefill runs the chunked SSD form (chunks of ``CHUNK`` tokens: within a
chunk the recurrence is a masked product of decays, across chunks a scan
carries the state); decode is one step against the serving pool's state,
stepped in place on a TPU by ``repro.kernels.mamba2_decode``, and the
conv window of the last w-1 inputs.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from .layers import P
from .rglru import _conv1d

__all__ = ["mamba2_spec", "mamba2_apply", "mamba2_step", "conv_dim",
           "init_mamba2_cache", "CHUNK"]

CHUNK = 128


def conv_dim(cfg) -> int:
    return cfg.mamba_inner + 2 * cfg.ssm_groups * cfg.ssm_state


def mamba2_spec(cfg) -> Dict[str, Any]:
    d, di, H = cfg.d_model, cfg.mamba_inner, cfg.mamba_heads
    cd = conv_dim(cfg)
    return {
        "in_proj": P((d, di + cd + H), ("embed", "rnn")),
        "conv_w": P((cfg.mamba_conv, cd), (None, "rnn")),
        "conv_b": P((cd,), ("rnn",), init="zeros"),
        "dt_bias": P((H,), (None,), init="zeros"),
        "A_log": P((H,), (None,), init="zeros"),
        "D": P((H,), (None,), init="ones"),
        "norm": P((di,), ("rnn",), init="ones"),
        "out_proj": P((di, d), ("rnn", "embed")),
    }


def init_mamba2_cache(cfg, n_layers: int, batch: int, dtype=jnp.bfloat16):
    H, Pd, N = cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state
    return {
        "ssm": jnp.zeros((n_layers, batch, H, Pd, N), jnp.float32),
        "conv": jnp.zeros((n_layers, batch, cfg.mamba_conv - 1,
                           conv_dim(cfg)), dtype),
    }


def _split_in(p, x, cfg):
    di, H = cfg.mamba_inner, cfg.mamba_heads
    zxbcdt = x @ p["in_proj"]
    return (zxbcdt[..., :di], zxbcdt[..., di:-H],
            zxbcdt[..., -H:].astype(jnp.float32))


def _split_xbc(xbc, cfg):
    """(B, T, conv_dim) -> x (B, T, H, P), B and C (B, T, G, N), float32."""
    di, G, N = cfg.mamba_inner, cfg.ssm_groups, cfg.ssm_state
    lead = xbc.shape[:-1]
    xbc = xbc.astype(jnp.float32)
    x = xbc[..., :di].reshape(lead + (cfg.mamba_heads, cfg.mamba_head_dim))
    b = xbc[..., di:di + G * N].reshape(lead + (G, N))
    c = xbc[..., di + G * N:].reshape(lead + (G, N))
    return x, b, c


def _out(p, y, z, cfg, dtype):
    """Gated grouped RMSNorm, then the output projection.  y: (..., H, P)
    float32; z: (..., di)."""
    lead = y.shape[:-2]
    G = cfg.ssm_groups
    g = y.reshape(lead + (cfg.mamba_inner,)) * jax.nn.silu(
        z.astype(jnp.float32))
    g = g.reshape(lead + (G, cfg.mamba_inner // G))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                          + cfg.norm_eps)
    g = g.reshape(lead + (cfg.mamba_inner,)) * p["norm"].astype(jnp.float32)
    return g.astype(dtype) @ p["out_proj"]


def _heads(t, cfg):
    """Group-shared (..., G, N) -> per head (..., H, N)."""
    return jnp.repeat(t, cfg.mamba_heads // cfg.ssm_groups, axis=-2)


def _ssd(x, dt, a, b, c, chunk: int):
    """Chunked scan.  x: (B, T, H, P); dt: (B, T, H); a: (H,); b, c:
    (B, T, H, N), all float32.  Returns y (B, T, H, P) without the D term,
    and the final state (B, H, P, N)."""
    Bz, T, H, Pd = x.shape
    N = b.shape[-1]
    L = min(chunk, T)
    pad = -T % L
    if pad:
        # dt = 0 past the end: no decay and no input, the state is kept
        x, dt, b, c = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] *
                               (t.ndim - 2)) for t in (x, dt, b, c))
    nc = (T + pad) // L

    def chunks(t):
        return jnp.moveaxis(t.reshape((Bz, nc, L) + t.shape[2:]), 1, 0)

    mask = jnp.tril(jnp.ones((L, L), bool))

    def step(h, inp):
        xc, dtc, bc, cc = inp                          # (B, L, ...)
        la = jnp.cumsum(dtc * a, axis=1)               # (B, L, H) log decay
        seg = la[:, :, None, :] - la[:, None, :, :]    # (B, Lt, Ls, H)
        decay = jnp.where(mask[None, :, :, None], jnp.exp(
            jnp.where(mask[None, :, :, None], seg, 0.0)), 0.0)
        u = xc * dtc[..., None]                        # (B, L, H, P)
        cb = jnp.einsum("bthn,bshn->btsh", cc, bc, precision="highest")
        y = jnp.einsum("btsh,bshp->bthp", cb * decay, u,
                       precision="highest")
        y = y + jnp.exp(la)[..., None] * jnp.einsum(
            "bthn,bhpn->bthp", cc, h, precision="highest")
        tail = jnp.exp(la[:, -1:, :] - la)             # (B, L, H)
        h = jnp.exp(la[:, -1])[..., None, None] * h + jnp.einsum(
            "bsh,bshp,bshn->bhpn", tail, u, bc, precision="highest")
        return h, y

    h0 = jnp.zeros((Bz, H, Pd, N), jnp.float32)
    h, y = jax.lax.scan(step, h0, tuple(chunks(t) for t in (x, dt, b, c)))
    y = jnp.moveaxis(y, 0, 1).reshape(Bz, nc * L, H, Pd)[:, :T]
    return y, h


def mamba2_apply(p, x, cfg, *, collect: bool = False):
    """Whole sequences.  x: (B, T, d).  Returns (out (B, T, d), cache),
    the cache {ssm (B, H, P, N) float32, conv (B, w-1, conv_dim)} when
    ``collect``, else {}."""
    z, xbc, dt = _split_in(p, x, cfg)
    xbc, window = _conv1d({"conv_w": p["conv_w"], "conv_b": p["conv_b"]},
                          xbc, cfg.mamba_conv)
    xs, b, c = _split_xbc(jax.nn.silu(xbc), cfg)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, h = _ssd(xs, dt, a, _heads(b, cfg), _heads(c, cfg), CHUNK)
    y = y + p["D"].astype(jnp.float32)[:, None] * xs
    out = _out(p, y, z, cfg, x.dtype)
    return out, ({"ssm": h, "conv": window} if collect else {})


def mamba2_step(p, x, cfg, pool, layer, window):
    """One token.  x: (B, 1, d); pool: the pooled state (L, B, H, P, N)
    float32, of which block ``layer``'s rows are advanced in place;
    window: (B, w-1, conv_dim).  Returns (out (B, 1, d), pool, new
    window)."""
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref
    z, xbc, dt = _split_in(p, x, cfg)
    xbc, window = _conv1d({"conv_w": p["conv_w"], "conv_b": p["conv_b"]},
                          xbc, cfg.mamba_conv, state=window)
    xs, b, c = _split_xbc(jax.nn.silu(xbc[:, 0]), cfg)   # (B, H, P), (B, G, N)
    dt = jax.nn.softplus(dt[:, 0] + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    # the kernel is chosen by the compile target, not by the process's
    # default backend: a CPU process may compile for a TPU
    y, pool = jax.lax.platform_dependent(
        pool, layer, jnp.exp(dt * a), dt[..., None] * xs, b, c,
        tpu=kops.mamba2_decode, default=kref.mamba2_decode_ref)
    y = y + p["D"].astype(jnp.float32)[:, None] * xs
    return _out(p, y[:, None], z, cfg, x.dtype), pool, window
