"""Jit'd model-facing wrappers for the Pallas kernels.

These fold the model layouts into the kernel layouts and pick block sizes.
Every kernel compiles natively by default (``interpret=False``): a caller
that wants the Pallas interpreter, as the CPU tests do, asks for it.  There
is no device sniffing here, so a kernel that cannot run natively fails
where it is called instead of quietly running interpreted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import rglru_scan as _rg
from . import rmsnorm as _rn
from . import wkv6 as _wkv

__all__ = ["flash_attention", "rglru_scan", "wkv6", "rmsnorm"]


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_vjp(q, k, v, causal, window, block_q, block_k,
                         interpret):
    B, S, K, G, D = q.shape
    T = k.shape[1]
    scale = 1.0 / (D ** 0.5)
    qf = (q * scale).transpose(0, 2, 1, 3, 4).reshape(B * K, S, G, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * K, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * K, T, D)
    o = _fa.flash_attention_folded(qf, kf, vf, causal=causal, window=window,
                                   block_q=block_q, block_k=block_k,
                                   interpret=interpret)
    return o.reshape(B, K, S, G, D).transpose(0, 2, 1, 3, 4)


def _flash_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    out = _flash_attention_vjp(q, k, v, causal, window, block_q, block_k,
                               interpret)
    return out, (q, k, v)


def _flash_bwd(causal, window, block_q, block_k, interpret, res, g):
    """Backward through the memory-efficient blockwise formulation (on real
    TPU a dedicated bwd kernel would slot in here; numerics are identical —
    validated in tests)."""
    from repro.models.attention import blockwise_attention
    q, k, v = res

    def f(q, k, v):
        return blockwise_attention(q, k, v, causal=causal, window=window)

    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


_flash_attention_vjp.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, S, K, G, D); k, v: (B, T, K, D) → (B, S, K, G, D).
    Differentiable: Pallas forward + blockwise online-softmax backward."""
    return _flash_attention_vjp(q, k, v, causal, window, block_q, block_k,
                                interpret)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def rglru_scan(a, b, *, block_t: int = 128, interpret: bool = False):
    """a, b: (B, T, D) → h (B, T, D) fp32."""
    return _rg.rglru_scan(a, b, block_t=block_t, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def wkv6(r, k, v, w, u, *, block_t: int = 64, interpret: bool = False):
    """r,k,v,w: (B, T, H, hs); u: (H, hs).
    Returns (o (B,T,H,hs) fp32, state (B,H,hs,hs) fp32)."""
    B, T, H, hs = r.shape
    def fold(t):
        return t.transpose(0, 2, 1, 3).reshape(B * H, T, hs)
    uu = jnp.broadcast_to(u[None], (B, H, hs)).reshape(B * H, hs)
    o, s = _wkv.wkv6_folded(fold(r), fold(k), fold(v), fold(w), uu,
                            block_t=block_t, interpret=interpret)
    o = o.reshape(B, H, T, hs).transpose(0, 2, 1, 3)
    return o, s.reshape(B, H, hs, hs)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows",
                                             "interpret"))
def rmsnorm(x, w, *, eps: float = 1e-6, block_rows: int = 256,
            interpret: bool = False):
    """x: (..., D); w: (D,)."""
    shape = x.shape
    xf = x.reshape(-1, shape[-1])
    n = xf.shape[0]
    br = block_rows
    while n % br:
        br //= 2
    o = _rn.rmsnorm(xf, w, eps=eps, block_rows=max(br, 1),
                    interpret=interpret)
    return o.reshape(shape)
