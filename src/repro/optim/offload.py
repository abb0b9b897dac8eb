"""Optimizer-state host offload — the paper's technique at training scale.

HMPP's ``advancedload``/``delegatestore`` become memory-kind transfers:
optimizer state lives in ``pinned_host`` memory and is streamed to the
device right before the update (advancedload, overlapped by XLA with the
backward pass) and streamed back after (delegatestore, overlapped with the
next step's forward).  Concretely this is just a sharding transform — the
jitted step's in/out shardings for the optimizer state carry
``memory_kind="pinned_host"`` and XLA inserts the transfers.

``host_memory_kind()`` says whether the device has a ``pinned_host``
space (TPU does, and so does the CPU backend of the installed JAX).  The
transforms below require it: on a device without one they raise, instead
of quietly leaving the optimizer state on the device.

``offload_shardings`` converts a device sharding tree; ``plan_step_program``
builds the equivalent explicit block-``Program`` (host update blocks +
device compute blocks) so the offload schedule can be *inspected* with the
paper's emitter and counted by the executor — used in tests and the
train-overlap benchmark.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax

from repro.core import Program

__all__ = ["offload_shardings", "offloaded_optimizer", "plan_step_program",
           "attention_step_program", "host_memory_kind"]

_HOST_KIND = "pinned_host"


@functools.lru_cache(maxsize=None)
def _device_memory_kinds(device) -> tuple:
    return tuple(m.kind for m in device.addressable_memories())


def host_memory_kind(device=None) -> Optional[str]:
    """The host-side memory kind usable for offload on ``device``, or
    ``None`` when the device addresses no ``pinned_host`` space."""
    if device is None:
        device = jax.devices()[0]
    return _HOST_KIND if _HOST_KIND in _device_memory_kinds(device) else None


def _require_host_kind() -> str:
    kind = host_memory_kind()
    if kind is None:
        raise RuntimeError(
            f"{jax.devices()[0]} has no {_HOST_KIND!r} memory: optimizer "
            f"state cannot be offloaded to host on this device")
    return kind


def offload_shardings(sharding_tree):
    """Move a sharding tree's memory kind to the host space."""
    kind = _require_host_kind()
    return jax.tree.map(
        lambda s: s.with_memory_kind(kind), sharding_tree,
        is_leaf=lambda x: isinstance(x, jax.sharding.Sharding))


def _to_space(tree, kind: str):
    _require_host_kind()
    target = (jax.memory.Space.Host if kind == _HOST_KIND
              else jax.memory.Space.Device)
    return jax.tree.map(
        lambda x: jax.device_put(x, target)
        if hasattr(x, "ndim") and x.ndim > 0 else x, tree)


def offloaded_optimizer(opt):
    """Wrap an Optimizer so its state lives in host memory: the update
    streams state in (advancedload — XLA overlaps it with the backward
    pass that produces the grads) and the new state back out
    (delegatestore, overlapped with the next forward)."""
    def update(grads, state, params):
        state_dev = _to_space(state, "device")
        new_p, new_s = opt.update(grads, state_dev, params)
        return new_p, _to_space(new_s, _HOST_KIND)

    return dataclasses.replace(opt, update=update,
                               name=opt.name + "+offload")


def plan_step_program(n_steps: int = 4) -> Program:
    """A miniature training loop as a block program: host data producer,
    device fwd/bwd codelet, device optimizer update reading offloaded state,
    host metric logging — the planner hoists the batch upload (prefetch) and
    sinks the metric download (lazy fetch), exactly the schedule train.py
    implements with real arrays."""
    import numpy as np

    p = Program("train_loop")
    p.bind("w", np.zeros((64, 64), np.float32))
    p.bind("opt_m", np.zeros((64, 64), np.float32))
    p.bind("seed", np.zeros((2,), np.float32))

    p.host(lambda xp, seed: {"batch": xp.outer(seed + 1.0,
                                               xp.ones(64, xp.float32))},
           reads=("seed",), writes=("batch",), name="next_batch")
    with p.loop(n_steps):
        p.offload(lambda xp, w, batch:
                  {"grad": (w @ batch.T @ batch) / 64.0,
                   "loss": ((batch @ w) ** 2).sum(keepdims=True)[:1]},
                  reads=("w", "batch"), writes=("grad", "loss"),
                  name="fwd_bwd")
        p.offload(lambda xp, w, grad, opt_m:
                  {"w": w - 0.1 * (0.9 * opt_m + grad),
                   "opt_m": 0.9 * opt_m + grad},
                  reads=("w", "grad", "opt_m"), writes=("w", "opt_m"),
                  name="opt_update")
    p.host(lambda xp, loss: {"final_loss": loss},
           reads=("loss",), writes=("final_loss",), name="log_metrics")
    p.set_outputs("final_loss", "w")
    return p


def attention_step_program(n_steps: int = 2, *, batch: int = 1,
                           seq: int = 128, kv_heads: int = 1,
                           group: int = 1, head_dim: int = 8,
                           dtype=None, interpret: bool = False) -> Program:
    """A flash-attention train step as a block program with a *tagged*
    Pallas kernel block: the ``kernel="flash_attention"`` tag lets the
    plan-space tuner enumerate tile variants (``block_q``/``block_k``)
    for the attention launch and price them with the two-level roofline,
    alongside the usual policy/stream/fuse axes.

    q is (batch, seq, kv_heads, group, head_dim) and k, v are
    (batch, seq, kv_heads, head_dim), in ``dtype`` (float32 when None).
    The defaults (S = T = 128, one head of 8) keep interpret-mode Pallas
    fast on the CPU while the clamped tile grid still yields >= 3
    distinct variants; qwen2.5-14b's layout is ``kv_heads=8, group=5,
    head_dim=128``.  ``interpret`` is passed to the kernel as is: the
    CPU needs ``True``, a TPU runs it natively."""
    import numpy as np

    from repro.kernels import ops

    dtype = np.float32 if dtype is None else dtype
    B, S, K, G, D = batch, seq, kv_heads, group, head_dim
    rng = np.random.default_rng(0)
    p = Program("attention_step")
    p.bind("q", rng.standard_normal((B, S, K, G, D)).astype(dtype))
    p.bind("k", rng.standard_normal((B, S, K, D)).astype(dtype))
    p.bind("v", rng.standard_normal((B, S, K, D)).astype(dtype))
    p.bind("gain", np.ones((1,), np.float32))

    p.host(lambda xp, gain: {"g": gain * 1.001},
           reads=("gain",), writes=("g",), name="next_gain")
    with p.loop(n_steps):
        # reads are the kernel's ops-layer operands, in operand order —
        # the tuner resolves the variant grid from their shapes
        p.offload(lambda xp, q, k, v, *, block_q=128, block_k=128:
                  {"o": ops.flash_attention(q, k, v, causal=True,
                                            block_q=block_q,
                                            block_k=block_k,
                                            interpret=interpret)},
                  reads=("q", "k", "v"), writes=("o",),
                  name="attention", kernel="flash_attention")
        # the sum of squares accumulates in float32 whatever o's dtype
        p.offload(lambda xp, o, g:
                  {"loss": xp.square(o.astype(xp.float32)).sum()
                   .reshape(1) * g},
                  reads=("o", "g"), writes=("loss",), name="reduce")
    p.host(lambda xp, loss: {"final_loss": loss},
           reads=("loss",), writes=("final_loss",), name="log_metrics")
    p.set_outputs("final_loss",)
    return p
