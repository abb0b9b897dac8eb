"""The decoder every architecture shares: embedding, a stack of layers,
final norm and head; ``loss`` (chunked CE, never materializing (B, S,
vocab)), ``prefill`` (which also builds the serving cache), ``decode_step``.

``cfg.layer_pattern`` picks the stack from ``STACKS`` once, when a
``Transformer`` is built.  Each stack module owns its parameters, layer
bodies and serving cache behind one interface:

  * ``spec(cfg)``: its parameter subtree,
  * ``forward(params, x, cfg, positions, policy, *, collect, max_seq,
    ...)`` -> ``(x, aux, caches)``, caches built only with ``collect``,
  * ``init_cache(cfg, batch, max_seq, dtype, ...)``,
  * ``decode(params, x, cfg, cache, pos, policy)`` -> ``(x, cache)``,
  * ``EXACT_PROMPTS``: True where the cache carries recurrent state, which
    a right-padded prompt would corrupt.

The model's options (``use_pallas``, ``moe_ep``, ``kv_quant``) reach a
stack as keywords; each stack reads those it uses and ignores the rest.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from . import dense, nemotron_h, rglru, rwkv6
from .layers import (P, Policy, abstract_tree, axes_tree, cross_entropy,
                     init_tree, rms_norm)

__all__ = ["Transformer", "STACKS"]

LOSS_CHUNK = 512

# layer_pattern -> the module of its stack
STACKS = {"full": dense, "griffin": rglru, "rwkv": rwkv6,
          "nemotron_h": nemotron_h}


@dataclasses.dataclass
class Transformer:
    cfg: Any
    use_pallas: bool = False
    moe_ep: bool = False   # expert-parallel shard_map MoE (train/prefill)
    kv_quant: bool = False  # int8 KV cache (decode)
    stack: Any = dataclasses.field(init=False, repr=False, compare=False)

    def __post_init__(self):
        self.stack = STACKS.get(self.cfg.layer_pattern)
        if self.stack is None:
            raise ValueError(f"unknown layer_pattern {self.cfg.layer_pattern!r}"
                             f"; known are {sorted(STACKS)}")

    def _opts(self):
        return {"use_pallas": self.use_pallas, "moe_ep": self.moe_ep,
                "kv_quant": self.kv_quant}

    # ---- params ----------------------------------------------------------
    def spec(self) -> Dict[str, Any]:
        cfg = self.cfg
        d, v = cfg.d_model, cfg.vocab
        spec: Dict[str, Any] = {
            "final_norm": P((d,), ("embed",), init="ones"),
            "head": P((d, max(cfg.n_codebooks, 1) * v), ("embed", "vocab"))}
        if cfg.input_embeds:
            spec["in_proj"] = P((d, d), ("embed", "embed_out"))
        else:
            spec["embed"] = P((v, d), ("vocab", "embed"))
        return spec | self.stack.spec(cfg)

    def init(self, key, dtype=None):
        dt = dtype or jnp.dtype(self.cfg.dtype)
        return init_tree(self.spec(), key, dt)

    def abstract_params(self, dtype=None):
        dt = dtype or jnp.dtype(self.cfg.dtype)
        return abstract_tree(self.spec(), dt)

    def logical_axes(self):
        return axes_tree(self.spec())

    # ---- embedding -------------------------------------------------------
    def _embed(self, params, batch, policy, kind="embeds"):
        cfg = self.cfg
        if cfg.input_embeds:
            x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
            x = x @ params["in_proj"]
        else:
            x = params["embed"][batch["tokens"]]
        if policy is not None:
            x = policy.acts(x, kind)
        return x

    def _head(self, params, h):
        """Final norm and head of the last hidden states h (B, d)."""
        cfg = self.cfg
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        logits = h @ params["head"]
        if cfg.n_codebooks:
            logits = logits.reshape(h.shape[0], cfg.n_codebooks, cfg.vocab)
        return logits

    # ---- training --------------------------------------------------------
    def loss(self, params, batch, policy: Optional[Policy] = None):
        """batch: tokens (B,S) [or embeds (B,S,d)] + labels
        (B,S) or (B,S,n_codebooks).  Returns (loss, metrics)."""
        cfg = self.cfg
        x = self._embed(params, batch, policy)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        h, aux, _ = self.stack.forward(params, x, cfg, positions, policy,
                                       **self._opts())
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
        logits are returned instead of ``S - 1``: the serving engine
        right-pads prompts to a shape bucket wherever the stack allows it
        (``EXACT_PROMPTS`` False) and needs each request's real last
        token."""
        cfg = self.cfg
        x = self._embed(params, batch, policy)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        h, _, caches = self.stack.forward(params, x, cfg, positions, policy,
                                          collect=True, max_seq=max_seq,
                                          **self._opts())
        hl = h[:, -1] if last_pos is None else h[jnp.arange(B), last_pos]
        return self._head(params, hl), caches

    def init_cache(self, batch: int, max_seq: int, dtype=None):
        dt = dtype or jnp.dtype(self.cfg.dtype)
        return self.stack.init_cache(self.cfg, batch, max_seq, dt,
                                     **self._opts())

    def decode_step(self, params, cache, batch, pos,
                    policy: Optional[Policy] = None):
        """One token for the whole stack.
        batch: tokens (B,) [or embeds (B, d)]; pos: (B,) int32.
        Returns (logits (B, vocab[, cb]), new_cache)."""
        # each input as a sequence of one token
        x = self._embed(params, {k: v[:, None] for k, v in batch.items()},
                        policy, "embeds_dec")
        x, new_cache = self.stack.decode(params, x, self.cfg, cache, pos,
                                         policy)
        return self._head(params, x[:, 0]), new_cache
