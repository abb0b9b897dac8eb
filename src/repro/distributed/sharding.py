"""Logical-axis → mesh-axis sharding rules.

Model code tags every param dim with a logical axis name (see
``models/layers.py::P``); here those names map to mesh axes per *shape kind*
(train / prefill / decode).  A **divisibility guard** drops any mesh axis
that does not evenly divide the dim (e.g. qwen2.5's 40 q-heads or Arctic's
56 on a 16-way "model" axis stay unsharded and the drop is recorded), so
every produced ``PartitionSpec`` is always valid for ``jax.jit``
in_shardings.

Parallelism layout (single pod 16×16, multi-pod 2×16×16):
  * batch        → ("pod", "data")      — DP across pods and data axis
  * embed        → "data"               — FSDP: params ZeRO-3-sharded over
                                          data; XLA all-gathers per layer
                                          and reduce-scatters grads
  * ffn/heads/vocab/experts/rnn → "model" — TP / EP
  * decode KV cache seq dim → "model"   — sequence-parallel decode
    (Flash-Decoding style: softmax stats all-reduce over "model")
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["ShardingRules", "make_rules", "spec_for_axes", "tree_shardings",
           "MeshPolicy", "batch_axes", "batch_specs", "cache_shardings",
           "abstract_mesh"]


def abstract_mesh(shape=(16, 16), axes=("data", "model")):
    """Device-less mesh for rule evaluation."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(axes))


# logical axis -> mesh axis (or tuple), per shape kind
PARAM_RULES: Dict[str, Dict[str, Any]] = {
    "train": {
        "embed": "data",        # FSDP
        "embed_out": None,
        "ffn": "model",
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "vocab": "model",
        "experts": "model",
        "rnn": "model",
        "layers": None,
    },
    # inference: no FSDP (weights all-gathered once is wasteful per step);
    # keep TP on model, replicate the small rest
    "serve": {
        "embed": None,
        "embed_out": None,
        "ffn": "model",
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "vocab": "model",
        "experts": "model",
        "rnn": "model",
        "layers": None,
    },
}


@dataclasses.dataclass
class ShardingRules:
    mesh: Mesh
    kind: str                       # train | prefill | decode
    rules: Dict[str, Any]
    dropped: List[Tuple[str, str, int]] = dataclasses.field(
        default_factory=list)       # (context, axis, dim) divisibility drops

    @property
    def data_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in ("pod", "data") if a in self.mesh.shape)

    def axis_size(self, name) -> int:
        if name is None:
            return 1
        if isinstance(name, tuple):
            return int(np.prod([self.mesh.shape[a] for a in name]))
        return self.mesh.shape[name]


def make_rules(mesh: Mesh, kind: str, *,
               fsdp_layers: bool = False) -> ShardingRules:
    """``fsdp_layers``: shard stacked params on their LAYER dim over "data"
    instead of the embed dim (§Perf iteration: XLA then materializes only
    the current layer's slice per scan step instead of all-gathering the
    whole stack — the layers axis precedes embed in every stacked spec, so
    the divisibility-guarded used-set drops the embed rule there while
    unstacked params keep plain embed-FSDP)."""
    table = dict(PARAM_RULES["train" if kind == "train" else "serve"])
    if fsdp_layers:
        table["layers"] = "data"
    return ShardingRules(mesh=mesh, kind=kind, rules=table)


def spec_for_axes(rules: ShardingRules, shape: Tuple[int, ...],
                  axes: Tuple[Optional[str], ...],
                  context: str = "") -> PartitionSpec:
    """Build a valid PartitionSpec, dropping non-dividing mesh axes."""
    used = set()
    entries = []
    for dim, logical in zip(shape, axes):
        mesh_axis = rules.rules.get(logical) if logical else None
        if mesh_axis is None:
            entries.append(None)
            continue
        size = rules.axis_size(mesh_axis)
        flat = mesh_axis if isinstance(mesh_axis, tuple) else (mesh_axis,)
        if dim % size != 0 or any(a in used for a in flat):
            if dim % size != 0:
                rules.dropped.append((context, str(logical), dim))
            entries.append(None)
            continue
        used.update(flat)
        entries.append(mesh_axis)
    return PartitionSpec(*entries)


def tree_shardings(rules: ShardingRules, shapes_tree, axes_tree_,
                   context: str = "params"):
    """NamedSharding tree parallel to a ShapeDtypeStruct/array tree."""
    def one(leaf, axes):
        shape = leaf.shape if hasattr(leaf, "shape") else np.shape(leaf)
        spec = spec_for_axes(rules, tuple(shape), tuple(axes), context)
        return NamedSharding(rules.mesh, spec)
    return jax.tree.map(one, shapes_tree, axes_tree_,
                        is_leaf=lambda x: hasattr(x, "shape"))


def batch_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.shape) or None


def batch_specs(rules: ShardingRules, cfg, shape_kind: str,
                batch_shapes: Dict[str, Any]) -> Dict[str, NamedSharding]:
    """Shardings for the input batch: batch dim over (pod, data)."""
    b = batch_axes(rules.mesh)
    out = {}
    for name, sds in batch_shapes.items():
        nd = len(sds.shape)
        if sds.shape and sds.shape[0] % rules.axis_size(b) == 0:
            spec = PartitionSpec(b, *([None] * (nd - 1)))
        else:
            spec = PartitionSpec(*([None] * nd))
        out[name] = NamedSharding(rules.mesh, spec)
    return out


def cache_shardings(rules: ShardingRules, cache_tree):
    """Decode-cache shardings, chosen by the cache dict keys:

      k/v  (…, B, T, K, D) : batch→(pod,data), seq→model (sequence-parallel
                              decode — Flash-Decoding on TPU)
      pos  (…, B, T)       : matches k/v
      h    (…, B, D)       : batch→(pod,data), channel→model
      conv (…, B, w-1, D)  : batch→(pod,data), channel→model
      state(…, B, H, s, s) : batch→(pod,data), heads→model
      tm_x/cm_x (…, B, D)  : batch→(pod,data), channel→model

    All through the divisibility guard, so e.g. B=1 (long_500k) or H=40
    simply stay replicated."""
    mesh = rules.mesh
    b = batch_axes(mesh)

    # per-key: (offset from END of shape -> mesh axis)
    KEY_RULES = {
        "k":    {4: b, 3: "model"},
        "v":    {4: b, 3: "model"},
        "pos":  {2: b, 1: "model"},
        "h":    {2: b, 1: "model"},
        "conv": {3: b, 1: "model"},
        "state": {4: b, 3: "model"},
        "tm_x": {2: b, 1: "model"},
        "cm_x": {2: b, 1: "model"},
        "k_scale": {3: b, 2: "model"},
        "v_scale": {3: b, 2: "model"},
    }

    def one(path, leaf):
        key = None
        for entry in reversed(path):
            k = getattr(entry, "key", None)
            if isinstance(k, str):
                key = k
                break
        shape = tuple(leaf.shape)
        nd = len(shape)
        entries: List[Any] = [None] * nd
        used: set = set()
        for off, ax in KEY_RULES.get(key, {}).items():
            i = nd - off
            if i < 0 or ax is None:
                continue
            size = rules.axis_size(ax)
            flat = set(ax) if isinstance(ax, tuple) else {ax}
            if shape[i] % size == 0 and not (flat & used):
                entries[i] = ax
                used |= flat
            else:
                rules.dropped.append((f"cache/{key}", str(ax), shape[i]))
        return NamedSharding(mesh, PartitionSpec(*entries))

    return jax.tree_util.tree_map_with_path(
        one, cache_tree, is_leaf=lambda x: hasattr(x, "shape"))


# ---------------------------------------------------------------------------
# Activation policy (with_sharding_constraint hints inside the model)
# ---------------------------------------------------------------------------

class MeshPolicy:
    """Maps the model's activation tags to PartitionSpecs.  Must run inside
    a mesh context (the dry-run and train loop do).

    ``seq_shard``: shard the residual stream on the SEQUENCE dim over
    "model" (Megatron-SP style) instead of the embed dim — norms and
    elementwise ops stay local, matmuls all-gather activations over seq
    and reduce-scatter back (§Perf iteration 'seqshard')."""

    def __init__(self, rules: ShardingRules, cfg, *, seq_shard: bool = False):
        self.rules = rules
        b = batch_axes(rules.mesh)
        m = "model"
        def div(n):
            return m if n % rules.axis_size(m) == 0 else None
        if seq_shard:
            emb_spec = PartitionSpec(b, m, None)
        else:
            # residual sharded over "model" on embed: keeps the per-layer
            # saved carries (scan + remat) within HBM at 48 layers
            emb_spec = PartitionSpec(b, None, div(cfg.d_model))
        nh = getattr(cfg, "n_heads", 0) or 1
        nkv = getattr(cfg, "n_kv_heads", 0) or 1
        self.table: Dict[str, PartitionSpec] = {
            # FSDP weight-gather hints: constrain layer weights to their
            # TP-only sharding at the point of use, so XLA all-gathers the
            # (small) weight slice over "data" instead of all-reducing the
            # (huge) activations over the FSDP-contracted dim
            # block inputs gathered ONCE per block in bf16 (shared by
            # q/k/v or gate/up): avoids per-dot fp32 partial-sum
            # all-reduces from contracting the D-sharded residual
            "block_in": PartitionSpec(b, None, None),
            "w_ffn_in": PartitionSpec(None, div(cfg.d_ff)),
            "w_ffn_out": PartitionSpec(div(cfg.d_ff), None),
            "w_attn_q": PartitionSpec(None, div(nh), None),
            "w_attn_kv": PartitionSpec(None, div(nkv), None),
            "w_attn_out": PartitionSpec(div(nh), None, None),
            "embeds": emb_spec,
            "embeds_dec": PartitionSpec(b, None, div(cfg.d_model)),
            "ffn_hidden": PartitionSpec(b, None, div(cfg.d_ff)),
            "rnn_hidden": PartitionSpec(b, None, div(cfg.d_model)),
            "q5": PartitionSpec(b, None,
                                div(getattr(cfg, "n_kv_heads", 0) or 1),
                                None, None),
            "kv4": PartitionSpec(b, None, None, None),
            "kvcache": PartitionSpec(b, m, None, None),
            "moe_buf": PartitionSpec(
                div(getattr(cfg, "n_experts", 0) or 1), None, None),
            "moe_hidden": PartitionSpec(
                div(getattr(cfg, "n_experts", 0) or 1), None, None),
        }

    def acts(self, x, kind: str):
        spec = self.table.get(kind)
        if spec is None:
            return x
        spec = PartitionSpec(*(spec[: x.ndim]))
        try:
            return jax.lax.with_sharding_constraint(x, spec)
        except Exception:
            return x
