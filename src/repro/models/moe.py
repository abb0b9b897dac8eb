"""Mixture-of-Experts FFN: top-k routing, sort-based capacity dispatch,
expert-parallel grouped compute, optional dense-residual branch (Arctic).

``moe_held_apply`` is the other dispatch, for a chip that holds a share of
the experts (Nemotron-H): a sigmoid router with a correction bias over all
experts, and a dropless dispatch to the held ones plus a shared expert.

Dispatch is the static-shape "dropping" formulation (GShard/Switch style,
sort-based like MaxText): tokens are sorted by assigned expert, ranked
within the expert, and tokens beyond ``capacity`` are dropped (their combine
weight is zero, residual passes through).  Expert weights are stacked with a
leading ``experts`` logical axis → sharded over the "model" mesh axis
(expert parallelism); the dispatch/combine scatters become all-to-alls under
GSPMD.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import P, Policy, ffn_apply, ffn_spec

__all__ = ["moe_spec", "moe_apply", "moe_apply_ep", "moe_held_apply",
           "sigmoid_route"]


def moe_spec(cfg, prefix_shape=(), prefix_names=()) -> Dict[str, Any]:
    pa, pn = tuple(prefix_shape), tuple(prefix_names)
    if cfg.router == "sigmoid":
        return {
            "router": P(pa + (cfg.d_model, cfg.n_experts),
                        pn + ("embed", "experts")),
            "router_bias": P(pa + (cfg.n_experts,), pn + ("experts",),
                             init="zeros"),
            "experts": ffn_spec(cfg.d_model, cfg.d_ff, cfg.activation,
                                pa + (cfg.n_held,), pn + ("experts",)),
            "shared": ffn_spec(cfg.d_model, cfg.moe_shared_ff,
                               cfg.activation, pa, pn),
        }
    spec: Dict[str, Any] = {
        "router": P(pa + (cfg.d_model, cfg.n_experts),
                    pn + ("embed", "experts")),
        "experts": ffn_spec(cfg.d_model, cfg.d_ff, cfg.activation,
                            pa + (cfg.n_experts,), pn + ("experts",)),
    }
    if cfg.moe_dense_residual:
        spec["dense"] = ffn_spec(cfg.d_model, cfg.d_ff, cfg.activation,
                                 pa, pn)
    return spec


def _capacity(n_tokens: int, n_experts: int, top_k: int,
              capacity_factor: float) -> int:
    cap = int(n_tokens * top_k * capacity_factor / n_experts)
    return max(8, (cap + 7) // 8 * 8)   # pad to 8 for tiling friendliness


def moe_apply(params, x, cfg, *, policy: Optional[Policy] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d).  Returns (out, router aux loss)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    C = _capacity(T, E, k, cfg.capacity_factor)
    xf = x.reshape(T, d)

    # --- routing ----------------------------------------------------------
    logits = (xf.astype(jnp.float32) @
              params["router"].astype(jnp.float32))          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)           # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)               # renormalize

    # load-balancing aux loss (Switch):  E * sum_e f_e * p_e
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[expert_idx.reshape(-1)].add(
        1.0 / (T * k))
    aux = E * jnp.sum(me * ce)

    # --- sort-based dispatch (static shapes) ------------------------------
    flat_e = expert_idx.reshape(-1)                           # (T*k,)
    flat_g = gate_vals.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), k)                     # token of slot
    order = jnp.argsort(flat_e)                               # group by e
    se, sg, st = flat_e[order], flat_g[order], flat_t[order]
    # rank within expert = index - start offset of that expert's run
    counts = jnp.zeros((E,), jnp.int32).at[se].add(1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(T * k) - offsets[se]
    keep = rank < C
    slot = se * C + jnp.where(keep, rank, 0)                  # (T*k,)

    buf = jnp.zeros((E * C, d), x.dtype)
    buf = buf.at[jnp.where(keep, slot, E * C)].add(
        xf[st], mode="drop")                                  # OOB drops
    buf = buf.reshape(E, C, d)
    if policy is not None:
        buf = policy.acts(buf, "moe_buf")

    # --- expert compute: grouped FFN over stacked weights ------------------
    ew = params["experts"]
    if cfg.activation in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.activation == "swiglu" else jax.nn.gelu
        h = act(jnp.einsum("ecd,edf->ecf", buf, ew["w_gate"])) * \
            jnp.einsum("ecd,edf->ecf", buf, ew["w_up"])
    else:
        h = jnp.square(jax.nn.relu(
            jnp.einsum("ecd,edf->ecf", buf, ew["w_up"])))
    if policy is not None:
        h = policy.acts(h, "moe_hidden")
    y = jnp.einsum("ecf,efd->ecd", h, ew["w_down"])           # (E, C, d)
    y = y.reshape(E * C, d)
    if policy is not None:
        y = policy.acts(y.reshape(E, C, d), "moe_buf").reshape(E * C, d)

    # --- combine ------------------------------------------------------------
    gathered = y[jnp.where(keep, slot, 0)]                    # (T*k, d)
    w = jnp.where(keep, sg, 0.0).astype(jnp.float32)
    out = jnp.zeros((T, d), jnp.float32).at[st].add(
        gathered.astype(jnp.float32) * w[:, None])
    out = out.astype(x.dtype)

    if cfg.moe_dense_residual:
        out = out + ffn_apply(params["dense"], xf, cfg.activation,
                              policy=policy)
    return out.reshape(B, S, d), aux


def sigmoid_route(params, xf, cfg):
    """Top-k experts of each token and their weights, in float32: scores
    ``s = sigmoid(x W_r)``, the k chosen on ``s + bias``, their weights
    ``s`` at those experts over their sum, times ``routed_scaling``."""
    s = jax.nn.sigmoid(xf.astype(jnp.float32)
                       @ params["router"].astype(jnp.float32))
    _, idx = jax.lax.top_k(s + params["router_bias"].astype(jnp.float32),
                           cfg.top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scaling
    return w, idx


def moe_held_apply(params, x, cfg) -> Tuple[jax.Array, jax.Array]:
    """The share of an expert layer that the experts ``[expert_offset,
    expert_offset + n_held)`` give, plus the shared expert, all relu² and
    not gated, as in Nemotron-H.  x: (B, S, d).

    Each token is routed over all ``n_experts``; a chosen expert that this
    chip does not hold adds nothing here.  Dropless: a held expert has one
    slot per token of the call, and a token takes the slot of its own
    index (a token chooses an expert at most once).  Returns (out, 0)."""
    B, S, d = x.shape
    T, Eh, k = B * S, cfg.n_held, cfg.top_k
    xf = x.reshape(T, d)
    w, idx = sigmoid_route(params, xf, cfg)                   # (T, k)
    local = idx - cfg.expert_offset
    held = (local >= 0) & (local < Eh)
    tok = jnp.broadcast_to(jnp.arange(T)[:, None], (T, k))
    slot = jnp.where(held, local * T + tok, Eh * T)           # OOB: dropped
    buf = jnp.zeros((Eh * T, d), x.dtype).at[slot.reshape(-1)].set(
        jnp.repeat(xf, k, axis=0), mode="drop").reshape(Eh, T, d)
    ew = params["experts"]
    h = jnp.square(jax.nn.relu(jnp.einsum("etd,edf->etf", buf, ew["w_up"])))
    y = jnp.einsum("etf,efd->etd", h, ew["w_down"]).reshape(Eh * T, d)
    got = y[jnp.where(held, slot, 0)].astype(jnp.float32)     # (T, k, d)
    out = jnp.sum(got * jnp.where(held, w, 0.0)[..., None], axis=1)
    out = out.astype(x.dtype) + ffn_apply(params["shared"], xf,
                                          cfg.activation)
    return out.reshape(B, S, d), jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Expert-parallel shard_map implementation (§Perf iteration 1 for MoE):
# GSPMD's scatter-based partitioning of the einsum formulation replicates
# the dispatch buffers (≈10 TB of all-gather per step for qwen3-moe at
# 256 chips).  Here the parallelism is explicit: tokens stay sharded over
# (pod, data) and are replicated over "model"; each model column owns
# E/16 experts, dispatches ONLY its local tokens→local experts (zero
# communication), and a single psum over "model" combines expert outputs —
# per layer that is one (B_loc, S, d) all-reduce instead of buffer-sized
# all-gathers.  Expert weights stay FSDP-sharded over "data"; the body
# all-gathers them per layer (the standard per-layer FSDP gather) and the
# transpose of that gather reduce-scatters the weight grads.
# ---------------------------------------------------------------------------

def moe_apply_ep(params, x, cfg, mesh, *, policy: Optional[Policy] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    model = "model"
    n_model = mesh.shape[model]
    has_data = "data" in mesh.shape
    assert E % n_model == 0, (E, n_model)
    E_loc = E // n_model
    gated = cfg.activation in ("swiglu", "geglu")

    def body(xl, router_w, ew):
        j = jax.lax.axis_index(model)
        Bl, Sl, _ = xl.shape
        T = Bl * Sl
        C = _capacity(T, E, k, cfg.capacity_factor)
        xf = xl.reshape(T, d)

        # FSDP gather of this column's expert weights (d dim over "data")
        if has_data:
            ew = {
                "w_up": jax.lax.all_gather(ew["w_up"], "data", axis=1,
                                           tiled=True),
                "w_down": jax.lax.all_gather(ew["w_down"], "data", axis=2,
                                             tiled=True),
                **({"w_gate": jax.lax.all_gather(ew["w_gate"], "data",
                                                 axis=1, tiled=True)}
                   if gated else {}),
            }

        logits = xf.astype(jnp.float32) @ router_w.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

        me = probs.mean(axis=0)
        ce = jnp.zeros((E,), jnp.float32).at[expert_idx.reshape(-1)].add(
            1.0 / (T * k))
        aux = E * jnp.sum(me * ce)

        flat_e = expert_idx.reshape(-1)
        flat_g = gate_vals.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(T), k)
        order = jnp.argsort(flat_e)
        se, sg, st = flat_e[order], flat_g[order], flat_t[order]
        counts = jnp.zeros((E,), jnp.int32).at[se].add(1)
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(counts)[:-1]])
        rank = jnp.arange(T * k) - offsets[se]
        local = (se >= j * E_loc) & (se < (j + 1) * E_loc)
        keep = (rank < C) & local
        le = jnp.where(local, se - j * E_loc, 0)
        slot = le * C + jnp.where(keep, rank, 0)

        buf = jnp.zeros((E_loc * C, d), xl.dtype)
        buf = buf.at[jnp.where(keep, slot, E_loc * C)].add(
            xf[st], mode="drop").reshape(E_loc, C, d)

        if gated:
            act = jax.nn.silu if cfg.activation == "swiglu" else jax.nn.gelu
            h = act(jnp.einsum("ecd,edf->ecf", buf, ew["w_gate"])) * \
                jnp.einsum("ecd,edf->ecf", buf, ew["w_up"])
        else:
            h = jnp.square(jax.nn.relu(
                jnp.einsum("ecd,edf->ecf", buf, ew["w_up"])))
        y = jnp.einsum("ecf,efd->ecd", h, ew["w_down"]).reshape(E_loc * C, d)

        gathered = y[jnp.where(keep, slot, 0)]
        wgt = jnp.where(keep, sg, 0.0).astype(jnp.float32)
        out = jnp.zeros((T, d), jnp.float32).at[st].add(
            gathered.astype(jnp.float32) * wgt[:, None])
        # combine expert columns: one activation-sized all-reduce per
        # layer — in bf16 (halves the wire bytes; partial sums of ≤top_k
        # expert outputs are bf16-safe)
        out = jax.lax.psum(out.astype(xl.dtype), model)
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
        return out.reshape(Bl, Sl, d), aux

    bspec = batch_axes if batch_axes else None
    ew_specs = {
        "w_up": P(model, "data" if has_data else None, None),
        "w_down": P(model, None, "data" if has_data else None),
    }
    if gated:
        ew_specs["w_gate"] = P(model, "data" if has_data else None, None)
    out, aux = shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, None), P(None, None), ew_specs),
        out_specs=(P(bspec, None, None), P()),
        check_rep=False,
    )(x, params["router"], params["experts"])

    if cfg.moe_dense_residual:
        out = out + ffn_apply(params["dense"], x.reshape(-1, d),
                              cfg.activation, policy=policy
                              ).reshape(B, S, d)
    return out, aux
