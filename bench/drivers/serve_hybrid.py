"""The serving cells of a hybrid model whose configuration file
holds the published ``config.json``'s keys (Nemotron-H: Mamba-2, MoE and
attention blocks): offline batch jobs through ``repro.serve.ServeRuntime``
and ``Engine``, in rounds, as ``serve_rounds`` drives them.

Rounds, warm-up (one whole round), window, the checked sample and the
reference check are those of ``serve_rounds``, whose helpers it uses,
and so are the traffic keys.  What differs: an untraced window holds at
least ``WINDOW_ROUNDS`` rounds (the checked sample is drawn from its
first), the program's configuration is read from the published keys,
the reference is called with the whole configuration, the run records
one slot's bytes per kind of cache, and a traced run on the chip
attributes the decode program's device time to the block kinds' named
scopes (``bench/scope_trace.py``).
"""
from __future__ import annotations

import dataclasses
import itertools
import os
import time
from math import inf
from typing import Any, Dict, List

import numpy as np

from bench import harness, scope_trace
from bench.drivers.serve_rounds import (_sample, calibrate, make_round,
                                        max_seq_of, sequences,
                                        weights_to_host)
from bench.harness import BENCH

# program field <- key of the published config (or of the benchmark file)
KEYS = {"d_model": "hidden_size", "vocab": "vocab_size",
        "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
        "d_head": "head_dim", "d_ff": "moe_intermediate_size",
        "moe_shared_ff": "moe_shared_expert_intermediate_size",
        "n_experts": "n_routed_experts_published",
        "experts_held": "n_routed_experts", "expert_offset": "expert_offset",
        "top_k": "num_experts_per_tok",
        "routed_scaling": "routed_scaling_factor",
        "block_pattern": "hybrid_override_pattern",
        "mamba_heads": "mamba_num_heads", "mamba_head_dim": "mamba_head_dim",
        "ssm_state": "ssm_state_size", "ssm_groups": "n_groups",
        "mamba_conv": "conv_kernel", "norm_eps": "layer_norm_epsilon",
        "dtype": "dtype"}
# Rounds an untraced window holds at the least.  A round's length is not
# fixed: most orders the seed draws take 1534 decode steps, a few take
# 1788 (a 1024-token output admitted last), and a pause of a second or so
# lands in one round.  Two rounds halve the effect of either on out_tok_s.
WINDOW_ROUNDS = 2
# what the program computes and the file has to state
FIXED = {"mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
         "norm_topk_prob": True, "use_conv_bias": True,
         "tie_word_embeddings": False, "n_shared_experts": 1}


def program_config(cfg):
    """The program's configuration with the sizes of the benchmark's file
    (an unknown ``program_config`` raises at once, before any weight is
    made)."""
    from repro.configs import get_config
    base = get_config(cfg["program_config"])
    for k, v in FIXED.items():
        if cfg[k] != v:
            raise harness.BenchError(f"the program computes {k} = {v!r}, "
                                     f"the configuration states {cfg[k]!r}")
    return dataclasses.replace(
        base, n_layers=len(cfg["hybrid_override_pattern"]),
        **{field: cfg[key] for field, key in KEYS.items()})


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    import jax

    from repro.serve import Engine, Request, ServeRuntime

    cfg, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    prog_cfg = program_config(cfg)
    ref = harness.load_module(BENCH / "ref" / f"{cfg['reference']}.py")
    capacity = int(cfg["capacity"])
    n_round = int(traffic["rounds_of_capacity"] * capacity)
    traced = bool(ctx["trace_dir"])

    t0 = time.perf_counter()
    weights = weights_to_host(ref.init(cfg, harness.jax_seed(seed, 0),
                                       cfg["dtype"]))
    rt = ServeRuntime(prog_cfg, max_seq=max_seq_of(traffic), params=weights,
                      use_pallas=False)
    eng = Engine(rt, capacity=capacity)
    rids = itertools.count()

    def requests(data):
        return [Request(rid=next(rids), prompt=r["prompt"],
                        max_new_tokens=r["gen"]) for r in data]

    # warm-up: one whole round of the cell's own shapes
    eng.run(requests(make_round(traffic, n_round, cfg["vocab_size"],
                                harness.rng_for(seed, 1))),
            respect_arrivals=False)
    calibration = (calibrate(rt, traffic, ctx["trace_dir"]) if traced
                   else None)
    setup_s = time.perf_counter() - t0

    rounds: List[List[Any]] = []
    with harness.CompileClock() as clock, ctx["tracer"]():
        with harness.span(ctx["window_span"], traced):
            tw = time.perf_counter()
            while True:
                reqs = requests(make_round(
                    traffic, n_round, cfg["vocab_size"],
                    harness.rng_for(seed, 2, len(rounds))))
                with harness.span("bench.round", traced):
                    eng.run(reqs, respect_arrivals=False)
                rounds.append(reqs)
                if traced and len(rounds) >= traffic["trace_rounds"]:
                    break
                if (len(rounds) >= WINDOW_ROUNDS
                        and time.perf_counter() - tw >= ctx["seconds"]):
                    break
            window_s = time.perf_counter() - tw

    done = [r for rr in rounds for r in rr]
    served = [r for r in done if r.tokens is not None
              and len(r.tokens) == r.max_new_tokens]
    gen_tokens = sum(r.max_new_tokens for r in served)
    device = ctx["device_record"]()
    scopes = (decode_scopes(rt, eng, traffic, ctx)
              if traced and jax.devices()[0].platform == "tpu" else None)
    slot_bytes = eng.pool.stats()["slot_bytes"]

    # free the program's device state before the reference runs; the
    # checked sample is drawn from the window's first round, as a window
    # of one round draws it
    first = {id(r) for r in rounds[0]}
    sample = _sample([r for r in served if id(r) in first],
                     traffic["checked_requests"], harness.rng_for(seed, 3))
    for leaf in jax.tree.leaves((rt.params, eng.pool.cache)):
        leaf.delete()
    del eng, rt, weights
    # the same weights again, made anew on the device from the seed
    weights = ref.init(cfg, harness.jax_seed(seed, 0), cfg["dtype"])
    gaps, ref_logits = served_gaps(ref, weights, cfg, sample)
    result = {
        "setup_s": setup_s,
        "end_to_end": {"out_tok_s": gen_tokens / window_s,
                       "setup_s": setup_s},
        "attempted": len(done), "failed": len(done) - len(served),
        "checks": [{"name": "served_logit_gap_mean",
                    "value": float(gaps.mean()) if len(gaps) else inf,
                    "limit": ctx["limits"]["served_logit_gap_mean"]}],
        "device": device,
        "observed": {
            "window_s": window_s, "rounds": len(rounds),
            "compiles": clock.compiles, "capacity": capacity,
            "gen_tokens": gen_tokens,
            "decode_tokens": sum(r.max_new_tokens - 1 for r in done),
            "prompt_tokens": sum(r.prompt_len for r in done),
            "requests": len(done), "calibration": calibration,
            "checked_tokens": len(gaps),
            "served_logit_gap_widest": float(gaps.max()) if len(gaps) else inf,
            "slot_bytes": slot_bytes, "scopes": scopes,
            # the serving readers read the sizes by the program's names
            "cfg": dict(cfg, d_model=cfg["hidden_size"],
                        vocab=cfg["vocab_size"]),
        },
    }
    if ctx.get("keep"):
        result["kept"] = {"ref": ref, "weights": weights, "cfg": cfg,
                          "sample": sample, "ref_logits": ref_logits}
    else:
        for leaf in jax.tree.leaves(weights):
            leaf.delete()
    return result


def decode_scopes(rt, eng, traffic, ctx):
    """The decode program's device time in the traced window, by named
    scope of block kind (``bench/scope_trace.py``).  The program's op
    metadata is read from the decode program compiled again for the
    shapes the window ran (a hit of the compile cache)."""
    import jax
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(jax.devices()[0])

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    C = eng.capacity
    gen_cap = max(v for v, _ in traffic["output_lens"])
    args = jax.tree.map(lambda a: shaped(a.shape, a.dtype),
                        (rt.params, eng.pool.cache))
    row = shaped((C,), np.int32)
    hlo = rt._decode.lower(*args, row, row, shaped((C, gen_cap), np.int32),
                           row).compile().as_text()
    return scope_trace.attribute(
        os.path.join(ctx["trace_dir"], "window"),
        scope_trace.op_scopes(hlo), module=scope_trace.DECODE_MODULE,
        window_span=ctx["window_span"])


def control_reading(kept) -> float:
    """The compared number of the control: at each served position of the
    checked requests, how far below the reference's best lies the token
    that the reference computed one precision below the configuration's
    (float8 products) puts first, the mean over those positions."""
    ref, cfg, sample = kept["ref"], kept["cfg"], kept["sample"]
    tokens, positions = sequences(sample)
    low = ref.logits_at(kept["weights"], tokens, positions, cfg=cfg,
                        quant=True)
    gaps = np.concatenate([ref.served_gaps(hi, lo.argmax(axis=-1))
                           for hi, lo in zip(kept["ref_logits"], low)])
    kept["control_widest"] = float(gaps.max())
    return float(gaps.mean())


def served_gaps(ref, weights, cfg, sample):
    """Per served token of the sample, how far its reference logit lies
    below the reference's best there; and the reference's logits."""
    if not sample:
        return np.zeros(0), []
    tokens, positions = sequences(sample)
    logits = ref.logits_at(weights, tokens, positions, cfg=cfg)
    return np.concatenate([ref.served_gaps(lg, r.tokens)
                           for lg, r in zip(logits, sample)]), logits
