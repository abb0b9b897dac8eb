"""Driver of the serving cells: offline batch jobs through
``repro.serve.ServeRuntime`` and ``Engine``.

Each round is one ``Engine.run(requests, respect_arrivals=False)`` over a
fixed number of requests, and rounds run back to back over the window;
the window ends with the round that crosses ``--seconds``.  The lengths of
a round are a fixed multiset, the traffic's ladders times their weights,
so every seed and every round has the same sizes and the same longest
output, in another order; the seed draws the order, the pairing of prompt
and output lengths, and the prompt tokens.

Traffic keys: ``prompt_lens`` and ``output_lens`` (ladders of
[length, weight]), ``rounds_of_capacity`` (requests per round, in units of
the configuration's decode capacity), ``checked_requests`` (how many
finished requests, drawn from the seed with the longest among them, are
compared with the reference) and ``trace_rounds`` (rounds a traced run
records).
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from math import inf
from typing import Any, Dict, List

import numpy as np

from bench import harness
from bench.harness import BENCH


def ladder_counts(ladder, n: int) -> List[int]:
    """Lengths of ``n`` requests from a ladder of [length, weight]: each
    length ``round(n * weight)`` times (largest remainders break ties), so
    the multiset is the same for every seed."""
    lens = [int(v) for v, _ in ladder]
    w = np.array([float(x) for _, x in ladder])
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return [length for length, c in zip(lens, counts) for _ in range(c)]


def make_round(traffic, n: int, vocab: int, rng) -> List[Dict[str, Any]]:
    """One round's requests as plain data: prompt tokens and output
    length."""
    plens = rng.permutation(ladder_counts(traffic["prompt_lens"], n))
    glens = rng.permutation(ladder_counts(traffic["output_lens"], n))
    return [{"prompt": rng.integers(0, vocab, int(p)).astype(np.int32),
             "gen": int(g)} for p, g in zip(plens, glens)]


def max_seq_of(traffic) -> int:
    return (max(v for v, _ in traffic["prompt_lens"])
            + max(v for v, _ in traffic["output_lens"]))


def program_config(cfg):
    """The program's configuration with the sizes of the benchmark's
    file."""
    from repro.configs import get_config
    keys = ("n_layers", "d_model", "d_ff", "vocab", "rwkv_head_size",
            "dtype", "norm_eps")
    return dataclasses.replace(get_config(cfg["program_config"]),
                               **{k: cfg[k] for k in keys})


def weights_to_host(tree):
    """Each leaf to a host array, its device copy deleted at once, so that
    the program, which uploads host weights, never finds two sets on the
    device."""
    import jax
    leaves, treedef = jax.tree.flatten(tree)
    host = []
    for leaf in leaves:
        host.append(np.asarray(jax.device_get(leaf)))
        leaf.delete()
    return jax.tree.unflatten(treedef, host)


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    import jax

    from repro.serve import Engine, Request, ServeRuntime

    cfg, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    ref = harness.load_module(BENCH / "ref" / f"{cfg['reference']}.py")
    capacity = int(cfg["capacity"])
    n_round = int(traffic["rounds_of_capacity"] * capacity)
    traced = bool(ctx["trace_dir"])

    t0 = time.perf_counter()
    weights = weights_to_host(ref.init(cfg, harness.jax_seed(seed, 0),
                                       cfg["dtype"]))
    rt = ServeRuntime(program_config(cfg), max_seq=max_seq_of(traffic),
                      params=weights, use_pallas=False)
    eng = Engine(rt, capacity=capacity)
    rids = itertools.count()

    def requests(data):
        return [Request(rid=next(rids), prompt=r["prompt"],
                        max_new_tokens=r["gen"]) for r in data]

    # warm-up: one whole round of the cell's own shapes
    eng.run(requests(make_round(traffic, n_round, cfg["vocab"],
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
                    traffic, n_round, cfg["vocab"],
                    harness.rng_for(seed, 2, len(rounds))))
                with harness.span("bench.round", traced):
                    eng.run(reqs, respect_arrivals=False)
                rounds.append(reqs)
                if traced and len(rounds) >= traffic["trace_rounds"]:
                    break
                if time.perf_counter() - tw >= ctx["seconds"]:
                    break
            window_s = time.perf_counter() - tw

    done = [r for rr in rounds for r in rr]
    served = [r for r in done if r.tokens is not None
              and len(r.tokens) == r.max_new_tokens]
    gen_tokens = sum(r.max_new_tokens for r in served)
    device = ctx["device_record"]()

    # free the program's device state before the reference runs
    sample = _sample(served, traffic["checked_requests"],
                     harness.rng_for(seed, 3))
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
            "cfg": cfg,
        },
    }
    if ctx.get("keep"):
        result["kept"] = {"ref": ref, "weights": weights, "cfg": cfg,
                          "sample": sample, "ref_logits": ref_logits}
    else:
        for leaf in jax.tree.leaves(weights):
            leaf.delete()
    return result


def control_reading(kept) -> float:
    """The compared number of the control: at each served position of the
    checked requests, how far below the reference's best lies the token
    that the reference computed one precision below the configuration's
    (float8 products) puts first, the mean over those positions."""
    ref, cfg, sample = kept["ref"], kept["cfg"], kept["sample"]
    tokens, positions = sequences(sample)
    low = ref.logits_at(kept["weights"], tokens, positions,
                        head_size=cfg["rwkv_head_size"], quant=True)
    gaps = np.concatenate([ref.served_gaps(hi, lo.argmax(axis=-1))
                           for hi, lo in zip(kept["ref_logits"], low)])
    kept["control_widest"] = float(gaps.max())
    return float(gaps.mean())


def calibrate(rt, traffic, trace_dir: str):
    """Program ids of the prefill programs, from a short trace of direct
    calls made at warm-up, one per prompt length: the prefill is
    ``jit(<lambda>)`` in the program, as is the park program, so module
    names cannot tell them apart."""
    import os

    import jax
    import jax.numpy as jnp

    from bench import xplane

    log_dir = os.path.join(trace_dir, "calibration-prefill")
    with jax.profiler.trace(log_dir):
        for length, _ in traffic["prompt_lens"]:
            jax.block_until_ready(rt._prefill(
                rt.params, {"tokens": jnp.zeros((1, length), jnp.int32)},
                jnp.asarray([length - 1], jnp.int32)))
    try:
        summary = xplane.reduce(log_dir)
    except ValueError:              # no device plane: nothing to read
        return None
    return {"prefill": sorted({e.program_id for e in summary.executions
                               if e.program_id is not None})}


def _sample(done, k: int, rng):
    """``k`` finished requests drawn from the seed, the longest among
    them (most served tokens, then the longest prompt)."""
    if not done:
        return []
    longest = max(done, key=lambda r: (r.max_new_tokens, r.prompt_len,
                                       -r.rid))
    rest = [r for r in done if r is not longest]
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def sequences(sample):
    """Reference inputs for served requests: each prompt followed by its
    served tokens but the last, right-padded to one length, and the
    positions whose logits chose each served token."""
    seqs = [np.concatenate([r.prompt, r.tokens[:-1]]).astype(np.int32)
            for r in sample]
    T = max(len(s) for s in seqs)
    tokens = np.zeros((len(seqs), T), np.int32)
    for b, s in enumerate(seqs):
        tokens[b, :len(s)] = s
    positions = [np.arange(r.prompt_len - 1, r.prompt_len - 1 + len(r.tokens))
                 for r in sample]
    return tokens, positions


def served_gaps(ref, weights, cfg, sample):
    """Per served token of the sample, how far its reference logit lies
    below the reference's best there; and the reference's logits."""
    if not sample:
        return np.zeros(0), []
    tokens, positions = sequences(sample)
    logits = ref.logits_at(weights, tokens, positions,
                           head_size=cfg["rwkv_head_size"])
    return np.concatenate([ref.served_gaps(lg, r.tokens)
                           for lg, r in zip(logits, sample)]), logits
