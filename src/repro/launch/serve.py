"""Serving driver: batched prefill + greedy decode with donated caches.

Residency policy (the paper's, applied to serving): weights and KV caches
are uploaded once and stay device-resident (noupdate); per-request tokens
are the only per-step host→device transfer (advancedload of a few bytes);
sampled tokens are fetched back lazily in batches (delegatestore).

``serve()`` is the one-shot static-batch path: one group of ``batch``
identical requests, prefill + ``gen - 1`` decode steps.  The continuous-
batching engine (``repro.serve``) generalizes it to request-level
scheduling; ``--engine`` runs a seeded open-loop trace through it.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b --reduced \
        --batch 4 --prompt-len 16 --gen 16
    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b --reduced \
        --engine --n-requests 24 --rate 50 --capacity 4 --policy fcfs
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced as reduce_cfg
from repro.launch.compile_cache import use_compile_cache
from repro.models import Transformer


def serve(cfg, *, batch: int, prompt_len: int, gen: int, seed: int = 0):
    model = Transformer(cfg)
    params = model.init(jax.random.key(seed))     # resident (noupdate)
    rng = np.random.default_rng(seed)
    max_seq = prompt_len + gen

    if cfg.input_embeds:
        prompt = {"embeds": jnp.asarray(rng.standard_normal(
            (batch, prompt_len, cfg.d_model), dtype=np.float32))}
    else:
        prompt = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab, (batch, prompt_len)), jnp.int32)}

    prefill = jax.jit(lambda p, b: model.prefill(p, b, max_seq=max_seq))
    decode = jax.jit(model.decode_step, donate_argnums=(1,))

    t0 = time.perf_counter()
    logits, cache = prefill(params, prompt)
    if cfg.n_codebooks:
        logits = logits[..., 0, :]
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    jax.block_until_ready(tok)
    t_prefill = time.perf_counter() - t0

    out_tokens = [tok]
    t0 = time.perf_counter()
    for i in range(gen - 1):
        pos = jnp.full((batch,), prompt_len + i, jnp.int32)
        if cfg.input_embeds:
            step_in = {"embeds": jnp.zeros((batch, cfg.d_model),
                                           jnp.float32)}
        else:
            step_in = {"tokens": tok}
        logits, cache = decode(params, cache, step_in, pos)
        if cfg.n_codebooks:
            logits = logits[..., 0, :]
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out_tokens.append(tok)
    # delegatestore: one fetch for the whole generation
    generated = np.stack([np.asarray(t) for t in out_tokens], axis=1)
    t_decode = time.perf_counter() - t0
    # gen == 1 never enters the decode loop: the only token comes from the
    # prefill, so decode throughput is 0 by definition (not prefill tokens
    # divided by an ~empty decode timer, which reported nonsense here).
    decode_tok_s = (batch * (gen - 1) / max(t_decode, 1e-9)
                    if gen > 1 else 0.0)
    total = t_prefill + t_decode
    return {
        "generated": generated,
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        "decode_tok_s": decode_tok_s,
        "tokens_per_s": batch * gen / max(total, 1e-9),
    }


def run_engine(cfg, *, n_requests: int, rate_rps: float, capacity: int,
               policy: str, join_policy: str = "continuous",
               max_seq: int = 64, seed: int = 0,
               respect_arrivals: bool = True):
    """Replay a seeded open-loop trace through the continuous-batching
    engine (``repro.serve``) and return its report."""
    from repro.serve import Engine, ServeRuntime, make_trace
    rt = ServeRuntime(cfg, max_seq=max_seq, seed=seed)
    eng = Engine(rt, capacity=capacity, join_policy=join_policy,
                 policy=policy)
    reqs = make_trace(cfg, n_requests=n_requests, rate_rps=rate_rps,
                      seed=seed, max_seq=max_seq)
    return eng.run(reqs, respect_arrivals=respect_arrivals)


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching engine over a seeded trace")
    ap.add_argument("--n-requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=50.0,
                    help="open-loop arrival rate (requests/s)")
    ap.add_argument("--capacity", type=int, default=4)
    ap.add_argument("--policy", default="fcfs", choices=("fcfs", "sjf"))
    ap.add_argument("--join-policy", default="continuous",
                    choices=("continuous", "static"))
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)

    if args.engine:
        rep = run_engine(cfg, n_requests=args.n_requests,
                         rate_rps=args.rate, capacity=args.capacity,
                         policy=args.policy, join_policy=args.join_policy,
                         max_seq=args.max_seq, seed=args.seed)
        print(f"[serve.engine] {rep['n_requests']} requests in "
              f"{rep['wall_s']:.2f}s  {rep['requests_per_s']:.1f} req/s  "
              f"{rep['tokens_per_s']:.0f} tok/s  "
              f"p50={rep['latency_p50_s']*1e3:.0f}ms "
              f"p99={rep['latency_p99_s']*1e3:.0f}ms  "
              f"occupancy={rep['occupancy']:.2f}")
        print(f"[serve.engine] pool: {rep['pool']}")
        return

    out = serve(cfg, batch=args.batch, prompt_len=args.prompt_len,
                gen=args.gen)
    print(f"[serve] generated shape {out['generated'].shape} "
          f"prefill={out['prefill_s']:.2f}s decode={out['decode_s']:.2f}s "
          f"({out['tokens_per_s']:.0f} tok/s end-to-end, "
          f"{out['decode_tok_s']:.0f} tok/s decode)")
    print("[serve] sample:", out["generated"][0][:12])


if __name__ == "__main__":
    main()
