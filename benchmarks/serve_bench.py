"""Serving benchmark: continuous batching vs the static-batch baseline.

Both modes replay the IDENTICAL seeded open-loop trace (Poisson
arrivals, skewed generation-length mix) through the same ``ServeRuntime``
— same resident params, same compiled prefill/decode/insert programs —
so the measured gap is purely the scheduling discipline:

* ``static``    — requests may only join when the decode batch has fully
                  drained, so every group runs to its slowest member
                  (head-of-line blocking on the long tail);
* ``continuous``— freed rows are backfilled at any step boundary, so the
                  batch stays occupied.

A short warmup trace runs first (excluded from timing) to compile every
shape bucket and the decode step.

Invariants checked on every run (``--check`` also gates the speedup):
all requests finish, none dropped, p99 latency finite, zero KV-slot
leaks.

    PYTHONPATH=src python benchmarks/serve_bench.py --quick
    PYTHONPATH=src python benchmarks/serve_bench.py --check   # CI gate

Writes ``BENCH_serve_<YYYYMMDD>.json`` at the repo root (CI uploads
``BENCH_*.json`` artifacts).
"""
from __future__ import annotations

import argparse
import json
import math
import time

import numpy as np

from repro.configs import get_config, reduced
from repro.launch.compile_cache import use_compile_cache
from repro.serve import Engine, ServeRuntime, make_trace

# mostly-short with a long tail: the traffic shape where static batching
# pays its head-of-line penalty
GEN_MIX = ((4, 0.50), (8, 0.25), (112, 0.25))
PROMPT_MIX = ((8, 0.70), (16, 0.30))
SPEEDUP_FLOOR = 1.5


def run_mode(rt, reqs, *, join_policy: str, capacity: int):
    eng = Engine(rt, capacity=capacity, join_policy=join_policy,
                 policy="fcfs")
    # fresh copies: Request objects are mutated by the engine
    replay = [r.__class__(rid=r.rid, prompt=r.prompt.copy(),
                          max_new_tokens=r.max_new_tokens,
                          arrival_s=r.arrival_s) for r in reqs]
    rep = eng.run(replay, respect_arrivals=False)
    rep["leaked_slots"] = eng.pool.in_use        # assert_no_leaks already ran
    return rep


def check(rep, n_expected: int) -> None:
    assert rep["n_requests"] == n_expected, (rep["n_requests"], n_expected)
    assert rep["dropped"] == 0
    assert rep["leaked_slots"] == 0
    assert math.isfinite(rep["latency_p99_s"]), rep["latency_p99_s"]


def bench(*, arch: str, n_requests: int, capacity: int, max_seq: int,
          seed: int, gate: bool):
    cfg = reduced(get_config(arch))
    rt = ServeRuntime(cfg, max_seq=max_seq, seed=seed)

    trace = make_trace(cfg, n_requests=n_requests, rate_rps=1e6, seed=seed,
                       prompt_mix=PROMPT_MIX, gen_mix=GEN_MIX,
                       max_seq=max_seq)

    # warmup: compile every shape the timed runs will hit
    # (excluded from timing) — one request per distinct prompt length for
    # full bucket coverage, same trace length and same max generation
    # length so the decode/park jits are byte-identical.
    from repro.serve import Request
    lens = sorted({r.prompt_len for r in trace})
    gen_cap = max(r.max_new_tokens for r in trace)

    def _prompt(L):
        return (np.zeros((L, cfg.d_model), np.float32)
                if cfg.input_embeds else np.zeros((L,), np.int32))
    warm = [Request(rid=1000 + i, prompt=_prompt(lens[i % len(lens)]),
                    max_new_tokens=gen_cap if i == 0 else 2)
            for i in range(len(trace))]
    run_mode(rt, warm, join_policy="continuous", capacity=capacity)

    cont = run_mode(rt, trace, join_policy="continuous", capacity=capacity)
    stat = run_mode(rt, trace, join_policy="static", capacity=capacity)
    check(cont, n_requests)
    check(stat, n_requests)

    ratio = cont["requests_per_s"] / max(stat["requests_per_s"], 1e-9)
    row = {
        "arch": cfg.name,
        "n_requests": n_requests,
        "capacity": capacity,
        "max_seq": max_seq,
        "seed": seed,
        "speedup_requests_per_s": ratio,
        "continuous": {k: cont[k] for k in (
            "requests_per_s", "tokens_per_s", "latency_p50_s",
            "latency_p99_s", "occupancy", "steps", "fetch_batches")},
        "static": {k: stat[k] for k in (
            "requests_per_s", "tokens_per_s", "latency_p50_s",
            "latency_p99_s", "occupancy", "steps")},
        "pool": cont["pool"],
    }
    print(f"[serve_bench] {cfg.name}: continuous "
          f"{cont['requests_per_s']:.1f} req/s (occ {cont['occupancy']:.2f})"
          f" vs static {stat['requests_per_s']:.1f} req/s "
          f"(occ {stat['occupancy']:.2f}) -> {ratio:.2f}x")
    if gate:
        assert ratio >= SPEEDUP_FLOOR, (
            f"continuous batching speedup {ratio:.2f}x below the "
            f"{SPEEDUP_FLOOR}x floor")
    return row


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-14b,rwkv6-3b",
                    help="comma-separated arch list (one bench row each)")
    ap.add_argument("--n-requests", type=int, default=48)
    ap.add_argument("--capacity", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: ~20 requests, no speedup gate")
    ap.add_argument("--check", action="store_true",
                    help="gate: continuous >= 1.5x static requests/s")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.quick:
        args.n_requests = 20
        args.capacity = min(args.capacity, 4)

    rows = []
    for arch in [a.strip() for a in args.arch.split(",") if a.strip()]:
        rows.append(bench(arch=arch, n_requests=args.n_requests,
                          capacity=args.capacity, max_seq=args.max_seq,
                          seed=args.seed, gate=args.check))
    path = args.out or f"BENCH_serve_{time.strftime('%Y%m%d')}.json"
    snap = {"date": time.strftime("%Y-%m-%d"), "bench": "serve",
            "rows": rows,
            # single-arch "row" kept so older trajectory diffs keep working
            "row": rows[0]}
    with open(path, "w") as f:
        json.dump(snap, f, indent=2, sort_keys=True, default=float)
    print(f"[serve_bench] snapshot written to {path}")
    return rows


if __name__ == "__main__":
    main()
