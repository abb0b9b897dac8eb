"""Fig. 6 analogue: Polybench speedups.

Paper axes: OMP2HMPP-generated vs sequential / OpenMP / hand-CUDA.
Container axes (CPU device): per problem we time
    seq       — pure-host numpy execution (the paper's 'sequential'),
    naive     — device offload, transfers at every callsite (Figs. 4a/5a),
    omp2hmpp  — the planner's optimized schedule (this paper's system),
    hand      — ideal hand-tuned bound: inputs pre-resident, zero
                transfers (the paper's 'hand-coded' reference point).
Derived columns: speedups vs seq and transfer bytes saved vs naive.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from repro.core import execute, naive_plan, plan, run_host_oracle
from repro.core.backend import _jitted_block as _jitted
from repro.launch.compile_cache import use_compile_cache
from repro.polybench import PROBLEMS, build

SIZES = {
    "2mm": dict(n=512), "3mm": dict(n=512), "gemm": dict(n=512, iters=4),
    "atax": dict(n=2048), "bicg": dict(n=2048), "mvt": dict(n=2048),
    "gesummv": dict(n=1536), "syrk": dict(n=512, iters=2),
    "covariance": dict(n=768), "jacobi2d": dict(n=768, iters=10),
}
REPS = 3


def _time(fn, reps=REPS):
    fn()                     # warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return min(ts)


def _time_hand(p, inputs):
    """Ideal bound: every offload block jitted, all arrays device-resident,
    one final host fetch."""
    import jax.numpy as jnp

    def run():
        env = {k: jnp.asarray(v) for k, v in inputs.items()}

        def exec_blocks(blocks, path):
            i = 0
            while i < len(blocks):
                blk = blocks[i]
                rel = blk.loop_path[len(path):]
                if not rel:
                    fn = _jitted(blk.fn, tuple(blk.reads),
                                 tuple(blk.writes))
                    outs = fn(*[env[v] for v in blk.reads])
                    for w, val in zip(blk.writes, outs):
                        env[w] = val
                    i += 1
                else:
                    lid = rel[0]
                    j = i
                    while j < len(blocks) and \
                            len(blocks[j].loop_path) > len(path) and \
                            blocks[j].loop_path[len(path)] == lid:
                        j += 1
                    for _ in range(p.loops[lid].n_iters):
                        exec_blocks(blocks[i:j], path + (lid,))
                    i = j
        exec_blocks(p.blocks, ())
        for name in p.outputs:
            np.asarray(env[name])
    return _time(run)


def run_suite() -> List[Dict]:
    rows = []
    for name in sorted(PROBLEMS):
        p, inputs = build(name, **SIZES[name])
        opt_plan, nv_plan = plan(p), naive_plan(p)

        t_seq = _time(lambda: run_host_oracle(p))
        t_nv = _time(lambda: execute(nv_plan))
        t_opt = _time(lambda: execute(opt_plan))
        t_hand = _time_hand(p, inputs)
        _, s_opt = execute(opt_plan)
        _, s_nv = execute(nv_plan)

        rows.append({
            "problem": name,
            "t_seq_ms": t_seq * 1e3,
            "t_naive_ms": t_nv * 1e3,
            "t_omp2hmpp_ms": t_opt * 1e3,
            "t_hand_ms": t_hand * 1e3,
            "speedup_vs_seq": t_seq / t_opt,
            "speedup_vs_naive": t_nv / t_opt,
            "hand_vs_omp2hmpp": t_opt / t_hand,
            "bytes_saved_vs_naive": (s_nv.h2d_bytes + s_nv.d2h_bytes
                                     - s_opt.h2d_bytes - s_opt.d2h_bytes),
            "transfers_opt": s_opt.h2d_transfers + s_opt.d2h_transfers,
            "transfers_naive": s_nv.h2d_transfers + s_nv.d2h_transfers,
        })
    return rows


def main():
    use_compile_cache()
    rows = run_suite()
    for r in rows:
        print(f"fig6_{r['problem']},{r['t_omp2hmpp_ms'] * 1e3:.0f},"
              f"speedup_seq={r['speedup_vs_seq']:.2f}x;"
              f"speedup_naive={r['speedup_vs_naive']:.2f}x;"
              f"hand_gap={r['hand_vs_omp2hmpp']:.2f}x;"
              f"bytes_saved={r['bytes_saved_vs_naive']}")
    return rows


if __name__ == "__main__":
    main()
