"""Beyond-paper: the planner's schedule applied to a real training loop.

Compares wall-clock of N training steps with
    sync     — batch built + uploaded synchronously inside the loop,
               metrics fetched every step (the naive schedule), vs
    planned  — prefetch thread uploads batch i+1 during step i
               (advancedload) and metrics are fetched once at the end
               (delegatestore sunk ALAP).

``run_plan_executor`` additionally runs the same schedule as an explicit
block-``Program`` (``plan_step_program``) through the plan executor in
three execution modes — interpreted, compiled (per-iteration segment
dispatch), and compiled+loop (the whole step loop rolled into one
``lax.fori_loop`` launch) — isolating how much of the step loop's cost
is Python directive dispatch vs the schedule itself.
"""
from __future__ import annotations

import time

import jax

from repro.configs import get_config, reduced
from repro.core import execute, naive_plan, plan
from repro.data import PrefetchIterator, SyntheticLM
from repro.launch.compile_cache import use_compile_cache
from repro.launch.train import make_train_step
from repro.models import Transformer
from repro.optim import default_optimizer, plan_step_program

STEPS = 20
BATCH, SEQ = 8, 128


def run(arch: str = "internlm2-20b"):
    cfg = reduced(get_config(arch))
    model = Transformer(cfg)
    opt = default_optimizer(cfg)
    src = SyntheticLM(cfg, BATCH, SEQ, seed=0)
    step_fn = make_train_step(model, opt)

    def fresh():
        params = model.init(jax.random.key(0))
        return params, opt.init(params)

    # --- sync schedule --------------------------------------------------
    params, opt_state = fresh()
    batch0 = {k: jax.device_put(v) for k, v in src.batch_at(0).items()}
    params, opt_state, m = step_fn(params, opt_state, batch0)  # compile
    float(m["loss"])
    t0 = time.perf_counter()
    for i in range(STEPS):
        host_batch = src.batch_at(i)                       # host produce
        dev_batch = {k: jax.device_put(v)
                     for k, v in host_batch.items()}       # upload (sync pt)
        params, opt_state, metrics = step_fn(params, opt_state, dev_batch)
        float(metrics["loss"])                             # fetch every step
    t_sync = time.perf_counter() - t0

    # --- planned schedule ------------------------------------------------
    params, opt_state = fresh()
    params, opt_state, m = step_fn(params, opt_state, batch0)
    float(m["loss"])
    it = PrefetchIterator(src, start_index=0, depth=2)     # advancedload
    t0 = time.perf_counter()
    metrics = None
    for i in range(STEPS):
        dev_batch = next(it)
        params, opt_state, metrics = step_fn(params, opt_state, dev_batch)
    loss = float(metrics["loss"])                          # one late fetch
    t_planned = time.perf_counter() - t0
    it.close()

    return {
        "name": "train_overlap",
        "t_sync_ms": t_sync * 1e3,
        "t_planned_ms": t_planned * 1e3,
        "speedup": t_sync / t_planned,
        "final_loss": loss,
    }


def run_plan_executor(n_steps: int = 64, reps: int = 3):
    """The miniature train loop as a block program, every cell of
    {naive, optimized} x {interpreted, compiled, compiled+loop}, plus
    the plan-space explorer's winner (``policy="auto"``) as a fourth
    row — the tuner must never lose to the fixed schedules it
    enumerates.  All wall times are steady-state: the jits are warmed
    before timing and one-time plan lowering is surfaced separately
    (``compile_ms``, from ``ExecStats.compile_time``)."""
    p = plan_step_program(n_steps=n_steps)
    plans = {"naive": naive_plan(p), "opt": plan(p)}
    modes = (("interpreted", dict(mode="interpreted")),
             ("compiled", dict(mode="compiled", fuse_loops=False)),
             ("compiled_loop", dict(mode="compiled", fuse_loops=True)))
    out = {"name": "train_plan_executor", "n_steps": n_steps}
    compile_ms = 0.0
    for pname, pl in plans.items():
        for label, kw in modes:
            _, s0 = execute(pl, **kw)                   # warm the jits
            compile_ms += s0.compile_time * 1e3
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                execute(pl, **kw)
                ts.append(time.perf_counter() - t0)
            out[f"t_{pname}_{label}_ms"] = min(ts) * 1e3
    out["compile_ms"] = compile_ms
    out["speedup_interpreted"] = (out["t_naive_interpreted_ms"]
                                  / out["t_opt_interpreted_ms"])
    out["speedup_compiled"] = (out["t_naive_compiled_ms"]
                               / out["t_opt_compiled_ms"])
    out["compile_win_opt"] = (out["t_opt_interpreted_ms"]
                              / out["t_opt_compiled_ms"])
    out["loop_win_opt"] = (out["t_opt_compiled_ms"]
                           / out["t_opt_compiled_loop_ms"])

    # --- plan-space explorer: the tuned winner ---------------------------
    from repro.core import winner_exec_kwargs
    tuned = plan(p, policy="auto", reps=reps)
    kw = winner_exec_kwargs(tuned)   # honors fuse_loops AND donate
    execute(tuned, **kw)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        execute(tuned, **kw)
        ts.append(time.perf_counter() - t0)
    out["t_auto_ms"] = min(ts) * 1e3
    out["auto_variant"] = tuned.meta["tuning"]["chosen"]
    out["auto_candidates"] = sum(
        1 for c in tuned.meta["tuning"]["candidates"] if c["valid"])
    chosen = tuned.predicted_cost()
    out["auto_predicted_ms"] = chosen["predicted_s"] * 1e3
    # persistent-cache + calibration outcome (ISSUE 5): a repeated run
    # answers from the tuning cache with zero measurements
    cache_info = tuned.tuning_cache_info()
    out["auto_cache_hit"] = cache_info["hit"]
    out["auto_measurements"] = cache_info["measurements"]
    cal = tuned.tuning_calibration() or {}
    out["auto_calibration_accepted"] = bool(cal.get("accepted"))
    return out


def main():
    use_compile_cache()
    r = run()
    print(f"{r['name']},{r['t_planned_ms'] * 1e3 / STEPS:.0f},"
          f"speedup={r['speedup']:.2f}x;sync_ms={r['t_sync_ms']:.0f};"
          f"planned_ms={r['t_planned_ms']:.0f}")
    e = run_plan_executor()
    extra = ";".join(f"{k}={v:.2f}" if isinstance(v, float) else f"{k}={v}"
                     for k, v in e.items() if k != "name")
    print(f"{e['name']},{e['t_opt_compiled_ms'] * 1e3:.0f},{extra}")
    return r


if __name__ == "__main__":
    main()
