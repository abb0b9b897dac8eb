"""Driver of the planner cells: a Polybench program planned by
``repro.core.plan`` and executed by ``repro.core.execute`` (compiled, on
the ``jax`` backend), back to back over the window, from host inputs to
host outputs: a closed loop with one caller, as a host program calls its
offloaded region.

Traffic keys: ``program`` (a file under ``bench/programs/`` and one under
``bench/ref/``), ``input_sets`` (how many seeded input sets the
executions cycle through), ``checked_executions`` (how many executions of
the window, drawn from the seed, are compared with the reference) and
``trace_seconds`` (the longest window a traced run records).
"""
from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

from bench import harness
from bench.harness import BENCH


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    import jax

    from repro.core import execute, plan

    cfg, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    name = traffic["program"]
    ds = cfg["datasets"][name]
    program = harness.load_module(BENCH / "programs" / f"{name}.py")
    ref = harness.load_module(BENCH / "ref" / f"{name}.py")
    dtype = np.dtype(cfg["dtype"])

    t0 = time.perf_counter()
    sets = [program.make_inputs(ds, harness.rng_for(seed, 1, i), dtype)
            for i in range(traffic["input_sets"])]
    p = program.build(ds, sets[0])
    pl = plan(p)
    kw = dict(mode="compiled", backend="jax")
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        for inputs in sets:                       # compiles, then warm
            execute(pl, inputs, **kw)
        setup_s = time.perf_counter() - t0

        seconds = ctx["seconds"]
        if ctx["trace_dir"]:
            seconds = min(seconds, traffic["trace_seconds"])
        pick = harness.rng_for(seed, 2)
        sampled, stats = {}, None
        n = 0
        with harness.CompileClock() as clock, ctx["tracer"]():
            with harness.span(ctx["window_span"], bool(ctx["trace_dir"])):
                tw = time.perf_counter()
                while True:
                    i = n % len(sets)
                    with harness.span("bench.execute", bool(ctx["trace_dir"])):
                        outs, stats = execute(pl, sets[i], **kw)
                    n += 1
                    # reservoir sample of the window's executions
                    slot = (n - 1 if n <= traffic["checked_executions"]
                            else int(pick.integers(0, n)))
                    if slot < traffic["checked_executions"]:
                        sampled[slot] = (i, outs[program.OUTPUT])
                    if time.perf_counter() - tw >= seconds:
                        break
                window_s = time.perf_counter() - tw

    device = ctx["device_record"]()
    refs = {}
    checks_err = 0.0
    for i, out in sampled.values():
        if i not in refs:
            refs[i] = ref.reference(ds, sets[i])
        checks_err = max(checks_err, harness.err(out, refs[i])["max_rel_err"])
    work = ref.work(ds, dtype.itemsize)
    st = stats.as_dict()
    result = {
        "setup_s": setup_s,
        "end_to_end": {"exec_ms": window_s * 1e3 / n, "setup_s": setup_s},
        "attempted": n, "failed": 0,
        "checks": [{"name": "max_rel_err", "value": checks_err,
                    "limit": ctx["limits"]["max_rel_err"]}],
        "device": device,
        "observed": {
            "window_s": window_s, "executions": n,
            "compiles": clock.compiles, "exec_stats": st, "work": work,
            "checked": len(sampled),
        },
    }
    if ctx.get("keep"):
        result["kept"] = {"ds": ds, "ref": ref, "sets": sets, "refs": refs}
    return result


def control_reading(kept) -> float:
    """The compared number of the control: the reference one precision
    below the configuration's, put in the program's place, on the input
    sets the window checked."""
    return max(harness.err(kept["ref"].control(kept["ds"], kept["sets"][i]),
                           r)["max_rel_err"]
               for i, r in kept["refs"].items())

