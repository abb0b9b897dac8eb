"""The whole planner cell's share of the chips' bf16 peak: the
program's operations times the executions of the traced window, over
the window and the chips."""


def read(obs):
    trace, peak = obs.get("trace"), obs.get("peak")
    if trace is None or peak is None or "exec_stats" not in obs:
        return None
    flops = obs["work"]["flops"] * obs["executions"]
    return 100.0 * flops / trace.window_s / (obs["chips"] * peak["bf16_flops"])
