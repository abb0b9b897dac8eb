"""Shared by the per-layer metric readers: the serving programs' device
time from the trace, and the serving configuration's work counts."""
from bench import harness
from bench.harness import BENCH

# jit of ServeRuntime._decode_impl; prefill and park are both jit(<lambda>),
# told apart by the program ids recorded at warm-up
DECODE_MODULE = "jit__decode_impl"


def decode_executions(obs):
    trace = obs.get("trace")
    if trace is None or "capacity" not in obs:
        return None
    ex = trace.executions_of(module=DECODE_MODULE)
    return ex or None


def prefill_executions(obs):
    trace, cal = obs.get("trace"), obs.get("calibration")
    if trace is None or not cal or not cal.get("prefill"):
        return None
    ex = trace.executions_of(program_ids=cal["prefill"])
    return ex or None


def reference_of(obs):
    return harness.load_module(
        BENCH / "ref" / f"{obs['cfg']['reference']}.py")


def planner_device_seconds(obs):
    """Device seconds of the planner's programs in the traced window, per
    device, or None."""
    trace = obs.get("trace")
    if trace is None or "exec_stats" not in obs or not trace.executions:
        return None
    return sum(e.seconds for e in trace.executions) / trace.devices
