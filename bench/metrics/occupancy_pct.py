"""Share of the decode batch doing useful work: tokens the decode steps
produced over decode executions in the trace times the capacity."""
from bench.metrics._common import decode_executions


def read(obs):
    ex = decode_executions(obs)
    if ex is None:
        return None
    return 100.0 * obs["decode_tokens"] / (len(ex) * obs["capacity"])
