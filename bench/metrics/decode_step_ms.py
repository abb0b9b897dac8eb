"""Device time of one decode step (one execution of the decode
program), in ms, the mean over the traced window."""
from bench.metrics._common import decode_executions


def read(obs):
    ex = decode_executions(obs)
    if ex is None:
        return None
    return 1e3 * sum(e.seconds for e in ex) / len(ex)
