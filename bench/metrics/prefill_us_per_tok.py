"""Device time of the prefill programs per prompt token, in us."""
from bench.metrics._common import prefill_executions


def read(obs):
    ex = prefill_executions(obs)
    if ex is None or not obs["prompt_tokens"]:
        return None
    return 1e6 * sum(e.seconds for e in ex) / obs["prompt_tokens"]
