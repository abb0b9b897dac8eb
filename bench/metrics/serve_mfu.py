"""The whole serving cell's share of the chips' bf16 peak: the forward
pass's operations per token times the tokens passed through the model in
the traced window (prompt tokens in prefill, one per decode step and
active row), over the window and the chips."""
from bench.metrics._common import reference_of


def read(obs):
    trace, peak = obs.get("trace"), obs.get("peak")
    if trace is None or peak is None or "capacity" not in obs:
        return None
    tokens = obs["prompt_tokens"] + obs["decode_tokens"]
    flops = reference_of(obs).flops_per_token(obs["cfg"]) * tokens
    return 100.0 * flops / trace.window_s / (obs["chips"] * peak["bf16_flops"])
