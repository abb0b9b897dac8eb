"""Share of the decode step's roofline: the least time of a step on
this chip over its device time.  A step must read every weight once
(of the embedding only the active rows it looks up) and read and write
each active row's recurrent state (at the HBM bandwidth),
and do the forward pass's operations for each active row (at the bf16
peak); the longer of the two is the least time.  Active rows are the
decode tokens over the decode steps of the traced window."""
from bench.metrics._common import decode_executions, reference_of


def read(obs):
    ex, peak = decode_executions(obs), obs.get("peak")
    if ex is None or peak is None:
        return None
    ref, cfg = reference_of(obs), obs["cfg"]
    active = obs["decode_tokens"] / len(ex)
    embed = cfg["vocab"] * cfg["d_model"]
    weight_bytes = (ref.param_count(cfg) - embed + active * cfg["d_model"]) * 2
    nbytes = weight_bytes + 2 * active * ref.state_bytes_per_slot(cfg)
    least = max(nbytes / peak["hbm_bytes_per_s"],
                ref.flops_per_token(cfg) * active / peak["bf16_flops"])
    step = sum(e.seconds for e in ex) / len(ex)
    return 100.0 * least / step
