"""Shared by the readers of one block kind's roofline share: the least
time of that kind's blocks in one decode step on this chip over their
device time in the step.  That time is the named scope's span over the
decode executions of the traced window: the union of the intervals of
its ops and of the asynchronous copies that prefetch its weights while
another block computes (``bench/scope_trace.py``), so every byte the
kind reads is read inside it.  The least time is the longer of the
kind's bytes at the HBM bandwidth and its operations at the bf16 peak,
as the reference counts them (``decode_work``) for the step's active
rows: the decode tokens over the decode steps of the window."""
from bench.metrics._common import reference_of


def roofline(obs, kind: str):
    sc, peak = obs.get("scopes"), obs.get("peak")
    if not sc or peak is None or not sc["executions"]:
        return None
    if not sc["spans"][kind]:
        return None
    steps = sc["executions"]
    work = reference_of(obs).decode_work(obs["cfg"], obs["decode_tokens"] / steps)[kind]
    least = max(work["bytes"] / peak["hbm_bytes_per_s"],
                work["flops"] / peak["bf16_flops"])
    return 100.0 * least / (sc["spans"][kind] / steps)
