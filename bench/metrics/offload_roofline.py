"""Share of the roofline of the offloaded region: the least time its
work needs on this chip (its operations at the bf16 peak, or its least
device-memory traffic at the HBM bandwidth, whichever is longer; full
precision fp32 cannot reach the bf16 peak) over the device time of its
programs in the trace, per execution."""
from bench.metrics._common import planner_device_seconds


def read(obs):
    device_s, peak = planner_device_seconds(obs), obs.get("peak")
    if not device_s or peak is None:
        return None
    work = obs["work"]
    least = max(work["flops"] / peak["bf16_flops"],
                work["hbm_bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least * obs["executions"] / device_s
