"""Share of the traced window of a serving cell in which no operation
ran on the device: 1 - union of op intervals / window."""


def read(obs):
    trace = obs.get("trace")
    if trace is None or "capacity" not in obs or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
