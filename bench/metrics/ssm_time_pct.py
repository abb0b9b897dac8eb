"""Share of the decode step's device time spent in the Mamba-2 blocks (the
named scope ``mamba2``), from the traced window's decode executions
(``bench/scope_trace.py``)."""


def read(obs):
    sc = obs.get("scopes")
    if not sc or not sc["device_s"]:
        return None
    return 100.0 * sc["seconds"]["mamba2"] / sc["device_s"]
