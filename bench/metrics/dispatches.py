"""Dispatches of one execution, as the executor counts them: launches
of compiled segments plus transfers each way."""


def read(obs):
    st = obs.get("exec_stats")
    if st is None:
        return None
    return st["fused_launches"] + st["h2d_transfers"] + st["d2h_transfers"]
