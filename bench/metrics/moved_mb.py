"""Host-device traffic of one execution in MB (1e6 bytes): the
executor's own byte counts, up and down, which the plan decides."""


def read(obs):
    st = obs.get("exec_stats")
    if st is None:
        return None
    return (st["h2d_bytes"] + st["d2h_bytes"]) / 1e6
