"""host pump: the window's wall time over the DAG rounds it advanced."""


def read(obs):
    c = obs["counters"]
    if not c.get("rounds_advanced"):
        return None
    return 1e3 * c["window_s"] / c["rounds_advanced"]
