"""consensus: the window's seconds over the rounds validator 0 advanced
in it (its event log's ``round_advance`` stamps)."""


def read(obs):
    c = obs.get("counters", {})
    if not c.get("rounds_advanced") or not c.get("window_s"):
        return None
    return 1e3 * c["window_s"] / c["rounds_advanced"]
