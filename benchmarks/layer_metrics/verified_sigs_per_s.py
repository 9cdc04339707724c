"""end to end: signatures whose mask came back inside the window, over
the window's seconds."""

from benchmarks.harness import stats


def read(obs):
    back = obs["counters"].get("sigs_back_in_window")
    return None if back is None else stats.rate(back, obs["seconds"])
