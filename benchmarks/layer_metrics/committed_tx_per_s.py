"""client: transactions delivered at their submitting validator inside the
window, over the window's seconds. Far under capacity this follows the
latency and steps by a whole wave's deliveries when a wave's decision
crosses the window's end, so it carries no bound."""

from benchmarks.harness import stats


def read(obs):
    done = obs["counters"].get("tx_delivered_in_window")
    return None if done is None else stats.rate(done, obs["seconds"])
