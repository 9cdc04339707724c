"""WAN: ``wan_floor_ms_per_round`` over the validators left alive — what
the configuration's one-way delays alone make a round cost once the
traffic's ``crashed`` are gone: they send, echo and propose nothing and
a quorum is still 2f+1 of n, so with f down every quorum waits for the
farthest live pair (``reference_crash.wan_round_floor_ms``). Beside
``round_ms.crash``: the difference is the hosts'."""

from benchmarks.harness import reference_crash


def read(obs):
    c = obs.get("config", {})
    if "one_way_delay_ms" not in c or not obs.get("counters", {}).get("rounds_advanced"):
        return None
    names = c["regions"]
    regions = [names[i % len(names)] for i in range(c["n"])]
    crashed = set(obs.get("traffic", {}).get("crashed", ()))
    live = [i for i in range(c["n"]) if i not in crashed]
    return reference_crash.wan_round_floor_ms(
        c["n"], c["f"], regions, c["one_way_delay_ms"], live
    )
