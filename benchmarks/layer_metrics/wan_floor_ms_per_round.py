"""WAN: what the configuration's one-way delays alone make a round cost
— each vertex three hops of reliable broadcast (VAL out, 2f+1 ECHOs in,
2f+1 READYs in), a round once 2f+1 vertices are delivered — with every
processor infinitely fast (``reference_cluster.wan_round_floor_ms``).
Beside ``round_ms.wan``: the difference is the hosts'."""

from benchmarks.harness import reference_cluster


def read(obs):
    c = obs.get("config", {})
    if "one_way_delay_ms" not in c or not obs.get("counters", {}).get("rounds_advanced"):
        return None
    names = c["regions"]
    regions = [names[i % len(names)] for i in range(c["n"])]
    return reference_cluster.wan_round_floor_ms(c["n"], c["f"], regions, c["one_way_delay_ms"])
