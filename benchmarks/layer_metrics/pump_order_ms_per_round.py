"""host pump: delivering a decided wave's history and retiring what lies
under the horizon — self times of ``pump.order`` and ``pump.prune`` — per
round."""

from benchmarks.harness import spanbook


def read(obs):
    return spanbook.self_ms_per_round(obs, "pump.order", "pump.prune")
