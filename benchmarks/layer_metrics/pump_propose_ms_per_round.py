"""host pump: making, signing and broadcasting a round's vertices — self
times of ``pump.propose`` and ``sign.vertex`` — per round."""

from benchmarks.harness import spanbook


def read(obs):
    return spanbook.self_ms_per_round(obs, "pump.propose", "sign.vertex")
