"""host pump: the commit rule — self times of ``pump.wave`` (coin ready,
leader, votes) and ``pump.chain`` (the walk back through undecided
waves) — per round."""

from benchmarks.harness import spanbook


def read(obs):
    return spanbook.self_ms_per_round(obs, "pump.wave", "pump.chain")
