"""coin: signing shares and combining them into a wave's leader — self
times of ``coin.share`` and ``coin.combine`` — per round."""

from benchmarks.harness import spanbook


def read(obs):
    return spanbook.self_ms_per_round(obs, "coin.share", "coin.combine")
