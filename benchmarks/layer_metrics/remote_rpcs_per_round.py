"""remote verify: how many RPCs (``remote.verify`` spans) validator 0
ships a round — 1 where its loop gathers a whole round into a batch,
n where every vertex goes alone."""

from benchmarks.harness import validatorbook


def read(obs):
    return validatorbook.count_per_round(obs, "remote.verify")
