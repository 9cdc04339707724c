"""node loop: validator 0's time in ``node.checkpoint`` — the periodic
checkpoint its loop's thread writes between passes, three files and a
manifest with an fsync each — per round: time in which the validator
neither receives nor proposes."""

from benchmarks.harness import validatorbook


def read(obs):
    return validatorbook.total_ms_per_round(obs, "node.checkpoint")
