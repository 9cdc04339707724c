"""node loop: validator 0's busy time in its loop (``node.tick``: client
blocks in, the transport's inbox through reliable broadcast into the
process, ``Process.step``; the idle sleep left out), everything nested
in it included, per round."""

from benchmarks.harness import validatorbook


def read(obs):
    return validatorbook.total_ms_per_round(obs, "node.tick")
