"""reliable broadcast: validator 0's self time in ``rbc.val``,
``rbc.echo`` and ``rbc.ready`` — a received frame's vote booked, the
quorums looked at, a delivered vertex's admission checks in the process
— per round; the votes it sends in answer are the transport's."""

from benchmarks.harness import validatorbook


def read(obs):
    return validatorbook.self_ms_per_round(obs, "rbc.val", "rbc.echo", "rbc.ready")
