"""transport: validator 0's time sending, per round — ``net.broadcast``
on the loop's thread (encode, a MAC a peer, the delay's verdict, the
hand-over to the delay queue) and ``net.send`` on the delay thread (one
attempt handed to gRPC)."""

from benchmarks.harness import validatorbook


def read(obs):
    return validatorbook.self_ms_per_round(obs, "net.broadcast", "net.send")
