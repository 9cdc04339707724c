"""transport: validator 0's time in ``net.recv`` — a received frame's MAC
check, decode and place in the inbox, on gRPC's server threads — per
round."""

from benchmarks.harness import validatorbook


def read(obs):
    return validatorbook.self_ms_per_round(obs, "net.recv")
