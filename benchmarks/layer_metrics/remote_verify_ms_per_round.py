"""remote verify: validator 0's time in ``remote.verify`` — one
``RemoteVerifier.verify_batch``: encode, the RPC to the sidecar that
holds the chip, the mask — per round. Its loop waits for it."""

from benchmarks.harness import validatorbook


def read(obs):
    return validatorbook.total_ms_per_round(obs, "remote.verify")
