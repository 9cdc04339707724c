"""sidecar server: between leaving ``backend.verify_batch`` for one RPC and
entering it for the next — reply, gRPC, the next request's decode."""

from benchmarks.harness import stats


def read(obs):
    gap = stats.mean(obs["samples"].get("server_gap_s", ()))
    return None if gap is None else 1e3 * gap
