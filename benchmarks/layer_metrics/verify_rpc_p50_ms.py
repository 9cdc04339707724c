"""sidecar client: the median of the same books the tail is taken from."""

from benchmarks.harness import stats


def read(obs):
    p50 = stats.percentile(obs["samples"].get("rpc_latency_s", ()), 50)
    return None if p50 is None else 1e3 * p50
