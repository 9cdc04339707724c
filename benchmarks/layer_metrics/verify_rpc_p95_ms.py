"""end to end: 95th percentile over all RPCs sent in the window — what a
validator waits for one round's accept mask."""

from benchmarks.harness import stats


def read(obs):
    p95 = stats.percentile(obs["samples"].get("rpc_latency_s", ()), 95)
    return None if p95 is None else 1e3 * p95
