"""end to end: 95th percentile, over all transactions offered in the
window, of due time -> ``a_deliver`` at the validator it was submitted
to; one never delivered counts the wait so far."""

from benchmarks.harness import stats


def read(obs):
    p95 = stats.percentile(obs["samples"].get("commit_latency_s", ()), 95)
    return None if p95 is None else 1e3 * p95
