"""client: how late the generator ran — injected minus due, 95th percentile."""

from benchmarks.harness import stats


def read(obs):
    lag = stats.percentile(obs["samples"].get("inject_lag_s", ()), 95)
    return None if lag is None else 1e3 * lag
