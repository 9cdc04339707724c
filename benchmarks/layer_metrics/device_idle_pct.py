"""device: 1 - the union of device-op intervals over the traced window."""

from benchmarks.harness import trace


def read(obs):
    return trace.idle_pct(obs["trace"])
