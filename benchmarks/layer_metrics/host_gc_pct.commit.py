"""host runtime: full collections (``host.gc``) over ``pump.run``."""

from benchmarks.harness import spanbook


def read(obs):
    return spanbook.gc_pct(obs, "pump.run")
