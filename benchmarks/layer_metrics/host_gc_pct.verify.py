"""host runtime: full collections (``host.gc``) over the served time,
``sidecar.rpc`` + ``sidecar.between_rpcs``."""

from benchmarks.harness import spanbook


def read(obs):
    return spanbook.gc_pct(obs, "sidecar.rpc", "sidecar.between_rpcs")
