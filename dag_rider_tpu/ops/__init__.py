"""JAX device kernels. Every module of the package that compiles a
program imports from here, so this is the one place the persistent
compile cache is switched on (utils/jaxcache.py has the rule): a node, a
sidecar, a Simulation, the bench, the smoke, the tests and a library
user all get the same cache without asking for it."""

from dag_rider_tpu.utils.jaxcache import enable_persistent_cache

enable_persistent_cache()

from dag_rider_tpu.ops.dag_kernels import (
    admission_mask,
    closure_from,
    closure_from_full,
    leader_reach,
    pairwise_reach,
    reach_chain,
    round_complete,
    strong_edge_quorum,
    wave_commit_votes,
)

__all__ = [
    "admission_mask",
    "closure_from",
    "closure_from_full",
    "leader_reach",
    "pairwise_reach",
    "reach_chain",
    "round_complete",
    "strong_edge_quorum",
    "wave_commit_votes",
]
