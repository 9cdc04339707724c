"""Test configuration.

Forces JAX onto a virtual 8-device CPU platform *before* jax is imported
anywhere, so multi-chip sharding (Mesh/pjit/shard_map) is exercised in every
test run without TPU hardware. The driver separately dry-runs the multi-chip
path via ``__graft_entry__.dryrun_multichip``.
"""

import os

# Force, don't setdefault: the suite runs on the virtual 8-device CPU
# mesh whatever platform the launch environment names.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import gc  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# The persistent compilation cache (the limb-field/curve programs cost
# ~20s+ each to compile on CPU) comes with the package: importing
# dag_rider_tpu.ops switches it on (utils/jaxcache.py).
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Dynamic lock-race harness (round 14, analysis/races.py): under
# DAGRIDER_RACE=1 every package lock is order-tracked (deadlock cycles
# raise at the acquire attempt) and the declared guarded-field /
# serialized-method classes are enforced on every instance the suite
# builds — the chaos/fuzz tests become the race driver with zero
# per-test code. Installed at conftest import so it precedes any
# instance construction; violations raised in pool threads (which a
# Future would swallow) are re-checked session-wide below.
from dag_rider_tpu.config import env_flag as _env_flag  # noqa: E402

_RACE = _env_flag("DAGRIDER_RACE")
if _RACE:
    from dag_rider_tpu.analysis import races as _races  # noqa: E402

    _races.install()


@pytest.fixture(autouse=True)
def _unfreeze_heap_after_each_test():
    """A ``TPUVerifier.warmup`` that compiles freezes the heap
    (``gc.freeze``), once in a served process's life. A test worker
    compiles many verifiers: without this, every cycle a test leaves
    alive at a later test's freeze would stay for the worker's life."""
    yield
    gc.unfreeze()


@pytest.fixture(autouse=True, scope="module")
def _benchmark_files_open_an_empty_span_book(request):
    """The span book (``obs/spans.py``) is the process's and is never
    reset, and the files under ``tests/benchmark`` read metrics off it as
    a benchmark run does: from a process that has run one cell. A worker
    that ran other files first (``--dist loadfile`` deals them out as
    they come) still has their spans and counts, so a share of 0 read
    whatever a ``Node`` test had counted before it. Each such file
    starts from an empty book."""
    if "benchmark" in request.node.path.parts:
        from dag_rider_tpu.obs import spans

        for share in list(spans._shares):
            share.spans.clear()
            share.counts.clear()
    yield


def pytest_sessionfinish(session, exitstatus):
    if _RACE:
        leftover = _races.drain_violations()
        if leftover:
            raise _races.RaceViolation(
                "race harness recorded violation(s) the tests did not "
                "surface (worker-thread raises swallowed by Futures):\n"
                + "\n".join(leftover)
            )


# Long-tail tests (>= ~10 s each on this host, measured with
# --durations=50; together ~75% of suite wall time). Kept here as the
# single source of truth instead of scattering @pytest.mark.slow
# decorators — re-measure and update when the profile shifts.
_SLOW = {
    "test_pallas_group381.py::test_msm_kernel_pallas_impl_traces",
    "test_pallas_group381.py::test_padd381_pallas_program_traces",
    "test_bls_msm.py::test_scalar_mul_matches_host",
    "test_bls_msm.py::test_field_ring_ops_match_host",
    "test_bls_msm.py::test_msm_matches_host[1]",
    "test_bls_msm.py::test_msm_matches_host[5]",
    "test_net_transport.py::test_grpc_16_node_cluster_with_rbc_reaches_consensus",
    "test_full_stack.py::test_seven_nodes_two_equivocators_with_rbc",
    "test_full_stack.py::test_full_stack_byzantine_coin_share_plus_faults",
    "test_comb.py::test_comb_mask_matches_windowed_and_cpu",
    "test_parallel.py::test_sharded_comb_pallas_path_traces",
    "test_parallel.py::test_sharded_mask_equals_single_device_and_cpu",
    "test_parallel.py::test_sharded_msm_matches_host_oracle",
    "test_parallel.py::test_sharded_verifier_large_batch_matches_cpu_oracle",
    "test_parallel.py::test_round_step_matches_host_twins_on_figure1",
    # round-7 mesh-sharded async/AOT/pipeline seam (tier1-mesh CI lane
    # runs these with the slow marker included)
    "test_parallel.py::test_sharded_async_seam_dispatches_on_mesh",
    "test_parallel.py::test_sharded_sim_commit_order_matches_cpu",
    "test_pipeline.py::test_sharded_pipeline_masks_byte_identical[None-1]",
    "test_pipeline.py::test_sharded_pipeline_masks_byte_identical[None-2]",
    "test_pipeline.py::test_sharded_pipeline_masks_byte_identical[None-4]",
    "test_pipeline.py::test_sharded_pipeline_masks_byte_identical[16-1]",
    "test_pipeline.py::test_sharded_pipeline_masks_byte_identical[16-2]",
    "test_pipeline.py::test_sharded_pipeline_masks_byte_identical[16-4]",
    # round-8 parallel host-prep engine, mesh side (tier1-mesh and
    # tier1-prep CI lanes run these with the slow marker included)
    "test_prep.py::test_sharded_prep_masks_byte_identical[2]",
    "test_prep.py::test_sharded_prep_masks_byte_identical[4]",
    "test_pallas_group.py::test_finish_kernel_matches_jnp_tail",
    "test_pallas_group.py::test_pow22523_kernel_matches_field",
    "test_node.py::test_churn_restored_logs_stay_prefix_consistent",
    "test_node.py::test_node_restart_from_checkpoint_catches_up",
    "test_determinism.py::test_pipelined_coalesced_path_matches_sync_path",
    "test_determinism.py::test_device_verify_is_deterministic",
    "test_determinism.py::test_cpu_vs_device_verifier_commit_order_byte_identical",
    "test_determinism.py::test_dedup_coalesced_dispatch_is_delivery_identical",
    "test_determinism.py::test_dedup_does_not_conflate_corrupted_copies",
    "test_coin_e2e.py::test_byzantine_share_cannot_stall_the_coin",
    # round-20 multi-process cluster smoke: 4 OS processes over UDS w/
    # a real SIGKILL + rejoin (tier1-cluster CI lane runs it with the
    # slow marker included)
    "test_cluster.py::test_cluster_kill9_rejoin_zero_loss",
    # chip_smoke.py rehearsals: the full served path at n=4 (~20 s) and
    # the four-chip phase on the virtual mesh (sharded verifier +
    # sharded MSM compiles) — run them before spending chip time
    "test_chip_smoke.py::test_phase_a_rehearsal",
    "test_chip_smoke.py::test_phase_d_rehearsal_on_the_virtual_mesh",
}


def pytest_collection_modifyitems(config, items):
    """Two-tier lanes (SURVEY §4): tests in _SLOW get @slow, everything
    else gets @fast — so `pytest -m fast` (inner loop, ~3 min) and
    `pytest -m slow` (long tail) partition the suite; a bare `pytest`
    still runs everything."""
    import pytest as _pytest

    for item in items:
        name = item.nodeid.split("/")[-1]
        if name in _SLOW or "slow" in item.keywords:
            item.add_marker(_pytest.mark.slow)
        else:
            item.add_marker(_pytest.mark.fast)
