"""Parallel host-prep engine equivalence (round-8 tentpole).

The engine changes WHERE prep rows are computed (row-block worker
threads, writing into block offsets of the staging-ring slot) and WHEN
whole prep calls run (ahead, on the seam thread, overlapping earlier
chunks' device execution) — never WHAT is computed. Masks must be
byte-identical to serial prep at every (workers, depth, bucket)
combination, on the single-chip and mesh-sharded verifiers, and the
staging-ring aliasing discipline (a slot is never rewritten while a
dispatch that shipped it may still be executing) must survive the
prep-ahead ordering.
"""

import collections
import random

import numpy as np
import pytest

from test_pipeline import N, _random_rounds, _signed_pool

from dag_rider_tpu.verifier.base import KeyRegistry
from dag_rider_tpu.verifier.cpu import CPUVerifier
from dag_rider_tpu.verifier.pipeline import VerifierPipeline
from dag_rider_tpu.verifier.prep import (
    MIN_BLOCK_ROWS,
    PrepEngine,
    default_prep_workers,
)
from dag_rider_tpu.verifier.tpu import TPUVerifier


@pytest.fixture(scope="module")
def keys():
    return KeyRegistry.generate(N)


# -- engine unit half -----------------------------------------------------


def test_plan_partitions_exactly():
    """Every plan covers [0, size) contiguously, exactly once, and small
    sizes stay a single block (no thread handoff below the floor)."""
    for workers in (1, 2, 3, 4, 8):
        eng = PrepEngine(workers)
        try:
            for size in (0, 1, 15, 16, 17, 31, 32, 48, 64, 100, 257):
                blocks = eng.plan(size)
                assert blocks[0][0] == 0
                assert blocks[-1][1] == size or (size == 0 and blocks == [(0, 0)])
                for (alo, ahi), (blo, bhi) in zip(blocks, blocks[1:]):
                    assert ahi == blo, "gap or overlap between blocks"
                if workers == 1 or size < 2 * MIN_BLOCK_ROWS:
                    assert len(blocks) == 1
                assert len(blocks) <= max(1, min(workers, size // MIN_BLOCK_ROWS))
        finally:
            eng.close()


def test_run_blocks_writes_every_row_and_counts():
    eng = PrepEngine(4)
    try:
        out = np.zeros(100, dtype=np.int64)

        def fill(lo, hi):
            out[lo:hi] = np.arange(lo, hi)

        eng.run_blocks(fill, eng.plan(100))
        assert np.array_equal(out, np.arange(100))
        assert eng.last_blocks == 4
        assert eng.rows_total == 100 and eng.rows_parallel == 100
        assert eng.parallel_fraction() == 1.0
        # a sub-floor dispatch takes the serial path and dilutes the gauge
        eng.run_blocks(fill, eng.plan(10))
        assert eng.last_blocks == 1
        assert 0.0 < eng.parallel_fraction() < 1.0
    finally:
        eng.close()


def test_run_blocks_propagates_persistent_exception():
    """A fault that survives the serial retry still propagates — the
    block-pool boundary contains transient worker faults, it does not
    invent masks for batches that cannot be prepped."""
    eng = PrepEngine(4)
    try:

        def boom(lo, hi):
            raise RuntimeError("worker failed")

        with pytest.raises(RuntimeError, match="worker failed"):
            eng.run_blocks(boom, eng.plan(64))
        assert eng.serial_retries == 1
    finally:
        eng.close()


def test_run_blocks_serial_retry_recovers_transient_fault():
    """A parallel-only fault (raises for worker blocks, lo > 0) is
    caught at the block-pool boundary and the whole range re-runs
    serially; the result is byte-complete because _prep_block-style
    fns fully overwrite their rows."""
    eng = PrepEngine(4)
    try:
        out = np.zeros(64, dtype=np.int64)
        retry_calls = []

        def flaky(lo, hi):
            if lo > 0:
                raise RuntimeError("transient worker fault")
            if (lo, hi) == (0, 64):
                retry_calls.append((lo, hi))
            out[lo:hi] = np.arange(lo, hi)

        eng.run_blocks(flaky, eng.plan(64))
        assert np.array_equal(out, np.arange(64))
        assert eng.serial_retries == 1
        # the retry was exactly one serial full-range pass
        assert retry_calls == [(0, 64)]
    finally:
        eng.close()


def test_seam_executor_is_fifo():
    eng = PrepEngine(2)
    try:
        order = []
        futs = [eng.submit(order.append, i) for i in range(16)]
        for f in futs:
            f.result()
        assert order == list(range(16))
    finally:
        eng.close()


def test_env_knob_and_engine_rebuild(keys, monkeypatch):
    """DAGRIDER_PREP_WORKERS seeds the default; the per-verifier
    prep_workers override rebuilds the engine on the next prep."""
    monkeypatch.setenv("DAGRIDER_PREP_WORKERS", "3")
    assert default_prep_workers() == 3
    reg, _ = keys
    v = TPUVerifier(reg)
    assert v.prep_stats()["workers"] == 3
    v.prep_workers = 2
    assert v.prep_stats()["workers"] == 2
    monkeypatch.setenv("DAGRIDER_PREP_WORKERS", "0")
    with pytest.raises(ValueError):
        default_prep_workers()


def test_metrics_prep_gauges_and_amortized_marker():
    from dag_rider_tpu.utils.metrics import Metrics

    m = Metrics()
    assert "verify_prep_workers" not in m.snapshot()
    m.observe_prep(4, 0.75)
    m.mark_verify_amortized()
    snap = m.snapshot()
    assert snap["verify_prep_workers"] == 4
    assert snap["verify_prep_parallel_fraction"] == 0.75
    assert snap["verify_timings_amortized"] == 1


# -- byte-identity half ---------------------------------------------------


@pytest.mark.parametrize("depth", [1, 2, 4])
@pytest.mark.parametrize("workers", [1, 2, 4])
def test_prep_masks_byte_identical(keys, workers, depth):
    """Property: row-block parallel prep + prep-ahead == serial prep ==
    CPU oracle at every (workers, depth, bucket) combination. Bucket 32
    forces over-cap chunking AND multi-block prep (32 rows = 2 blocks at
    4 workers); bucket 64 engages all 4 blocks on the padded tail."""
    reg, _ = keys
    cpu = CPUVerifier(reg)
    for bucket in (32, 64):
        rng = random.Random(10_000 * workers + 100 * depth + bucket)
        pool = _signed_pool(keys, 96, seed=rng.randrange(1 << 30))
        rounds = _random_rounds(pool, rng)
        want = [cpu.verify_batch(r) for r in rounds]
        assert any(not all(m) for m in want if m), "no corruption landed"

        bare = TPUVerifier(reg)
        bare.fixed_bucket = bucket
        bare.prep_workers = workers
        assert bare.verify_rounds(rounds) == want

        pipe = VerifierPipeline(
            TPUVerifier(reg), depth=depth, fixed_bucket=bucket, warmup=False
        )
        pipe.verifier.prep_workers = workers
        assert pipe.verify_rounds(rounds) == want
        flat = [v for r in rounds for v in r]
        assert pipe.verify_batch(flat) == [m for ms in want for m in ms]
        if workers > 1:
            assert pipe.stats()["prep_workers"] == workers
            assert pipe.verifier.prep_stats()["parallel_fraction"] > 0.0


@pytest.mark.parametrize("depth", [2, 4])
def test_sharded_prep_masks_byte_identical(keys, depth):
    """Round-8 acceptance, sharded side: the prep engine rides the
    placement hooks, so the MESH verifier at 4 workers must match the
    CPU oracle and its own serial prep — and the pipeline must observe
    the engine engaged (no silent single-thread fallback)."""
    import jax

    from dag_rider_tpu.parallel.mesh import make_mesh
    from dag_rider_tpu.parallel.sharded_verifier import ShardedTPUVerifier

    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    reg, _ = keys
    cpu = CPUVerifier(reg)
    rng = random.Random(5000 + depth)
    pool = _signed_pool(keys, 96, seed=800 + depth)
    rounds = _random_rounds(pool, rng)
    want = [cpu.verify_batch(r) for r in rounds]
    assert any(not all(m) for m in want if m), "no corruption landed"

    serial = ShardedTPUVerifier(reg, make_mesh(8))
    serial.fixed_bucket = 64
    serial.prep_workers = 1
    assert serial.verify_rounds(rounds) == want

    pipe = VerifierPipeline(
        ShardedTPUVerifier(reg, make_mesh(8)),
        depth=depth,
        fixed_bucket=64,
        warmup=False,
    )
    pipe.verifier.prep_workers = 4
    assert pipe.verify_rounds(rounds) == want
    s = pipe.stats()
    assert s.get("mesh_devices") == 8, "fell back to single-chip dispatch"
    assert s["prep_workers"] == 4
    assert s["prep_parallel_fraction"] > 0.0, "prep never ran parallel"


def test_prep_engine_active_through_async_seam(keys):
    """Acceptance (structural): a multi-chunk burst through the pipeline
    at workers=4 must show the engine genuinely engaged — parallel
    row-block dispatches AND prep-ahead on the seam thread — not a
    silent serial fallback."""
    reg, _ = keys
    cpu = CPUVerifier(reg)
    pool = _signed_pool(keys, 160, seed=42)
    want = cpu.verify_batch(pool)

    pipe = VerifierPipeline(
        TPUVerifier(reg), depth=2, fixed_bucket=64, warmup=False
    )
    pipe.verifier.prep_workers = 4
    assert pipe.verify_batch(pool) == want
    eng = pipe.verifier._prep()
    assert eng.workers == 4
    assert eng.dispatches_parallel > 0, "row-block pool never engaged"
    assert eng._seam is not None, "prep-ahead seam thread never engaged"
    s = pipe.stats()
    assert s["prep_workers"] == 4
    assert s["prep_parallel_fraction"] > 0.0


def test_bare_verifier_chunks_over_cap_without_a_window(keys):
    """A TPUVerifier with no pipeline over it takes an over-cap merge
    chunk by chunk: same mask, row-block prep still parallel, and no
    prep-ahead thread — the window is VerifierPipeline's alone."""
    reg, _ = keys
    cpu = CPUVerifier(reg)
    pool = _signed_pool(keys, 160, seed=43)
    want = cpu.verify_batch(pool)
    v = TPUVerifier(reg)
    v.fixed_bucket = 64
    v.prep_workers = 4
    assert v.verify_rounds([pool]) == [want]
    assert v.total_dispatches == 3  # ceil(160 / 64)
    assert v._prep().dispatches_parallel > 0
    assert v._prep()._seam is None, "a bare verifier opened a window"


class _RingWatchVerifier(TPUVerifier):
    """Snapshots every dispatched staging slot and asserts at resolve
    time that the live slot still holds the dispatched bytes — i.e. no
    later prep rewrote it while the dispatch could still be executing
    (the CPU PJRT client may alias host arrays zero-copy)."""

    def __init__(self, reg):
        super().__init__(reg)
        self.snaps = collections.deque()
        self.checked = 0

    def dispatch_prepped(self, prepped):
        out = super().dispatch_prepped(prepped)
        arrs = [a for a in prepped.args if isinstance(a, np.ndarray)]
        assert arrs, "expected numpy staging arrays in the dispatch args"
        self.snaps.append((arrs, [a.copy() for a in arrs]))
        return out

    def resolve_batch(self, handle):
        arrs, copies = self.snaps.popleft()  # FIFO == ring claim order
        for live, snap in zip(arrs, copies):
            assert np.array_equal(live, snap), (
                "staging slot rewritten while its dispatch was in flight"
            )
        self.checked += 1
        return super().resolve_batch(handle)


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_staging_ring_slot_not_rewritten_while_inflight(keys, depth):
    """Aliasing discipline under prep-ahead: with 4 workers and many
    over-cap chunks in flight, every resolved dispatch must still see
    the exact bytes it shipped."""
    reg, _ = keys
    cpu = CPUVerifier(reg)
    pool = _signed_pool(keys, 320, seed=9 * depth)
    want = cpu.verify_batch(pool)
    v = _RingWatchVerifier(reg)
    v.fixed_bucket = 64
    v.prep_workers = 4
    pipe = VerifierPipeline(v, depth=depth, warmup=False)
    assert pipe.verify_batch(pool) == want
    assert v.checked == 5  # ceil(320 / 64)
    assert not v.snaps
