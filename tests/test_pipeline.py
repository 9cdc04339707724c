"""Depth-K verifier pipeline equivalence (round-6 tentpole).

The pipeline changes WHEN the host blocks, never WHAT the device
computes: masks from the depth-K window (verifier/pipeline.py), the
bare verifier's chunk-by-chunk ``verify_rounds``, and the CPU oracle
must be byte-identical across randomized burst shapes, window depths
(K in {1, 2, 4}), and ``fixed_bucket`` settings — including empty rounds
and merges larger than the bucket (the over-cap chunking edge). The
commit order downstream of those masks is checked end-to-end through the
simulator at every depth.
"""

import dataclasses
import random

import pytest

from dag_rider_tpu.core.types import Block, Vertex, VertexID
from dag_rider_tpu.verifier.base import KeyRegistry, VertexSigner
from dag_rider_tpu.verifier.cpu import CPUVerifier
from dag_rider_tpu.verifier.pipeline import VerifierPipeline
from dag_rider_tpu.verifier.tpu import TPUVerifier

N = 8


@pytest.fixture(scope="module")
def keys():
    return KeyRegistry.generate(N)


def _signed_pool(keys, count, seed):
    """``count`` signed vertices over randomized rounds/sources/edges,
    with a deterministic sprinkle of corruptions (zeroed signature,
    foreign signer) the mask must reject."""
    reg, seeds = keys
    signers = [VertexSigner(s) for s in seeds]
    rng = random.Random(seed)
    out = []
    for j in range(count):
        src = rng.randrange(N)
        r = rng.randrange(1, 6)
        v = Vertex(
            id=VertexID(r, src),
            block=Block((f"s{seed}j{j}".encode(),)),
            strong_edges=tuple(
                VertexID(r - 1, s) for s in range(rng.randrange(0, N))
            ),
        )
        v = signers[src].sign_vertex(v)
        roll = rng.random()
        if roll < 0.15:
            v = dataclasses.replace(v, signature=bytes(64))
        elif roll < 0.25:
            v = dataclasses.replace(
                v,
                signature=signers[(src + 1) % N].sign_vertex(v).signature,
            )
        out.append(v)
    return out


def _random_rounds(pool, rng):
    """Randomized burst shapes over the pool, with explicit empty rounds
    sprinkled in."""
    rounds, i = [], 0
    while i < len(pool):
        if rng.random() < 0.2:
            rounds.append([])
        k = rng.randint(1, 17)
        rounds.append(pool[i : i + k])
        i += k
    rounds.append([])
    return rounds


@pytest.mark.parametrize("depth", [1, 2, 4])
@pytest.mark.parametrize("bucket", [None, 16, 32])
def test_pipeline_masks_byte_identical(keys, depth, bucket):
    """Property: depth-K pipeline == the bare verifier's chunked
    verify_rounds == CPU oracle, for every (depth, bucket) combination. A 48-vertex pool
    against bucket 16/32 forces over-cap chunking; bucket None exercises
    the power-of-two ladder."""
    reg, _ = keys
    cpu = CPUVerifier(reg)
    rng = random.Random(1000 * depth + (bucket or 7))
    pool = _signed_pool(keys, 48, seed=100 * depth + (bucket or 7))
    rounds = _random_rounds(pool, rng)
    want = [cpu.verify_batch(r) for r in rounds]
    assert any(not all(m) for m in want if m), "no corruption landed"

    bare = TPUVerifier(reg)
    bare.fixed_bucket = bucket
    assert bare.verify_rounds(rounds) == want

    pipe = VerifierPipeline(
        TPUVerifier(reg), depth=depth, fixed_bucket=bucket, warmup=False
    )
    assert pipe.verify_rounds(rounds) == want
    flat = [v for r in rounds for v in r]
    assert pipe.verify_batch(flat) == [m for ms in want for m in ms]
    assert pipe.verify_batch([]) == []


@pytest.mark.parametrize("depth", [1, 2, 4])
@pytest.mark.parametrize("bucket", [None, 16])
def test_sharded_pipeline_masks_byte_identical(keys, depth, bucket):
    """Round-7 tentpole: the MESH-sharded verifier through the depth-K
    window must produce the same bytes as the CPU oracle and the
    single-chip path at every depth — chunk boundaries are set
    by the caller's bucket exactly as on one chip; only the padded
    dispatch size rounds up to the mesh multiple (invisible after the
    ``[:count]`` slice)."""
    import jax

    from dag_rider_tpu.parallel.mesh import make_mesh
    from dag_rider_tpu.parallel.sharded_verifier import ShardedTPUVerifier

    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    reg, _ = keys
    cpu = CPUVerifier(reg)
    rng = random.Random(7000 * depth + (bucket or 3))
    pool = _signed_pool(keys, 48, seed=700 * depth + (bucket or 3))
    rounds = _random_rounds(pool, rng)
    want = [cpu.verify_batch(r) for r in rounds]
    assert any(not all(m) for m in want if m), "no corruption landed"

    single = TPUVerifier(reg)
    single.fixed_bucket = bucket
    assert single.verify_rounds(rounds) == want

    sharded = ShardedTPUVerifier(reg, make_mesh(8))
    sharded.fixed_bucket = bucket
    assert sharded.verify_rounds(rounds) == want

    pipe = VerifierPipeline(
        ShardedTPUVerifier(reg, make_mesh(8)),
        depth=depth,
        fixed_bucket=bucket,
        warmup=False,
    )
    assert pipe.verify_rounds(rounds) == want
    flat = [v for r in rounds for v in r]
    assert pipe.verify_batch(flat) == [m for ms in want for m in ms]
    assert pipe.verify_batch([]) == []
    # the window really ran on the mesh, not a single-chip fallback
    assert pipe.stats().get("mesh_devices") == 8


def test_aot_warmup_is_mask_invariant(keys):
    """warmup()'s jit().lower().compile() executable must be a pure
    speed move: identical masks before/after, idempotent, accounted."""
    reg, _ = keys
    pool = _signed_pool(keys, 20, seed=7)
    cold = TPUVerifier(reg)
    cold.fixed_bucket = 16
    before = cold.verify_batch(pool)

    warm = TPUVerifier(reg)
    warm.fixed_bucket = 16
    dt = warm.warmup()
    assert dt >= 0.0 and warm._aot, "warmup compiled nothing"
    assert warm.verify_batch(pool) == before
    assert warm.warmup() == 0.0  # second call: shape already compiled
    assert warm.warmup_compile_s == dt


def test_window_gauges_and_serial_degeneration(keys):
    """The depth-4 window keeps chunks genuinely in flight (high-water
    >= 2), its gauges stay sane, and a depth-1 window degenerates to the
    serial dispatch-then-resolve shape with the same mask."""
    reg, _ = keys
    pool = _signed_pool(keys, 40, seed=3)
    pipe = VerifierPipeline(
        TPUVerifier(reg), depth=4, fixed_bucket=16, warmup=False
    )
    mask = pipe.verify_batch(pool)
    assert pipe.dispatches == 3  # ceil(40 / 16)
    assert pipe.sigs_dispatched == 40
    assert pipe.depth_hwm >= 2, "chunks never overlapped in flight"
    s = pipe.stats()
    assert 0.0 <= s["overlap_fraction"] <= 1.0
    assert s["seam_s"] >= s["wait_s"] >= 0.0

    serial = VerifierPipeline(
        TPUVerifier(reg), depth=1, fixed_bucket=16, warmup=False
    )
    assert serial.verify_batch(pool) == mask
    assert serial.depth_hwm == 1


def test_sim_commit_order_matches_cpu_at_every_depth(keys):
    """Acceptance: CPU-vs-device commit order stays byte-identical with
    the pipeline enabled at every tested depth, with per-cycle bursts
    larger than the fixed bucket so the depth-K window genuinely engages
    (n*(n-1) = 56 unique entries vs bucket 16 = 4 chunks in flight)."""
    from dag_rider_tpu.config import Config
    from dag_rider_tpu.consensus.simulator import Simulation

    reg, seeds = keys
    signers = [VertexSigner(s) for s in seeds]

    def run(factory, dedup=True, window=None):
        cfg = Config(n=N, coin="round_robin", propose_empty=True)
        sim = Simulation(
            cfg,
            verifier_factory=factory,
            signer_factory=lambda i: signers[i],
        )
        sim.dedup = dedup
        # the window run() would build over the shared verifier, at the
        # depth under test
        sim._verify_pipe = window
        sim.submit_blocks(per_process=2)
        for _ in range(10):
            sim.run(max_messages=N * (N - 1))
        sim.check_agreement()
        log = [
            (v.id.round, v.id.source, v.digest())
            for v in sim.deliveries[0]
        ]
        return log, sim

    cpu_log, _ = run(lambda i: CPUVerifier(reg))
    assert len(cpu_log) > 10, "CPU reference run delivered too little"
    for depth in (1, 2, 4):
        shared = TPUVerifier(reg)
        window = VerifierPipeline(shared, depth=depth, fixed_bucket=16)
        # dedup off: the merged burst keeps all n*(n-1) copies, so a
        # cycle's dispatch genuinely exceeds the bucket and chunks
        # (deliveries are dedup-invariant — see the dedup tests)
        dev_log, sim = run(lambda i: shared, dedup=False, window=window)
        assert sim._verify_pipe is window and window.dispatches
        k = min(len(cpu_log), len(dev_log))
        assert k > 10 and cpu_log[:k] == dev_log[:k], f"depth {depth}"
        depths = [
            d
            for p in sim.processes
            for d in p.metrics.verify_queue_depth
        ]
        assert depths, "queue-depth gauge never observed"
        if depth > 1:
            assert max(depths) >= 2, "window never engaged"
        snap = sim.processes[0].metrics.snapshot()
        assert "verify_overlap_fraction" in snap
        assert "verify_queue_depth_p50" in snap


@pytest.mark.parametrize("depth", [2, 4])
def test_hold_tail_masks_fifo_across_calls(keys, depth):
    """ISSUE 16 tentpole 4: ``run_coalesced(..., hold_tail=True)`` may
    keep up to depth-1 chunks in flight ACROSS the call boundary — the
    cross-round verify window. Held results must emerge at the FRONT of
    a later call's mask (FIFO), ``drain()`` settles the remainder, and
    the concatenated stream is byte-identical to the CPU oracle."""
    reg, _ = keys
    cpu = CPUVerifier(reg)
    rng = random.Random(900 + depth)
    pool = _signed_pool(keys, 72, seed=900 + depth)
    want = cpu.verify_batch(pool)
    assert not all(want), "no corruption landed"

    pipe = VerifierPipeline(
        TPUVerifier(reg), depth=depth, fixed_bucket=8, warmup=False
    )
    got, held_once, i = [], False, 0
    while i < len(pool):
        k = rng.randint(1, 24)
        burst = pool[i : i + k]
        i += k
        mask = pipe.run_coalesced(burst, hold_tail=True)
        # held chunks can flush ahead of this burst's own results, but
        # never more than the window could have been holding
        assert len(mask) <= len(burst) + (depth - 1) * 8
        if len(mask) < len(burst):
            held_once = True
        got.extend(mask)
    got.extend(pipe.drain())
    assert held_once, "the window never held a tail across a call"
    assert got == want
    # a drained pipeline owes nothing more
    assert pipe.drain() == []


def test_hold_tail_depth_one_never_holds(keys):
    """depth=1 degenerates hold_tail to the synchronous path: every
    call settles its own burst in full."""
    reg, _ = keys
    pool = _signed_pool(keys, 24, seed=11)
    cpu = CPUVerifier(reg)
    pipe = VerifierPipeline(
        TPUVerifier(reg), depth=1, fixed_bucket=8, warmup=False
    )
    got = []
    for i in range(0, len(pool), 7):
        burst = pool[i : i + 7]
        mask = pipe.run_coalesced(burst, hold_tail=True)
        assert len(mask) == len(burst)
        got.extend(mask)
    assert got == cpu.verify_batch(pool)
    assert pipe.drain() == []
