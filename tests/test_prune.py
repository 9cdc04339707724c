"""DAG garbage collection / memory bounding (round-4 VERDICT missing #4).

The reference keeps its DAG forever (``process/process.go:72-85``); so did
rounds 1-3 here. With ``cfg.gc_depth`` set, the ordering rule excludes
vertices below ``leader_round - gc_depth`` deterministically at every
process, which makes retiring that state (DagState.prune_below) safe: the
total order cannot diverge on vertices nobody may deliver.
"""

from __future__ import annotations

import numpy as np
import pytest

from dag_rider_tpu import Config
from dag_rider_tpu.consensus import Process, Simulation
from dag_rider_tpu.core.types import Block, BroadcastMessage, Vertex, VertexID
from dag_rider_tpu.transport import InMemoryTransport
from dag_rider_tpu.utils import checkpoint

GC = Config(
    n=4,
    coin="round_robin",
    propose_empty=True,
    gc_depth=16,
    sync_window=8,
)


def _run_rounds(sim: Simulation, target_round: int) -> None:
    # small chunks: tests below stage scenarios at specific rounds, so a
    # call must not overshoot the target by thousands of rounds
    for _ in range(20 * target_round):
        sim.run(max_messages=100)
        if max(p.round for p in sim.processes) >= target_round:
            return
    raise AssertionError("simulation failed to reach target round")


def test_gc_depth_config_validation():
    with pytest.raises(ValueError):
        Config(n=4, gc_depth=4)  # below sync_window + 2*wave_length
    Config(n=4, gc_depth=16, sync_window=8)  # ok


def test_long_run_memory_bounded_and_agreement_holds():
    sim = Simulation(GC)
    sim.submit_blocks(per_process=2)
    _run_rounds(sim, 120)
    sim.check_agreement()
    for p in sim.processes:
        # pruning actually happened and tracked the frontier
        assert p.dag.base_round > 0
        assert p.dag.base_round >= p.round - 3 * GC.gc_depth
        # dense capacity is REUSED, not doubled forever: the initial
        # allocation (max_rounds=64 rows) never needs to grow when the
        # live window is ~gc_depth rounds
        assert p.dag._capacity <= 64
        # the vertex map holds only the live window
        window = p.dag.max_round - p.dag.base_round + 1
        assert len(p.dag.vertices) <= GC.n * (window + 1)
        assert window <= GC.gc_depth + 3 * GC.wave_length
        # book-keeping is windowed too
        assert len(p.delivered_log) <= GC.n * (window + GC.gc_depth + 8)
        assert p.delivered_trimmed > 0
    # cumulative delivery kept going far past the window: the protocol
    # ran unbounded history over bounded state
    total = sum(len(d) for d in sim.deliveries)
    assert total > 4 * GC.n * GC.gc_depth


def test_unpruned_and_pruned_total_order_agree():
    """GC exclusion is part of the ordering rule, not a local heuristic —
    but with every process configured identically, the delivered order
    must equal the unpruned run's order *for the delivered prefix above
    the horizon*. Deliveries happen in lockstep here, so the GC run's
    sink is a subsequence-free exact match of the unpruned sink except
    for vertices the rule excludes (none, in a lockstep run with no
    stragglers)."""
    cfg_plain = Config(n=4, coin="round_robin", propose_empty=True)
    sims = []
    for cfg in (cfg_plain, GC):
        sim = Simulation(cfg)
        sim.submit_blocks(per_process=2)
        _run_rounds(sim, 60)
        sims.append(sim)
    a = [(v.id.round, v.id.source, v.digest()) for v in sims[0].deliveries[0]]
    b = [(v.id.round, v.id.source, v.digest()) for v in sims[1].deliveries[0]]
    k = min(len(a), len(b))
    assert k > 150  # several waves' worth of common prefix
    assert a[:k] == b[:k]


def test_pruned_node_serves_sync_within_window_refuses_below():
    sim = Simulation(GC)
    sim.submit_blocks(per_process=2)
    _run_rounds(sim, 80)
    p = sim.processes[0]
    base = p.dag.base_round
    assert base > 1
    outbox = []
    # nacks broadcast; window serves unicast to the requester (round 11)
    p.transport.broadcast = lambda msg: outbox.append(msg)
    p.transport.enqueue = lambda dest, msg: outbox.append(msg)

    # request below the horizon -> clean refusal: no vertices served,
    # just the sync_nack that steers the requester to state transfer
    p._sync_last_serve.clear()
    p._serve_sync(
        BroadcastMessage(
            vertex=None, round=base - 1, sender=1, kind="sync", origin=base
        )
    )
    assert [m.kind for m in outbox] == ["sync_nack"]
    assert outbox[0].round == base and outbox[0].origin == 1
    assert p.metrics.counters.get("sync_refused_pruned", 0) == 1
    outbox.clear()

    # request within the live window -> served from the original vertices
    lo = base + 1
    p._sync_last_serve.clear()
    p._serve_sync(
        BroadcastMessage(
            vertex=None, round=lo, sender=1, kind="sync", origin=lo + 2
        )
    )
    assert outbox, "live-window sync must serve vertices"
    assert all(m.vertex.id.round >= lo for m in outbox)


def test_checkpoint_roundtrip_preserves_gc_window(tmp_path):
    sim = Simulation(GC)
    sim.submit_blocks(per_process=2)
    _run_rounds(sim, 80)
    p = sim.processes[0]
    assert p.dag.base_round > 0
    checkpoint.save(p, str(tmp_path))

    fresh = Process(GC, 0, InMemoryTransport())
    checkpoint.restore(fresh, str(tmp_path))
    assert fresh.dag.base_round == p.dag.base_round
    assert fresh.delivered_trimmed == p.delivered_trimmed
    assert fresh.delivered_log == p.delivered_log
    assert sorted(fresh.dag.vertices) == sorted(p.dag.vertices)
    # dense mirrors landed in the right (shifted) rows
    np.testing.assert_array_equal(
        fresh.dag.exists[: fresh.dag.max_round + 1 - fresh.dag.base_round],
        p.dag.exists[: p.dag.max_round + 1 - p.dag.base_round],
    )
    # and the restored machine still runs
    fresh._started = True
    fresh.step()


def test_below_horizon_vertex_is_dropped_not_wedged():
    """A straggler broadcast from under the GC floor must be discarded
    (it can never be delivered anywhere), not buffered forever."""
    sim = Simulation(GC)
    sim.submit_blocks(per_process=2)
    _run_rounds(sim, 80)
    p = sim.processes[0]
    base = p.dag.base_round
    ghost = Vertex(
        id=VertexID(max(1, base - 4), 1),
        block=Block((b"ghost",)),
        strong_edges=tuple(
            VertexID(max(0, base - 5), s) for s in range(GC.quorum)
        ),
    )
    p.on_message(
        BroadcastMessage(vertex=ghost, round=ghost.round, sender=1)
    )
    p.step()
    assert ghost.id not in {v.id for v in p.buffer}
    assert not p.dag.present(ghost.id)


def test_blocked_memo_reevaluated_after_prune_passes_weak_target():
    """A vertex blocked on a weak target that later falls under the GC
    floor must be re-evaluated and admitted (the below-base weak rule),
    not held forever by the stale blocked-on memo (round-4 review).
    Driven directly (a full sim's retroactive chains jump the floor
    several waves per commit, racing the observation window). Pinned to
    the scalar pump: the ``_blocked_on`` memo it asserts is a scalar
    drain internal (the vector drain re-checks batches wholesale)."""
    p = Process(
        Config(
            n=4,
            coin="round_robin",
            propose_empty=True,
            gc_depth=16,
            sync_window=8,
            pump="scalar",
        ),
        0,
        InMemoryTransport(),
    )
    # full rounds 1..8 from sources 0..2; source 3 is permanently absent
    for r in range(1, 9):
        prev = tuple(
            VertexID(r - 1, s)
            for s in (range(GC.n) if r == 1 else range(3))
        )[: max(GC.quorum, 3)]
        for s in range(3):
            p.dag.insert(Vertex(id=VertexID(r, s), strong_edges=prev))
    p.round = 8
    v = Vertex(
        id=VertexID(8, 3),
        block=Block((b"straggler",)),
        strong_edges=tuple(VertexID(7, s) for s in range(3)),
        weak_edges=(VertexID(2, 3),),  # absent forever
    )
    p.on_message(BroadcastMessage(vertex=v, round=8, sender=3))
    p._started = True
    p.step()
    assert v.id in p._buffered_ids  # blocked: memo points at (2, 3)
    assert p._blocked_on[v.id] == VertexID(2, 3)

    # a wave decision whose GC floor passes the weak target: floor =
    # r1(decided) - gc_depth = 21 - 16 = 5 > 2
    p.decided_wave = 6
    removed = p.maybe_prune()
    assert removed > 0 and p.dag.base_round == 5

    p.step()  # memo must re-evaluate, below-base weak rule admits v
    assert p.dag.present(v.id)
    assert v.id not in p._buffered_ids


def test_restore_rejects_corrupt_delivered_log(tmp_path):
    import json
    import os

    sim = Simulation(GC)
    sim.submit_blocks(per_process=2)
    _run_rounds(sim, 40)
    p = sim.processes[0]
    checkpoint.save(p, str(tmp_path))
    mpath = os.path.join(str(tmp_path), "manifest.json")
    manifest = json.load(open(mpath))
    assert manifest["delivered_log"]
    for bad in ([5, -3], [10**9, 1], [manifest["base_round"] - 1, 0]):
        manifest["delivered_log"][-1] = bad
        json.dump(manifest, open(mpath, "w"))
        fresh = Process(GC, 0, InMemoryTransport())
        with pytest.raises(ValueError, match="corrupt checkpoint"):
            checkpoint.restore(fresh, str(tmp_path))


def test_rbc_vote_books_pruned_with_dag():
    """The Bracha stage's per-slot state must follow the Process's GC
    floor (round-4: without this, RBC nodes leaked exactly the state
    class DagState.prune_below bounds), and frames for retired slots
    must be dropped, not re-admitted into fresh books."""
    sim = Simulation(GC, rbc=True)
    sim.submit_blocks(per_process=2)
    _run_rounds(sim, 90)
    sim.check_agreement()
    p = sim.processes[0]
    rbc = p.transport
    assert p.dag.base_round > 0
    assert rbc.floor == p.dag.base_round
    # every slot map is windowed to the live rounds
    for d in (rbc._val, rbc._decided):
        assert all(k[0] >= rbc.floor for k in d)
    for s in (rbc._echoed, rbc._readied, rbc._delivered):
        assert all(k[0] >= rbc.floor for k in s)
    for book in (rbc._echoes, rbc._readies):
        assert all(k[0][0] >= rbc.floor for k in book)
    live_slots = len(rbc._delivered)
    assert live_slots <= GC.n * (GC.gc_depth + 4 * GC.wave_length)

    # a replayed VAL for a retired slot is dropped silently
    old = BroadcastMessage(
        vertex=Vertex(
            id=VertexID(1, 1),
            strong_edges=tuple(VertexID(0, s) for s in range(GC.quorum)),
        ),
        round=1,
        sender=1,
    )
    before = len(rbc._val)
    rbc._on_inner(old)
    assert len(rbc._val) == before
    assert (1, 1) not in rbc._echoed


def test_rbc_floor_follows_restores():
    """checkpoint restore and snapshot transfer must re-establish the
    RBC slot floor, or replayed frames for retired rounds regrow the
    vote books until the next wave decision (round-4 review)."""
    from dag_rider_tpu.transport.rbc import RbcTransport

    sim = Simulation(GC, rbc=True)
    sim.submit_blocks(per_process=2)
    _run_rounds(sim, 80)
    donor = sim.processes[0]
    assert donor.dag.base_round > 0
    blob = checkpoint.snapshot_bytes(donor)

    broker = InMemoryTransport()
    rbc = RbcTransport(broker, 0, GC.n, GC.f)
    fresh = Process(GC, 0, rbc)
    assert checkpoint.restore_from_snapshot(fresh, blob)
    assert rbc.floor == fresh.dag.base_round > 0


def test_rbc_floor_follows_checkpoint_restore(tmp_path):
    from dag_rider_tpu.transport.rbc import RbcTransport

    sim = Simulation(GC, rbc=True)
    sim.submit_blocks(per_process=2)
    _run_rounds(sim, 80)
    donor = sim.processes[0]
    checkpoint.save(donor, str(tmp_path))

    broker = InMemoryTransport()
    rbc = RbcTransport(broker, 0, GC.n, GC.f)
    fresh = Process(GC, 0, rbc)
    checkpoint.restore(fresh, str(tmp_path))
    assert rbc.floor == fresh.dag.base_round > 0


def test_threshold_coin_books_pruned_with_dag():
    """The coin's per-wave share/sigma books follow the GC floor — the
    last unbounded-state holdout after DAG + RBC pruning."""
    from dag_rider_tpu.consensus.coin import ThresholdCoin
    from dag_rider_tpu.crypto import threshold as th

    n, f = 4, 1
    keys = th.ThresholdKeys.generate(n, f + 1)
    oracle = ThresholdCoin(keys, 0, n)

    def coin_factory(i):
        c = ThresholdCoin(keys, i, n)
        c._shares = oracle._shares
        c._sigma = oracle._sigma
        c._tried_at = oracle._tried_at
        return c

    cfg = Config(
        n=n, coin="threshold_bls", propose_empty=True, gc_depth=16
    )
    sim = Simulation(cfg, coin_factory=coin_factory)
    sim.submit_blocks(per_process=2)
    _run_rounds(sim, 60)
    sim.check_agreement()
    p = sim.processes[0]
    assert p.dag.base_round > 4
    floor_wave = cfg.wave_of_round(p.dag.base_round)
    assert oracle._sigma, "coin actually decided waves"
    assert all(w >= floor_wave for w in oracle._shares)
    assert all(w >= floor_wave for w in oracle._sigma)


def test_retro_chain_survives_pruned_coin_after_state_transfer():
    """After a state transfer, decided_wave resets to 0 while the coin
    books below the imported floor are pruned — the first wave commit's
    retroactive walk must skip those unknowable links (their deliveries
    are floor-excluded here) instead of raising 'coin not ready'
    (round-4 review)."""
    from dag_rider_tpu.consensus.coin import ThresholdCoin
    from dag_rider_tpu.crypto import threshold as th

    n = 4
    keys = th.ThresholdKeys.generate(n, 2)
    oracle = ThresholdCoin(keys, 0, n)

    def cf(i):
        c = ThresholdCoin(keys, i, n)
        c._shares = oracle._shares
        c._sigma = oracle._sigma
        c._tried_at = oracle._tried_at
        return c

    cfg = Config(n=n, coin="threshold_bls", propose_empty=True, gc_depth=16)
    sim = Simulation(cfg, coin_factory=cf)
    sim.submit_blocks(per_process=2)
    _run_rounds(sim, 60)
    donor = sim.processes[0]
    assert donor.dag.base_round > 4
    blob = checkpoint.snapshot_bytes(donor)

    fresh = Process(cfg, 0, InMemoryTransport(), coin=cf(0))
    assert checkpoint.restore_from_snapshot(fresh, blob)
    assert fresh.decided_wave == 0
    # waves below the imported floor have no books anymore
    floor_wave = cfg.wave_of_round(fresh.dag.base_round)
    assert all(w >= floor_wave for w in fresh.coin._shares)
    # the next wave commit walks the retro chain back to decided_wave=0
    # straight through the pruned-coin waves — it must skip them, not
    # raise "coin for wave w not ready"
    committed = False
    for w in range(cfg.wave_of_round(fresh.dag.max_round), 0, -1):
        if cfg.wave_round(w, cfg.wave_length) > fresh.dag.max_round:
            continue  # wave not fully inside the imported window
        if cfg.wave_round(w, 1) <= fresh.dag.base_round:
            break  # below the floor: nothing left to try
        fresh._try_wave(w)
        if fresh.decided_wave == w:
            committed = True
            break
    assert committed, "restored node could not commit any window wave"
    assert not any(
        cfg.wave_round(w, 1) <= fresh.dag.base_round
        for w in fresh._pending_waves
    )


@pytest.mark.parametrize("seed", [2, 19, 101, 977])
def test_gc_agreement_under_random_interleavings(seed):
    """Adversarial delivery order WITH pruning active: the interaction
    zone of stragglers, retro chains, floor exclusion and the blocked
    memo. Any interleaving must preserve total-order agreement and the
    bounded window — exactly where a GC determinism bug would surface."""
    from dag_rider_tpu.consensus import RandomizedScheduler

    sim = Simulation(GC)
    sim.submit_blocks(per_process=2)
    for p in sim.processes:
        p.start()
    sched = RandomizedScheduler(sim.transport, seed)
    for _ in range(400):
        if not sched.run(max_messages=200):
            break
        for p in sim.processes:
            p.step()
    sim.check_agreement()
    assert any(p.dag.base_round > 0 for p in sim.processes), "never pruned"
    for p in sim.processes:
        window = p.dag.max_round - p.dag.base_round + 1
        assert len(p.dag.vertices) <= GC.n * (window + 1)
