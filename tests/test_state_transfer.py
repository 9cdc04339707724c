"""Peer state transfer (snapshot sync) — elastic recovery past the GC
horizon.

With cfg.gc_depth set, peers refuse anti-entropy sync for pruned windows
(test_prune.py); a node that was down long enough can therefore never
catch up message-by-message. The recovery path: f+1 sync_nack floors
above our round flip ``state_transfer_needed``; the node runtime fetches
an UNTRUSTED peer's live window and replays it locally
(utils.checkpoint.restore_from_snapshot — signatures verified, admission
gate re-run, consensus state recomputed, lying floors rejected by the
window-width check).
"""

from __future__ import annotations

import dataclasses
import json
import struct
import time


from dag_rider_tpu import Config
from dag_rider_tpu.consensus import Process, Simulation
from dag_rider_tpu.core.types import Block, BroadcastMessage, VertexID
from dag_rider_tpu.transport import InMemoryTransport
from dag_rider_tpu.utils import checkpoint

GC = Config(n=4, coin="round_robin", propose_empty=True, gc_depth=16)


def _pruned_donor(target_round: int = 70) -> Simulation:
    sim = Simulation(GC)
    sim.submit_blocks(per_process=2)
    for _ in range(20 * target_round):
        sim.run(max_messages=100)
        if max(p.round for p in sim.processes) >= target_round:
            break
    assert sim.processes[0].dag.base_round > 0
    return sim


def test_snapshot_roundtrip_replays_window():
    sim = _pruned_donor()
    donor = sim.processes[0]
    blob = checkpoint.snapshot_bytes(donor)

    fresh = Process(GC, 0, InMemoryTransport())
    assert checkpoint.restore_from_snapshot(fresh, blob)
    assert fresh.dag.base_round == donor.dag.base_round
    assert fresh.dag.max_round == donor.dag.max_round
    assert fresh.round == donor.dag.max_round
    assert sorted(fresh.dag.vertices) == sorted(donor.dag.vertices)
    assert fresh.metrics.counters["state_transfers"] == 1
    # the replayed machine keeps running: feed it nothing and step —
    # no exception, and a wave decision becomes possible as traffic flows
    fresh._started = True
    fresh.step()


def test_snapshot_rejects_lying_floor():
    sim = _pruned_donor()
    donor = sim.processes[0]
    blob = checkpoint.snapshot_bytes(donor)
    (hlen,) = struct.unpack_from("<I", blob, 0)
    head = json.loads(blob[4 : 4 + hlen])
    # Byzantine donor claims a floor that leaves < gc_depth of window:
    # vertices below it are omitted-by-claim, shrinking usable history
    head["base_round"] = donor.dag.max_round - GC.gc_depth + 2
    forged_head = json.dumps(head).encode()
    forged = struct.pack("<I", len(forged_head)) + forged_head + blob[4 + hlen :]
    fresh = Process(GC, 0, InMemoryTransport())
    assert not checkpoint.restore_from_snapshot(fresh, forged)
    # untouched: still the genesis-only fresh process
    assert fresh.dag.base_round == 0 and fresh.dag.max_round == 0
    assert fresh.round == 0


def test_snapshot_rejects_wrong_committee_and_garbage():
    sim = _pruned_donor()
    blob = checkpoint.snapshot_bytes(sim.processes[0])
    other = Process(Config(n=7, gc_depth=16), 0, InMemoryTransport())
    assert not checkpoint.restore_from_snapshot(other, blob)
    fresh = Process(GC, 0, InMemoryTransport())
    assert not checkpoint.restore_from_snapshot(fresh, b"\x00\x01garbage")


def test_snapshot_drops_forged_vertex_signature():
    """A tampered vertex in the snapshot is dropped by signature
    verification while the rest of the window replays."""
    from dag_rider_tpu.verifier.base import KeyRegistry, VertexSigner
    from dag_rider_tpu.verifier.cpu import CPUVerifier

    n = 4
    reg, seeds = KeyRegistry.generate(n)
    signers = [VertexSigner(s) for s in seeds]
    cfg = Config(n=n, coin="round_robin", propose_empty=True, gc_depth=16)
    sim = Simulation(
        cfg,
        signer_factory=lambda i: signers[i],
        verifier_factory=lambda i: CPUVerifier(reg),
    )
    sim.submit_blocks(per_process=2)
    for _ in range(600):
        sim.run(max_messages=100)
        if max(p.round for p in sim.processes) >= 40:
            break
    donor = sim.processes[0]
    assert donor.dag.base_round > 0
    # tamper a frontier vertex (no dependents -> window stays intact)
    top = donor.dag.max_round
    victim = donor.dag.vertices_in_round(top)[0]
    forged = dataclasses.replace(victim, signature=b"\x99" * 64)
    del donor.dag.vertices[victim.id]
    donor.dag.vertices[forged.id] = forged

    blob = checkpoint.snapshot_bytes(donor)
    fresh = Process(cfg, 0, InMemoryTransport())
    assert checkpoint.restore_from_snapshot(
        fresh, blob, verifier=CPUVerifier(reg)
    )
    assert not fresh.dag.present(victim.id)  # forged copy filtered out
    # the rest of the window replayed (frontier may shrink by the one
    # dropped vertex when it was alone in its round)
    assert fresh.dag.max_round >= top - 1
    assert len(fresh.dag.vertices) >= len(donor.dag.vertices) - 2


def test_sync_nack_flow_flips_state_transfer_flag():
    sim = _pruned_donor()
    donor = sim.processes[0]
    base = donor.dag.base_round

    requester = Process(GC, 3, InMemoryTransport())
    requester.round = 1  # far below the cluster
    # donor refuses a below-horizon window and nacks
    outbox = []
    donor.transport.broadcast = lambda m: outbox.append(m)
    donor._sync_last_serve.clear()
    donor._serve_sync(
        BroadcastMessage(
            vertex=None, round=1, sender=3, kind="sync", origin=4
        )
    )
    nacks = [m for m in outbox if m.kind == "sync_nack"]
    assert nacks and nacks[0].round == base and nacks[0].origin == 3

    # f+1 distinct responders (f=1 -> 2) flip the flag; one is not enough
    requester._on_sync_nack(
        dataclasses.replace(nacks[0], sender=donor.index)
    )
    assert not requester.state_transfer_needed
    requester._on_sync_nack(dataclasses.replace(nacks[0], sender=1))
    assert requester.state_transfer_needed
    # a floor at/below our round clears that responder (stale signal)
    requester.round = base + 5
    requester._on_sync_nack(dataclasses.replace(nacks[0], sender=1))
    assert 1 not in requester._horizon_nacks


def test_node_rejoins_past_horizon_via_snapshot(tmp_path):
    """End to end over real gRPC: 3 of 4 nodes run far past the GC
    horizon; the 4th then joins fresh, gets refused+nacked on sync,
    fetches a snapshot, replays it, and delivers a suffix consistent
    with the cluster's order."""
    from dag_rider_tpu import node as node_mod

    keys_path = tmp_path / "keys.json"
    node_mod.main(
        ["keygen", "--n", "4", "--threshold", "2", "--out", str(keys_path)]
    )

    def mk(i):
        return node_mod.Node(
            {
                "index": i,
                "n": 4,
                "listen": "127.0.0.1:0",
                "peers": {},
                "keys": str(keys_path),
                "rbc": False,  # plain gRPC: nack/fetch path under test
                "verifier": "cpu",
                "coin": "round_robin",
                "propose_empty": True,
                "gc_depth": 16,
                "auth_master": "ef" * 32,
            }
        )

    nodes = [mk(i) for i in range(3)]
    addrs = {i: f"127.0.0.1:{nd.net.bound_port}" for i, nd in enumerate(nodes)}
    late = None
    try:
        for i, nd in enumerate(nodes):
            nd.net._peers.update({j: a for j, a in addrs.items() if j != i})
        for nd in nodes:
            nd.start()
        deadline = time.time() + 90
        while time.time() < deadline and (
            nodes[0].process.dag.base_round < 8
        ):
            time.sleep(0.1)
        assert nodes[0].process.dag.base_round >= 8, "cluster never pruned"

        late = mk(3)
        addrs[3] = f"127.0.0.1:{late.net.bound_port}"
        for i, nd in enumerate(nodes + [late]):
            nd.net._peers.update({j: a for j, a in addrs.items() if j != i})
        late.start()
        late.submit(Block((b"late-tx",)))
        deadline = time.time() + 90
        while time.time() < deadline and not late.process.metrics.counters.get(
            "state_transfers"
        ):
            time.sleep(0.1)
        assert late.process.metrics.counters.get("state_transfers") == 1
        base3 = late.process.dag.base_round
        assert base3 > 0

        # and it actually rejoins: deliveries flow after the transfer
        deadline = time.time() + 60
        while time.time() < deadline and len(late.delivered) < 8:
            time.sleep(0.1)
        assert len(late.delivered) >= 8, "no deliveries after transfer"
        # order consistency: the late node's log is the cluster's order
        # filtered to rounds above its snapshot floor — every entry
        # appears in node0's log in the same relative order
        log0 = [
            (v.id.round, v.id.source, v.digest())
            for v in nodes[0].delivered
        ]
        log3 = [
            (v.id.round, v.id.source, v.digest()) for v in late.delivered
        ]
        pos = {e: i for i, e in enumerate(log0)}
        got = [pos[e] for e in log3 if e in pos]
        # allow the freshest tail of log3 to be ahead of node0's sink
        assert len(got) >= len(log3) - 8
        assert got == sorted(got), "relative delivery order diverged"
    finally:
        for nd in nodes + ([late] if late is not None else []):
            nd.stop()


def test_snapshot_rejects_rewind_and_requires_gc():
    """Round-4 review hardening: (a) a valid-but-old window must not
    rewind a live node (duplicate a_deliver), (b) without gc_depth the
    import semantics are unsound and the function refuses, (c) a
    duplicate (round, source) pair — equivocation smuggled past the
    donor's RBC — refuses atomically instead of raising mid-commit."""
    sim = _pruned_donor()
    donor = sim.processes[0]
    blob = checkpoint.snapshot_bytes(donor)

    # (a) receiver already ahead of the claimed floor -> refuse untouched
    ahead = Process(GC, 0, InMemoryTransport())
    ahead.round = donor.dag.max_round + 5
    before = dict(ahead.dag.vertices)
    assert not checkpoint.restore_from_snapshot(ahead, blob)
    assert ahead.round == donor.dag.max_round + 5
    assert dict(ahead.dag.vertices) == before

    # (b) no gc_depth -> refuse
    plain = Process(
        Config(n=4, coin="round_robin", propose_empty=True),
        0,
        InMemoryTransport(),
    )
    assert not checkpoint.restore_from_snapshot(plain, blob)

    # (c) duplicate id in the payload -> atomic refusal, no exception
    from dag_rider_tpu.core import codec as _codec

    dup = donor.dag.vertices_in_round(donor.dag.max_round)[0]
    payload = _codec.encode_vertex(dup)
    forged = blob + struct.pack("<I", len(payload)) + payload
    fresh = Process(GC, 0, InMemoryTransport())
    assert not checkpoint.restore_from_snapshot(fresh, forged)
    assert fresh.dag.base_round == 0 and fresh.round == 0


def test_stale_nacks_do_not_count_after_catching_up():
    """A floor recorded while briefly behind must not combine with one
    later Byzantine nack into a fake f+1 quorum (round-4 review)."""
    p = Process(GC, 0, InMemoryTransport())
    p.round = 40
    p._on_sync_nack(
        BroadcastMessage(
            vertex=None, round=50, sender=1, kind="sync_nack", origin=0
        )
    )
    assert not p.state_transfer_needed  # 1 < f+1
    p.round = 100  # caught up via normal sync
    p._on_sync_nack(
        BroadcastMessage(
            vertex=None, round=10**9, sender=2, kind="sync_nack", origin=0
        )
    )
    # the stale floor-50 entry was purged; one live nack is not a quorum
    assert not p.state_transfer_needed
    assert list(p._horizon_nacks) == [2]


def _full_local_rounds(p: Process, hi: int, sources=(0, 1, 2)) -> None:
    """Rounds 1..hi from `sources` directly into the DAG (source 3 is the
    permanently-absent straggler whose history peers have pruned)."""
    from dag_rider_tpu.core.types import Vertex

    for r in range(1, hi + 1):
        prev = tuple(VertexID(r - 1, s) for s in sources)
        for s in sources:
            p.dag.insert(Vertex(id=VertexID(r, s), strong_edges=prev))
    p.round = hi


def test_attested_peer_floor_unwedges_blocked_buffer():
    """ADVICE r4: a node whose round is AHEAD of peers' floors but whose
    buffer is blocked on pruned straggler rounds must act on nacks whose
    floor exceeds the requested lo — not re-request unservable history
    forever. f+1 distinct floors above lo attest a pruned horizon, and
    the requester stops targeting blockers at/below it. Admission is
    deliberately untouched (round-5 review): f+1 floors prove ONE
    honest peer pruned, not that no honest peer can serve — blocked
    vertices stay buffered (bounded memory, zero traffic) in case a
    lower-floor peer serves their predecessors later; dropping them
    could forfeit that recovery and fork our delivered log."""
    from dag_rider_tpu.core.types import Vertex

    cfg = Config(
        n=4, coin="round_robin", propose_empty=True, sync_patience=1
    )  # gc_depth=None: the LOCAL floor never advances (the wedge case)
    p = Process(cfg, 0, InMemoryTransport())
    _full_local_rounds(p, 10)
    # three stragglers from source 3, all blocked:
    v_low = Vertex(  # inside the soon-attested horizon
        id=VertexID(6, 3),
        block=Block((b"low",)),
        strong_edges=(VertexID(5, 0), VertexID(5, 1), VertexID(5, 3)),
    )
    v_strong = Vertex(  # att+1, strong pred in attested history
        id=VertexID(9, 3),
        block=Block((b"strong",)),
        strong_edges=(VertexID(8, 0), VertexID(8, 1), VertexID(8, 3)),
    )
    v_weak = Vertex(  # above the horizon, missing weak target under it
        id=VertexID(10, 3),
        block=Block((b"weak",)),
        strong_edges=(VertexID(9, 0), VertexID(9, 1), VertexID(9, 2)),
        weak_edges=(VertexID(7, 3),),
    )
    for v in (v_low, v_strong, v_weak):
        p.on_message(BroadcastMessage(vertex=v, round=v.round, sender=3))
    p._started = True
    p.step()
    assert {v_low.id, v_strong.id, v_weak.id} <= {v.id for v in p.buffer}

    # stuck -> sync request fires at lo = min blocker round (5).
    # Requests are unicast (pull gossip, round 11): capture both seams,
    # and settle the receipt watermark — the backlog-aware patience gate
    # holds while receipts are still arriving, and the on_message calls
    # above count as receipts.
    outbox = []
    p.transport.broadcast = lambda m: outbox.append(m)
    p.transport.enqueue = lambda dest, m: outbox.append(m)
    p._rx_at_patience = p.metrics.counters.get("msgs_received", 0)
    p._maybe_request_sync()
    reqs = [m for m in outbox if m.kind == "sync"]
    assert reqs and reqs[0].round == 5
    assert p._sync_last_lo == 5

    # f+1 = 2 distinct responders nack with floor 8 (> lo, <= our round)
    for sender in (1, 2):
        p._on_sync_nack(
            BroadcastMessage(
                vertex=None, round=8, sender=sender, kind="sync_nack",
                origin=0,
            )
        )
    assert p._attested_floor == 8
    assert not p.state_transfer_needed  # floors <= our round: no rewind
    # admission untouched: everything stays buffered (a lower-floor
    # peer may yet serve the predecessors), nothing was admitted
    assert {v_low.id, v_strong.id, v_weak.id} <= {v.id for v in p.buffer}
    assert not p.dag.present(v_weak.id)
    # but the requester stops asking for the attested-pruned window —
    # the actual wedge: before the fix this re-requested lo=5 forever
    outbox.clear()
    p._sync_last_request = float("-inf")  # cooldown passed
    p._stuck_steps = 10**6
    p._rx_at_patience = p.metrics.counters.get("msgs_received", 0)
    p._maybe_request_sync()
    reqs = [m for m in outbox if m.kind == "sync"]
    assert reqs == [] or reqs[0].round > 8
    # the machine keeps running; ordering never touches the hole
    for _ in range(5):
        p.step()
    # if a lower-floor peer later serves the missing history, recovery
    # still happens: deliver the round-5..9 stragglers and watch the
    # whole chain admit
    from dag_rider_tpu.core.types import Vertex as _V

    for r in range(5, 10):
        prev = tuple(VertexID(r - 1, s) for s in (0, 1, 2))
        p.on_message(
            BroadcastMessage(
                vertex=_V(id=VertexID(r, 3), strong_edges=prev),
                round=r,
                sender=3,
            )
        )
    p.step()
    assert p.dag.present(v_low.id) and p.dag.present(v_strong.id)
    assert p.dag.present(v_weak.id)


def test_attested_floor_clips_byzantine_inflation():
    """A single Byzantine nack with a huge floor must not drag the
    attested floor past what an honest responder corroborates: the
    (f+1)-th largest reported value is the bound."""
    p = Process(GC, 0, InMemoryTransport())
    p.round = 10
    p._sync_last_lo = 5
    p._on_sync_nack(
        BroadcastMessage(
            vertex=None, round=10**9, sender=1, kind="sync_nack", origin=0
        )
    )
    assert p._attested_floor == 0  # one claim is not a quorum
    p._on_sync_nack(
        BroadcastMessage(
            vertex=None, round=8, sender=2, kind="sync_nack", origin=0
        )
    )
    assert p._attested_floor == 8  # clipped to the corroborated value


def test_snapshot_corruption_fuzz_never_crashes_or_corrupts():
    """Seeded fuzz over the untrusted-snapshot surface: random bit
    flips, truncations and splices must either refuse (False, receiver
    bit-untouched) or succeed with a self-consistent window — never
    raise, never commit partial state."""
    import numpy as np

    sim = _pruned_donor()
    donor = sim.processes[0]
    blob = bytearray(checkpoint.snapshot_bytes(donor))
    rng = np.random.default_rng(17)
    for trial in range(60):
        mutated = bytearray(blob)
        mode = trial % 4
        if mode == 0:  # random bit flips
            for _ in range(int(rng.integers(1, 8))):
                i = int(rng.integers(0, len(mutated)))
                mutated[i] ^= 1 << int(rng.integers(0, 8))
        elif mode == 1:  # truncation
            mutated = mutated[: int(rng.integers(0, len(mutated)))]
        elif mode == 2:  # splice a random chunk
            i = int(rng.integers(0, len(mutated)))
            mutated[i:i] = bytes(rng.integers(0, 256, 16, dtype=np.uint8))
        else:  # duplicate a tail chunk
            i = int(rng.integers(0, len(mutated)))
            mutated = mutated + mutated[i:]
        fresh = Process(GC, 0, InMemoryTransport())
        ok = checkpoint.restore_from_snapshot(fresh, bytes(mutated))
        if not ok:
            assert fresh.dag.base_round == 0 and fresh.round == 0
            assert len(fresh.dag.vertices) == GC.n  # genesis only
        else:
            # accepted: the window must be internally consistent
            assert fresh.dag.max_round - fresh.dag.base_round >= GC.gc_depth
            for v in fresh.dag.vertices.values():
                assert v.round >= fresh.dag.base_round
            fresh._started = True
            fresh.step()  # and the machine must still run


def test_snapshot_valid_json_wrong_shape_refused():
    """Valid-JSON-but-not-a-dict headers must take the False path, not
    raise (round-4 review; the bitflip fuzz can't produce these)."""
    for head in (b"[]", b"42", b'"x"', b"null"):
        blob = struct.pack("<I", len(head)) + head
        fresh = Process(GC, 0, InMemoryTransport())
        assert not checkpoint.restore_from_snapshot(fresh, blob)
        assert fresh.round == 0
