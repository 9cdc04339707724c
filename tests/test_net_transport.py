"""gRPC transport + Verifier sidecar integration tests (localhost)."""

import pytest

from dag_rider_tpu.config import Config
from dag_rider_tpu.consensus.process import Process
from dag_rider_tpu.core.types import Block, BroadcastMessage, Vertex, VertexID
from dag_rider_tpu.transport.net import GrpcTransport
from dag_rider_tpu.verifier.base import KeyRegistry, VertexSigner
from dag_rider_tpu.verifier.cpu import CPUVerifier
from dag_rider_tpu.verifier.sidecar import RemoteVerifier, VerifierSidecarServer


@pytest.fixture
def grpc_cluster():
    """4 GrpcTransports wired over localhost with real port discovery."""
    n = 4
    transports = []
    for i in range(n):
        transports.append(GrpcTransport(i, "127.0.0.1:0", {}))
    addrs = {i: f"127.0.0.1:{t.bound_port}" for i, t in enumerate(transports)}
    for t in transports:
        t._peers.update(addrs)
    yield transports
    for t in transports:
        t.close()


def _pump_all(transports, rounds=200):
    for _ in range(rounds):
        moved = False
        for t in transports:
            moved |= t.pump(16) > 0
        if not moved:
            break


def test_grpc_broadcast_reaches_all_peers(grpc_cluster):
    transports = grpc_cluster
    got = {i: [] for i in range(4)}
    for i, t in enumerate(transports):
        t.subscribe(i, got[i].append)
    v = Vertex(id=VertexID(1, 0), strong_edges=(VertexID(0, 1),))
    transports[0].broadcast(BroadcastMessage(vertex=v, round=1, sender=0))
    import time

    deadline = time.time() + 5
    while time.time() < deadline and any(
        not got[i] for i in range(1, 4)
    ):
        _pump_all(transports, rounds=1)
        time.sleep(0.01)
    assert not got[0], "sender must not receive its own broadcast"
    for i in range(1, 4):
        assert got[i] and got[i][0].vertex == v, f"peer {i} missed delivery"


def test_grpc_cluster_reaches_consensus(grpc_cluster):
    """Full 4-process consensus over real gRPC sockets."""
    import time

    transports = grpc_cluster
    cfg = Config(n=4)
    delivered = [[] for _ in range(4)]
    procs = [
        Process(cfg, i, transports[i], on_deliver=delivered[i].append)
        for i in range(4)
    ]
    for p in procs:
        for k in range(2):
            p.submit(Block((f"p{p.index}-b{k}".encode(),)))
    for p in procs:
        p.start()
    deadline = time.time() + 20
    while time.time() < deadline and not all(
        len(d) >= 4 for d in delivered
    ):
        _pump_all(transports, rounds=2)
        time.sleep(0.005)
    assert all(len(d) >= 4 for d in delivered), [len(d) for d in delivered]
    # agreement on the common prefix
    logs = [[v.id for v in d] for d in delivered]
    k = min(len(l) for l in logs)
    assert all(l[:k] == logs[0][:k] for l in logs)


def test_sidecar_roundtrip_matches_local():
    reg, seeds = KeyRegistry.generate(4)
    signers = [VertexSigner(s) for s in seeds]
    vs = []
    for i in range(4):
        v = Vertex(
            id=VertexID(2, i),
            block=Block((f"tx{i}".encode(),)),
            strong_edges=(VertexID(1, 0), VertexID(1, 1), VertexID(1, 2)),
        )
        vs.append(signers[i].sign_vertex(v))
    vs.append(vs[0])  # duplicate fine
    import dataclasses

    vs.append(dataclasses.replace(vs[1], signature=b"\x00" * 64))

    local = CPUVerifier(reg)
    server = VerifierSidecarServer(local)
    try:
        remote = RemoteVerifier(server.address)
        assert remote.verify_batch(vs) == local.verify_batch(vs)
        assert remote.verify_batch([]) == []
        remote.close()
    finally:
        server.stop()


def test_remote_verifier_fails_closed():
    remote = RemoteVerifier("127.0.0.1:1", timeout=0.5)  # nothing listening
    v = Vertex(id=VertexID(1, 0))
    assert remote.verify_batch([v, v]) == [False, False]
    remote.close()


# ----------------------------------------------------------------------
# Observability + retry (round-2 VERDICT weak #8)
# ----------------------------------------------------------------------


def test_grpc_send_counters_on_success(grpc_cluster):
    import time

    transports = grpc_cluster
    got = []
    transports[1].subscribe(1, got.append)
    v = Vertex(id=VertexID(1, 0), strong_edges=(VertexID(0, 1),))
    transports[0].broadcast(BroadcastMessage(vertex=v, round=1, sender=0))
    deadline = time.time() + 5
    while time.time() < deadline and (
        transports[0].metrics.counters.get("net_sends_ok", 0) < 3
    ):
        time.sleep(0.01)
    c = transports[0].metrics.counters
    assert c["net_sends"] == 3
    assert c["net_sends_ok"] == 3
    assert c.get("net_drops", 0) == 0


def test_grpc_retry_then_drop_on_dead_peer():
    import time

    # peer 1 points at a port with nothing listening
    t0 = GrpcTransport(
        0,
        "127.0.0.1:0",
        {1: "127.0.0.1:1"},
        retries=2,
        retry_backoff_s=0.01,
        rpc_timeout_s=0.3,
    )
    try:
        v = Vertex(id=VertexID(1, 0), strong_edges=(VertexID(0, 1),))
        t0.broadcast(BroadcastMessage(vertex=v, round=1, sender=0))
        deadline = time.time() + 10
        while time.time() < deadline and t0.metrics.counters.get("net_drops", 0) < 1:
            time.sleep(0.02)
        c = t0.metrics.counters
        assert c["net_send_errors"] == 3  # initial + 2 retries
        assert c["net_retries"] == 2
        assert c["net_drops"] == 1
    finally:
        t0.close()


def test_grpc_attach_metrics_merges_counters(grpc_cluster):
    import time

    from dag_rider_tpu.utils.metrics import Metrics

    transports = grpc_cluster
    transports[2].subscribe(2, lambda m: None)
    v = Vertex(id=VertexID(1, 0), strong_edges=(VertexID(0, 1),))
    transports[0].broadcast(BroadcastMessage(vertex=v, round=1, sender=0))
    deadline = time.time() + 5
    while time.time() < deadline and (
        transports[0].metrics.counters.get("net_sends_ok", 0) < 3
    ):
        time.sleep(0.01)
    shared = Metrics()
    shared.inc("vertices_admitted", 7)  # pre-existing consensus counter
    transports[0].attach_metrics(shared)
    snap = shared.snapshot()
    assert snap["net_sends"] == 3 and snap["vertices_admitted"] == 7
    # post-attach traffic lands in the shared Metrics
    transports[0].broadcast(BroadcastMessage(vertex=v, round=1, sender=0))
    deadline = time.time() + 5
    while time.time() < deadline and shared.counters.get("net_sends", 0) < 6:
        time.sleep(0.01)
    assert shared.counters["net_sends"] == 6


def test_grpc_16_node_cluster_with_rbc_reaches_consensus():
    """BASELINE rung #2 shape at n=16, over real gRPC sockets, with the
    Bracha RBC stage in the path (round-2 VERDICT next #10)."""
    import time

    from dag_rider_tpu.transport.rbc import RbcTransport

    n = 16
    cfg = Config(n=n, coin="round_robin", propose_empty=False)
    nets = [GrpcTransport(i, "127.0.0.1:0", {}) for i in range(n)]
    addrs = {i: f"127.0.0.1:{t.bound_port}" for i, t in enumerate(nets)}
    for t in nets:
        t._peers.update(addrs)
    try:
        rbcs = [RbcTransport(nets[i], i, n, cfg.f) for i in range(n)]
        delivered = [[] for _ in range(n)]
        procs = [
            Process(
                cfg, i, rbcs[i], on_deliver=delivered[i].append
            )
            for i in range(n)
        ]
        for p in procs:
            p.defer_steps = True  # burst delivery, one step per pump pass
            # 10 blocks/process: wave 2's boundary is round 8, so the
            # cluster must outlive round 8 for a multi-wave leader chain
            # (wave 1 alone delivers only the leader's 1-vertex history).
            for k in range(10):
                p.submit(Block((f"p{p.index}-b{k}".encode(),)))
        for p in procs:
            p.start()
        deadline = time.time() + 120
        while time.time() < deadline and not all(
            len(d) >= n for d in delivered
        ):
            moved = False
            for t in nets:
                moved |= t.pump(64) > 0
            for p in procs:
                p.step()
            if not moved:
                time.sleep(0.002)
        assert all(len(d) >= n for d in delivered), [
            len(d) for d in delivered
        ]
        logs = [[(v.id.round, v.id.source, v.digest()) for v in d] for d in delivered]
        k = min(len(l) for l in logs)
        assert all(l[:k] == logs[0][:k] for l in logs)
        # RBC really was in the path: every process echoed and readied
        assert all(r._delivered for r in rbcs)
        # transport observability: sends counted on every node
        assert all(t.metrics.counters["net_sends"] > 0 for t in nets)
    finally:
        for t in nets:
            t.close()


def test_failure_detector_marks_peer_down_and_recovers():
    """SURVEY §5 failure detection: consecutive send failures mark a peer
    down; the first success marks it up again."""
    import time

    victim = GrpcTransport(1, "127.0.0.1:0", {})
    victim_addr = f"127.0.0.1:{victim.bound_port}"
    victim.subscribe(1, lambda m: None)
    victim.close()  # peer starts dead

    t0 = GrpcTransport(
        0,
        "127.0.0.1:0",
        {1: victim_addr},
        retries=0,
        rpc_timeout_s=0.3,
    )
    try:
        v = Vertex(id=VertexID(1, 0), strong_edges=(VertexID(0, 1),))
        msg = BroadcastMessage(vertex=v, round=1, sender=0)
        deadline = time.time() + 15
        while (
            time.time() < deadline
            and t0.peer_status().get(1) != "down"
        ):
            t0.broadcast(msg)
            time.sleep(0.05)
        assert t0.peer_status() == {1: "down"}
        assert t0.metrics.counters["net_peer_down"] == 1

        # peer comes back on the same address
        revived = GrpcTransport(1, victim_addr, {})
        if revived.bound_port == 0:  # port was re-grabbed meanwhile
            revived.close()
            pytest.skip("ephemeral port reused by another process")
        try:
            revived.subscribe(1, lambda m: None)
            deadline = time.time() + 15
            while (
                time.time() < deadline
                and t0.peer_status().get(1) != "up"
            ):
                t0.broadcast(msg)
                time.sleep(0.05)
            assert t0.peer_status() == {1: "up"}
            assert t0.metrics.counters["net_peer_recovered"] >= 1
        finally:
            revived.close()
    finally:
        t0.close()


# ----------------------------------------------------------------------
# Authenticated control frames (round-3 VERDICT missing #5)
# ----------------------------------------------------------------------


def _auth_cluster(n, cfg):
    """n GrpcTransports with pairwise-MAC frame auth + RBC stages."""
    from dag_rider_tpu.transport.auth import FrameAuth
    from dag_rider_tpu.transport.rbc import RbcTransport

    auths = FrameAuth.derive(b"cluster-master-secret", n)
    nets = [
        GrpcTransport(i, "127.0.0.1:0", {}, auth=auths[i]) for i in range(n)
    ]
    addrs = {i: f"127.0.0.1:{t.bound_port}" for i, t in enumerate(nets)}
    for t in nets:
        t._peers.update(addrs)
    rbcs = [RbcTransport(nets[i], i, n, cfg.f) for i in range(n)]
    return nets, rbcs


def test_authenticated_cluster_reaches_consensus():
    """Positive path: MAC'd frames (incl. forwarded catch-up VALs) flow."""
    import time

    n = 4
    cfg = Config(n=n, coin="round_robin", propose_empty=False)
    nets, rbcs = _auth_cluster(n, cfg)
    try:
        delivered = [[] for _ in range(n)]
        procs = [
            Process(cfg, i, rbcs[i], on_deliver=delivered[i].append)
            for i in range(n)
        ]
        for p in procs:
            p.defer_steps = True
            # 10 blocks/process: the cluster must outlive round 8 (wave
            # 2's boundary) for a multi-wave leader chain to deliver n+
            # vertices everywhere.
            for k in range(10):
                p.submit(Block((f"p{p.index}-b{k}".encode(),)))
        for p in procs:
            p.start()
        deadline = time.time() + 60
        while time.time() < deadline and not all(
            len(d) >= n for d in delivered
        ):
            moved = False
            for t in nets:
                moved |= t.pump(64) > 0
            for p in procs:
                p.step()
            if not moved:
                time.sleep(0.002)
        assert all(len(d) >= n for d in delivered)
        logs = [
            [(v.id.round, v.id.source, v.digest()) for v in d]
            for d in delivered
        ]
        k = min(len(l) for l in logs)
        assert all(l[:k] == logs[0][:k] for l in logs)
        assert all(
            t.metrics.counters.get("net_auth_rejects", 0) == 0 for t in nets
        )
    finally:
        for t in nets:
            t.close()


def test_forged_ready_quorum_over_grpc_does_not_deliver():
    """THE attack the round-3 VERDICT names: a Byzantine peer crafts
    ECHO+READY frames stamped with every honest process's identity and
    fires them at one victim over the open gRPC endpoint, trying to
    fabricate a Bracha quorum for a vertex nobody broadcast. With frame
    auth the forged votes are rejected at the wire (wrong/absent MACs or
    sender != authenticated relayer) and nothing is delivered."""
    import struct
    import time

    import grpc as _grpc

    from dag_rider_tpu.core import codec
    from dag_rider_tpu.transport.auth import FrameAuth

    n = 4
    cfg = Config(n=n, coin="round_robin", propose_empty=False)
    nets, rbcs = _auth_cluster(n, cfg)
    try:
        sunk = []
        rbcs[0].subscribe(0, sunk.append)  # victim's delivery sink

        ghost = Vertex(
            id=VertexID(1, 2),
            block=Block((b"forged",)),
            strong_edges=tuple(VertexID(0, s) for s in range(cfg.quorum)),
        )
        digest = ghost.digest()
        victim_addr = f"127.0.0.1:{nets[0].bound_port}"
        chan = _grpc.insecure_channel(victim_addr)
        call = chan.unary_unary(
            "/dagrider.Transport/Deliver",
            request_serializer=lambda b: b,
            response_deserializer=lambda b: b,
        )
        # Byzantine node 3 DOES know its own pair key with the victim —
        # forge votes claiming senders 1 and 2 under node 3's MAC, plus
        # tagless and garbage-tagged variants.
        atk = FrameAuth.derive(b"cluster-master-secret", n)[3]
        frames = []
        for sender in (1, 2, 3):
            for kind in ("echo", "ready"):
                body = codec.encode_message(
                    BroadcastMessage(
                        vertex=None,
                        round=1,
                        sender=sender,
                        kind=kind,
                        origin=2,
                        digest=digest,
                    )
                )
                # relayer=3 with valid MAC (sender mismatch must reject
                # for sender in {1,2}; sender==3 is a legit single vote)
                frames.append(
                    struct.pack("<I", 3) + body + atk.tag(0, body)
                )
                # relayer claimed as the forged sender, MAC forged
                frames.append(
                    struct.pack("<I", sender) + body + b"\x00" * 32
                )
                # no auth wrapper at all
                frames.append(body)
        # the forged VAL itself, forwarded by 3 with a valid MAC (val forwards
        # are allowed through auth; Bracha still needs a READY quorum)
        val_body = codec.encode_message(
            BroadcastMessage(vertex=ghost, round=1, sender=2, kind="val")
        )
        frames.append(struct.pack("<I", 3) + val_body + atk.tag(0, val_body))
        for f in frames:
            call(f, timeout=5)
        deadline = time.time() + 3
        while time.time() < deadline:
            nets[0].pump(64)
            time.sleep(0.01)
        # one Byzantine identity cannot make a 2f+1 READY quorum:
        assert sunk == []
        slot = (1, 2)
        readies = rbcs[0]._readies.get((slot, digest), set())
        assert 3 not in readies or len(readies) < cfg.quorum
        assert 1 not in readies and 2 not in readies
        assert nets[0].metrics.counters.get("net_auth_rejects", 0) >= 8
        chan.close()
    finally:
        for t in nets:
            t.close()


def test_update_peer_repoints_stale_channel():
    """A peer that restarts on a NEW address is unreachable through the
    cached gRPC channel until update_peer drops it (round-4 soak
    finding; stable-address deployments reconnect automatically)."""
    import time

    a = GrpcTransport(0, "127.0.0.1:0", {})
    b1 = GrpcTransport(1, "127.0.0.1:0", {})
    a._peers.update({1: f"127.0.0.1:{b1.bound_port}"})
    b1._peers.update({0: f"127.0.0.1:{a.bound_port}"})
    got = []
    b1.subscribe(1, got.append)
    v = Vertex(id=VertexID(1, 0), strong_edges=(VertexID(0, 1),))
    a.broadcast(BroadcastMessage(vertex=v, round=1, sender=0))
    deadline = time.time() + 5
    while time.time() < deadline and not got:
        b1.pump(8)
        time.sleep(0.01)
    assert got, "baseline delivery failed"
    b1.close()

    # peer 1 restarts on a different port
    b2 = GrpcTransport(1, "127.0.0.1:0", {})
    b2._peers.update({0: f"127.0.0.1:{a.bound_port}"})
    got2 = []
    b2.subscribe(1, got2.append)
    a.update_peer(1, f"127.0.0.1:{b2.bound_port}")
    a.broadcast(BroadcastMessage(vertex=v, round=1, sender=0))
    deadline = time.time() + 5
    while time.time() < deadline and not got2:
        b2.pump(8)
        time.sleep(0.01)
    assert got2, "delivery after update_peer failed"
    a.close()
    b2.close()


def test_snapshot_rpc_hardening():
    """Round-5 review: the Snapshot endpoint must (a) serve authenticated
    fresh requests from a serialized-once cache, (b) throttle per relayer
    without letting replayed captures charge the victim's slot, (c) bound
    global egress with a token bucket, (d) refuse stale timestamps with a
    distinct counter, MAC-checked first."""
    import struct as _struct
    import time as _time

    import grpc

    from dag_rider_tpu.transport.auth import FrameAuth
    from dag_rider_tpu.transport.net import _SNAP_DOMAIN

    auths = FrameAuth.derive(b"m", 8)
    calls = [0]

    def provider():
        calls[0] += 1
        return b"w" * 256

    # Donor A: long interval so every throttle assertion is deterministic
    # however slow the host is (no wall-clock races).
    donor = GrpcTransport(
        0, "127.0.0.1:0", {}, auth=auths[0], snapshot_provider=provider,
        snapshot_min_interval_s=60.0,
    )
    peers = {0: f"127.0.0.1:{donor.bound_port}"}
    fetchers = [
        GrpcTransport(i, "127.0.0.1:0", dict(peers), auth=auths[i])
        for i in (1, 2, 3)
    ]
    try:
        # burst of 3 distinct relayers: all served (bucket), 1 serialization
        for f in fetchers:
            assert f.fetch_snapshot(0) == b"w" * 256
        assert calls[0] == 1, f"cache missed: {calls[0]}"
        # 4th distinct relayer in the same burst: global bucket empty
        extra = GrpcTransport(4, "127.0.0.1:0", dict(peers), auth=auths[4])
        try:
            assert extra.fetch_snapshot(0) is None
        finally:
            extra.close()
        snap = donor.metrics.snapshot()
        assert snap.get("net_snapshot_global_throttled", 0) >= 1, snap
        # same relayer again inside the interval: per-relayer throttle
        assert fetchers[0].fetch_snapshot(0) is None
        snap = donor.metrics.snapshot()
        assert snap.get("net_snapshot_throttled", 0) >= 1, snap
    finally:
        donor.close()
        for f in fetchers:
            f.close()

    # Donor B: tiny interval so replay/stale classification is exercised
    # without sleeping through a refill.
    donor = GrpcTransport(
        0, "127.0.0.1:0", {}, auth=auths[0], snapshot_provider=provider,
        snapshot_min_interval_s=0.01,
    )
    raw = grpc.insecure_channel(f"127.0.0.1:{donor.bound_port}")
    try:
        call = raw.unary_unary(
            "/dagrider.Transport/Snapshot",
            request_serializer=lambda b: b,
            response_deserializer=lambda b: b,
        )
        # replayed capture: a relayer's ts is consumed on first serve ->
        # the exact replay gets a distinct refusal that does NOT charge
        # the victim's throttle slot
        ts = _struct.pack("<d", _time.time())
        req2 = (
            _struct.pack("<I", 5)
            + ts
            + auths[5].tag(0, _SNAP_DOMAIN + ts)
        )
        assert bytes(call(req2, timeout=5)) != b""  # fresh ts: served
        assert bytes(call(req2, timeout=5)) == b""  # exact replay refused
        snap = donor.metrics.snapshot()
        assert snap.get("net_snapshot_replays", 0) == 1, snap
        # an OLDER ts from the same relayer: classified stale (clock
        # step / reordered capture), not replay
        older = _struct.pack("<d", _time.time() - 30)
        req_older = (
            _struct.pack("<I", 5)
            + older
            + auths[5].tag(0, _SNAP_DOMAIN + older)
        )
        assert bytes(call(req_older, timeout=5)) == b""
        snap = donor.metrics.snapshot()
        assert snap.get("net_snapshot_stale_refusals", 0) == 1, snap
        # out-of-freshness-window but MAC-valid: stale counter too
        old = _struct.pack("<d", _time.time() - 3600)
        req_old = (
            _struct.pack("<I", 3)
            + old
            + auths[3].tag(0, _SNAP_DOMAIN + old)
        )
        assert bytes(call(req_old, timeout=5)) == b""
        snap = donor.metrics.snapshot()
        assert snap.get("net_snapshot_stale_refusals", 0) == 2, snap
        # garbage of the right length: reject WITHOUT touching stale counter
        junk = b"\xff" * len(req_old)
        assert bytes(call(junk, timeout=5)) == b""
        snap = donor.metrics.snapshot()
        assert snap.get("net_snapshot_stale_refusals", 0) == 2, snap
        assert snap.get("net_snapshot_rejects", 0) >= 1, snap
    finally:
        raw.close()
        donor.close()


# -- round 20: cluster-mode seams (Submit door, WAN faults, unicast) ----


def test_snapshot_cached_serve_then_stale_cache_refresh():
    """The Snapshot cache serves repeated fetches from one serialization
    inside the TTL, then refreshes — a later fetch observes new donor
    state, which is what lets a rejoiner chase a moving head."""
    import time as _time

    from dag_rider_tpu.transport.auth import FrameAuth

    state = {"blob": b"A" * 64, "calls": 0}

    def provider():
        state["calls"] += 1
        return state["blob"]

    # frame auth so each fetcher has a relayer identity: the throttle is
    # then per-relayer + token bucket, not the strict anonymous cap
    auths = FrameAuth.derive(b"m", 3)
    donor = GrpcTransport(
        0, "127.0.0.1:0", {}, auth=auths[0],
        snapshot_provider=provider,
        snapshot_min_interval_s=0.3,
    )
    peers = {0: f"127.0.0.1:{donor.bound_port}"}
    f1 = GrpcTransport(1, "127.0.0.1:0", dict(peers), auth=auths[1])
    f2 = GrpcTransport(2, "127.0.0.1:0", dict(peers), auth=auths[2])
    try:
        assert f1.fetch_snapshot(0) == b"A" * 64
        # donor state moves on; within the TTL the cache still serves
        # the old blob from ONE serialization
        state["blob"] = b"B" * 64
        assert f2.fetch_snapshot(0) == b"A" * 64
        assert state["calls"] == 1, "cache must serve the second fetch"
        _time.sleep(0.35)  # TTL expiry
        assert f1.fetch_snapshot(0) == b"B" * 64, "stale cache must refresh"
        assert state["calls"] == 2
    finally:
        donor.close()
        f1.close()
        f2.close()


def test_snapshot_rpc_serves_pruned_window_for_rejoin():
    """Snapshot-while-pruned: the donor has GC'd past genesis, so a node
    that was dead too long can only rejoin via the Snapshot RPC — fetch
    the live window over the wire and replay it into a fresh process."""
    from dag_rider_tpu.consensus.simulator import Simulation
    from dag_rider_tpu.transport.memory import InMemoryTransport
    from dag_rider_tpu.utils import checkpoint

    gc_cfg = Config(n=4, coin="round_robin", propose_empty=True, gc_depth=16)
    sim = Simulation(gc_cfg)
    sim.submit_blocks(per_process=2)
    for _ in range(600):
        sim.run(max_messages=100)
        if max(p.round for p in sim.processes) >= 50:
            break
    donor_proc = sim.processes[0]
    assert donor_proc.dag.base_round > 0, "donor must have pruned"

    donor = GrpcTransport(
        0, "127.0.0.1:0", {},
        snapshot_provider=lambda: checkpoint.snapshot_bytes(donor_proc),
        snapshot_min_interval_s=0.01,
    )
    fetcher = GrpcTransport(
        1, "127.0.0.1:0", {0: f"127.0.0.1:{donor.bound_port}"}
    )
    try:
        blob = fetcher.fetch_snapshot(0)
        assert blob, "pruned-window snapshot must be served"
        fresh = Process(gc_cfg, 1, InMemoryTransport())
        assert checkpoint.restore_from_snapshot(fresh, blob)
        assert fresh.dag.base_round == donor_proc.dag.base_round
        assert fresh.round == donor_proc.dag.max_round
    finally:
        donor.close()
        fetcher.close()


def test_submit_door_roundtrip_and_failure_containment():
    """The client Submit front door: closed by default, serves the bound
    sink's bytes when open, contains sink exceptions as empty (=refusal)
    responses, and counts every call."""
    import grpc as _grpc

    node = GrpcTransport(0, "127.0.0.1:0", {})
    chan = _grpc.insecure_channel(f"127.0.0.1:{node.bound_port}")
    call = chan.unary_unary(
        "/dagrider.Transport/Submit",
        request_serializer=lambda b: b,
        response_deserializer=lambda b: b,
    )
    try:
        # door closed: gRPC-level unimplemented, not a crash
        with pytest.raises(_grpc.RpcError):
            call(b"{}", timeout=5)

        seen = []

        def sink(req: bytes) -> bytes:
            seen.append(req)
            if req == b"boom":
                raise ValueError("malformed frame")
            return b"ok:" + req

        node.set_submit_sink(sink)
        assert bytes(call(b"hello", timeout=5)) == b"ok:hello"
        assert bytes(call(b"boom", timeout=5)) == b"", (
            "sink exception must become an empty refusal"
        )
        assert seen == [b"hello", b"boom"]
        snap = node.metrics.snapshot()
        assert snap.get("net_client_submits", 0) == 2, snap
        # door closes again: refuse without invoking the old sink
        node.set_submit_sink(None)
        with pytest.raises(_grpc.RpcError):
            call(b"late", timeout=5)
        assert seen == [b"hello", b"boom"]
    finally:
        chan.close()
        node.close()


def test_enqueue_is_unicast_but_protocol_gate_opts_out():
    """GrpcTransport.enqueue sends to exactly one peer (the Byzantine
    per-destination seam), but resolve_unicast must NOT route honest
    protocol traffic through it — single-copy sync over a lossy socket
    loses whole patience windows during recovery."""
    import time as _time

    from dag_rider_tpu.transport.base import resolve_unicast

    transports = [GrpcTransport(i, "127.0.0.1:0", {}) for i in range(3)]
    addrs = {
        i: f"127.0.0.1:{t.bound_port}" for i, t in enumerate(transports)
    }
    for t in transports:
        t._peers.update(addrs)
    got = {i: [] for i in range(3)}
    for i, t in enumerate(transports):
        t.subscribe(i, got[i].append)
    try:
        # honest routing refuses the unicast seam on this transport
        assert resolve_unicast(transports[0]) is None
        assert GrpcTransport.protocol_unicast is False
        # ...but the seam itself works, one destination only
        v = Vertex(id=VertexID(1, 0), strong_edges=(VertexID(0, 1),))
        transports[0].enqueue(1, BroadcastMessage(vertex=v, round=1, sender=0))
        deadline = _time.time() + 5
        while _time.time() < deadline and not got[1]:
            _pump_all(transports, rounds=1)
            _time.sleep(0.01)
        assert got[1] and got[1][0].vertex == v
        assert not got[2], "enqueue must not broadcast"
        # the adversary seam deliberately ignores the honest gate
        from dag_rider_tpu.consensus.adversary import _resolve_enqueue

        assert _resolve_enqueue(transports[0]) is not None
    finally:
        for t in transports:
            t.close()


def test_wan_fault_drop_is_not_charged_to_failure_detector():
    """A WAN drop is weather, not a dead peer: the send never happens,
    net_wan_drops counts it, and the failure detector's consecutive-
    failure ledger stays clean."""
    from dag_rider_tpu.transport.net import WanFault

    sink = GrpcTransport(1, "127.0.0.1:0", {})
    src = GrpcTransport(
        0,
        "127.0.0.1:0",
        {1: f"127.0.0.1:{sink.bound_port}"},
        send_fault=WanFault(seed=1, drop=1.0),
    )
    got = []
    sink.subscribe(1, got.append)
    try:
        v = Vertex(id=VertexID(1, 0), strong_edges=(VertexID(0, 1),))
        for _ in range(5):
            src.broadcast(BroadcastMessage(vertex=v, round=1, sender=0))
        snap = src.metrics.snapshot()
        assert snap.get("net_wan_drops", 0) == 5, snap
        assert snap.get("net_sends", 0) == 0, "dropped before the socket"
        assert src._consec_fail.get(1, 0) == 0, (
            "drops must not charge the failure detector"
        )
        sink.pump(16)
        assert not got
    finally:
        src.close()
        sink.close()


def test_wan_fault_delay_still_delivers():
    """Delayed sends are late, not lost: the message arrives after the
    seeded hold and net_wan_delays records the weather."""
    import time as _time

    from dag_rider_tpu.transport.net import WanFault

    sink = GrpcTransport(1, "127.0.0.1:0", {})
    src = GrpcTransport(
        0,
        "127.0.0.1:0",
        {1: f"127.0.0.1:{sink.bound_port}"},
        send_fault=WanFault(seed=2, delay_ms=(5.0, 20.0), delay_rate=1.0),
    )
    got = []
    sink.subscribe(1, got.append)
    try:
        v = Vertex(id=VertexID(1, 0), strong_edges=(VertexID(0, 1),))
        src.broadcast(BroadcastMessage(vertex=v, round=1, sender=0))
        deadline = _time.time() + 5
        while _time.time() < deadline and not got:
            sink.pump(16)
            _time.sleep(0.01)
        assert got and got[0].vertex == v
        snap = src.metrics.snapshot()
        assert snap.get("net_wan_delays", 0) == 1, snap
    finally:
        src.close()
        sink.close()
