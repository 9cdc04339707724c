"""The proposer cuts its own block: a ``Process`` with a
``block_source`` asks it for a vertex's block when it makes the vertex,
after whatever ``submit`` queued; a ``Node`` with a mempool attaches it
as that source and stages nothing in front of consensus.
"""

import threading
import time

import pytest

from dag_rider_tpu import Config
from dag_rider_tpu import node as node_mod
from dag_rider_tpu.config import MempoolConfig
from dag_rider_tpu.consensus import Process
from dag_rider_tpu.core.types import Block, BroadcastMessage, Vertex, VertexID
from dag_rider_tpu.mempool import Mempool
from dag_rider_tpu.obs import spans
from dag_rider_tpu.transport import InMemoryTransport

N = 4


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def sourced(propose_empty, **mcfg):
    """Process 0 of four, alone on its transport, its mempool on a
    clock the test turns."""
    clock = Clock()
    mp = Mempool(MempoolConfig(cap=256, **mcfg), clock=clock)
    p = Process(
        Config(n=N, coin="round_robin", propose_empty=propose_empty),
        0,
        InMemoryTransport(),
    )
    p.on_propose = mp.observe_proposed
    p.block_source = mp
    return p, mp, clock


def peers_reach(p, rnd):
    """Peers 1 and 2 deliver their round-``rnd`` vertices: with
    process 0's own that is the quorum that lets it leave the round."""
    edges = tuple(VertexID(rnd - 1, i) for i in range(3))
    for src in (1, 2):
        v = Vertex(id=VertexID(rnd, src), strong_edges=edges)
        p.on_message(BroadcastMessage(vertex=v, round=rnd, sender=src))
    p.step()


def carried(p, rnd):
    return p.dag.get(VertexID(rnd, 0)).block.transactions


def cut_at_propose():
    return spans.snapshot()["counts"].get("mempool.cut_at_propose", 0)


@pytest.mark.parametrize("age_ms", [0.0, 1.0, 49.0, 500.0])
def test_a_transaction_pending_when_a_vertex_is_made_rides_that_vertex(age_ms):
    p, mp, clock = sourced(propose_empty=True, batch_deadline_ms=50.0)
    p.start()
    assert p.round == 1 and carried(p, 1) == ()
    for rnd in (2, 3, 4):
        mp.submit((f"tx-r{rnd}".encode(),))
        clock.t += age_ms / 1e3  # younger than the deadline or not
        before = cut_at_propose()
        peers_reach(p, rnd - 1)
        assert p.round == rnd and carried(p, rnd) == (f"tx-r{rnd}".encode(),)
        assert cut_at_propose() == before + 1
        assert not p.blocks_to_propose
    # nothing pending: an empty vertex, and nothing counted as cut
    before = cut_at_propose()
    peers_reach(p, 4)
    assert p.round == 5 and carried(p, 5) == ()
    assert cut_at_propose() == before


def test_one_block_a_vertex_takes_all_that_fits_and_leaves_the_rest():
    p, mp, clock = sourced(propose_empty=True, batch_bytes=64)
    mp.submit([bytes([65 + i]) * 32 for i in range(5)])
    p.start()
    assert len(carried(p, 1)) == 2 and len(mp.pool) == 3
    peers_reach(p, 1)
    peers_reach(p, 2)
    assert [len(carried(p, r)) for r in (2, 3)] == [2, 1] and len(mp.pool) == 0


def test_queued_blocks_go_before_anything_is_pulled():
    """A restart's restored staged blocks and a direct ``submit`` are
    served first, in their order; the pool waits behind them."""
    p, mp, clock = sourced(propose_empty=True)
    p.submit(Block((b"staged-0",)))
    p.submit(Block((b"staged-1",)))
    mp.submit((b"pooled",))
    before = cut_at_propose()
    p.start()
    peers_reach(p, 1)
    assert carried(p, 1) == (b"staged-0",) and carried(p, 2) == (b"staged-1",)
    assert cut_at_propose() == before and len(mp.pool) == 1
    mp.submit((b"pooled-later",))
    peers_reach(p, 2)
    assert carried(p, 3) == (b"pooled", b"pooled-later")
    assert cut_at_propose() == before + 1


def test_mempool_wait_is_stamped_where_the_pulled_block_leaves_for_its_vertex():
    p, mp, clock = sourced(propose_empty=True)
    p.start()
    mp.submit((b"early",))
    clock.t = 0.300
    mp.submit((b"late",))
    clock.t = 0.400
    before = spans.snapshot()["spans"].get("mempool.wait", {"count": 0, "total_ns": 0})
    peers_reach(p, 1)
    after = spans.snapshot()["spans"]["mempool.wait"]
    assert after["count"] == before["count"] + 1
    # from the block's EARLIEST submit to its vertex
    assert after["total_ns"] - before["total_ns"] == pytest.approx(0.4e9, rel=1e-6)


def test_without_empty_proposals_the_round_waits_for_the_deadline():
    p, mp, clock = sourced(propose_empty=False, batch_deadline_ms=50.0)
    p.start()
    assert p.round == 0  # nothing to propose: the paper's wait
    mp.submit((b"lonely",))
    clock.t = 0.049
    p.step()
    assert p.round == 0 and len(mp.pool) == 1  # a partial block is held
    clock.t = 0.050
    p.step()
    assert p.round == 1 and carried(p, 1) == (b"lonely",)
    # the next round waits again, and takes the young with the old
    peers_reach(p, 1)
    assert p.round == 1
    mp.submit((b"old",))
    clock.t = 0.099
    mp.submit((b"young",))
    p.step()
    assert p.round == 1
    clock.t = 0.100
    p.step()
    assert p.round == 2 and carried(p, 2) == (b"old", b"young")


def test_without_empty_proposals_a_full_block_does_not_wait():
    p, mp, clock = sourced(propose_empty=False, batch_deadline_ms=50.0, batch_bytes=64)
    p.start()
    mp.submit((b"a" * 32,))
    p.step()
    assert p.round == 0
    mp.submit((b"b" * 32,))  # batch_bytes reached, age 0
    p.step()
    assert p.round == 1 and carried(p, 1) == (b"a" * 32, b"b" * 32)


def test_without_empty_proposals_a_queued_block_is_available_at_once():
    p, mp, clock = sourced(propose_empty=False, batch_deadline_ms=50.0)
    p.start()
    p.submit(Block((b"direct",)))  # submit() steps the process itself
    assert p.round == 1 and carried(p, 1) == (b"direct",)


def test_a_process_without_a_source_proposes_only_what_was_submitted():
    p = Process(Config(n=N, coin="round_robin", propose_empty=False), 0, InMemoryTransport())
    assert p.block_source is None
    p.start()
    assert p.round == 0
    p.submit(Block((b"pushed",)))
    assert p.round == 1 and carried(p, 1) == (b"pushed",)


# -- four validators over sockets ---------------------------------------------


def cluster(tmp_path, keys_path, *, delay_ms=25.0, deadline_ms=5.0):
    """Four ``Node``s in this process over gRPC on localhost, every
    frame held ``delay_ms``: a round is several of the mempool's
    deadlines long, as a WAN committee's is."""
    nodes = []
    for i in range(N):
        nodes.append(
            node_mod.Node(
                {
                    "index": i,
                    "n": N,
                    "listen": "127.0.0.1:0",
                    "peers": {},
                    "keys": str(keys_path),
                    "rbc": False,
                    "verifier": "none",
                    "coin": "round_robin",
                    "propose_empty": True,
                    "wan": {"seed": 7, "delay_ms": [delay_ms, delay_ms]},
                    "mempool": {"batch_deadline_ms": deadline_ms},
                    "checkpoint_dir": str(tmp_path / f"ckpt{i}"),
                    "checkpoint_every_s": 0,  # on stop only
                }
            )
        )
    addrs = {i: f"127.0.0.1:{nd.net.bound_port}" for i, nd in enumerate(nodes)}
    for i, nd in enumerate(nodes):
        nd.net._peers.update({j: a for j, a in addrs.items() if j != i})
    return nodes


def watch_waits(nd):
    """Every ``mempool.wait`` of this node as its mempool books it (the
    span book is the process's, and four nodes share it), beside the
    time since the node's previous non-empty vertex: both in seconds."""
    waits = []
    mp = nd.mempool
    last = [mp.clock()]

    def on_propose(block):
        now = mp.clock()
        first = min(mp._inflight[tx] for tx in block.transactions if tx in mp._inflight)
        waits.append((now - first, now - last[0]))
        last[0] = now
        mp.observe_proposed(block)

    nd.process.on_propose = on_propose
    return waits


def delivered_txs(nd):
    return [tx for v in list(nd.delivered) for tx in v.block.transactions]


@pytest.fixture
def keys_path(tmp_path):
    path = tmp_path / "keys.json"
    node_mod.main(["keygen", "--n", str(N), "--threshold", "2", "--out", str(path)])
    return path


def test_four_nodes_stage_nothing_and_a_block_waits_under_two_rounds(tmp_path, keys_path):
    nodes = cluster(tmp_path, keys_path)
    waits = [watch_waits(nd) for nd in nodes]
    deepest = [0] * N
    offered = [[] for _ in range(N)]
    stop = threading.Event()

    def offer():
        # a transaction a validator every ~4 ms: several 5 ms deadline
        # blocks a round of ~25 ms and more
        k = 0
        while not stop.is_set():
            for i, nd in enumerate(nodes):
                tx = f"v{i}-tx{k:05d}".encode()
                if nd.submit(Block((tx,))).accepted:
                    offered[i].append(tx)
                deepest[i] = max(deepest[i], len(nd.process.blocks_to_propose))
            k += 1
            time.sleep(0.004)

    try:
        for nd in nodes:
            nd.start()
        t0 = time.monotonic()
        feeder = threading.Thread(target=offer, daemon=True)
        feeder.start()
        time.sleep(1.5)
        stop.set()
        feeder.join()
        seconds = time.monotonic() - t0
        rounds = [nd.process.round for nd in nodes]
        want = {tx for txs in offered for tx in txs}
        deadline = time.time() + 30
        while time.time() < deadline and not all(
            want <= set(delivered_txs(nd)) for nd in nodes
        ):
            time.sleep(0.05)
    finally:
        stop.set()
        for nd in nodes:
            nd.stop()
    assert min(rounds) >= 8, rounds
    assert min(len(txs) for txs in offered) >= 100
    # the node loop staged nothing in front of consensus, at any time
    assert deepest == [0] * N
    assert all(not nd.process.blocks_to_propose for nd in nodes)
    for i, nd in enumerate(nodes):
        round_s = seconds / rounds[i]
        assert len(waits[i]) >= 8
        # a block's earliest transaction came after the previous vertex
        # was made, or it would have ridden that one: it waited no
        # longer than its own round took (5 ms for the two clock reads
        # either side of the cut), not a queue of rounds
        assert all(wait < since_last + 0.005 for wait, since_last in waits[i]), waits[i]
        typical = sorted(wait for wait, _ in waits[i])[len(waits[i]) // 2]
        assert typical < 2 * round_s, (typical, round_s)
        # several deadline blocks' worth in one vertex
        assert max(len(v.block.transactions) for v in nd.delivered if v.id.source == i) >= 2
    # nothing lost, nothing twice, one order at all four
    logs = [delivered_txs(nd) for nd in nodes]
    for log in logs:
        assert len(log) == len(set(log))
        assert want <= set(log)
    orders = [[(v.id.round, v.id.source, v.digest()) for v in nd.delivered] for nd in nodes]
    k = min(len(o) for o in orders)
    assert k > 0 and all(o[:k] == orders[0][:k] for o in orders)


def test_a_restart_delivers_checkpointed_pending_transactions_exactly_once(tmp_path, keys_path):
    """What a stopped validator held — a staged block from before the
    restart, and transactions still in its pool — rides its first
    vertices after the restart, the staged block first, each once."""
    first = cluster(tmp_path, keys_path)
    try:
        # never started: what it accepted is still pending when it stops
        pooled = [f"pending-{k}".encode() for k in range(5)]
        for tx in pooled:
            assert first[0].submit(Block((tx,))).accepted == 1
        first[0].process.blocks_to_propose.append(Block((b"staged-before",)))
    finally:
        for nd in first:
            nd.stop()
    nodes = cluster(tmp_path, keys_path)
    restored = nodes[0]
    assert [b.transactions for b in restored.process.blocks_to_propose] == [(b"staged-before",)]
    assert {e.tx for e in restored.mempool.pool.pending()} == set(pooled)
    # acknowledged before the restart: a client's retry is a duplicate
    assert restored.submit(Block((pooled[0],))).deduped == 1
    want = set(pooled) | {b"staged-before"}
    try:
        for nd in nodes:
            nd.start()
        deadline = time.time() + 30
        while time.time() < deadline and not all(
            want <= set(delivered_txs(nd)) for nd in nodes
        ):
            time.sleep(0.05)
    finally:
        for nd in nodes:
            nd.stop()
    for nd in nodes:
        log = delivered_txs(nd)
        assert sorted(log) == sorted(want), log
    own = [v for v in restored.delivered if v.id.source == 0 and v.block.transactions]
    assert [v.block.transactions for v in own] == [(b"staged-before",), tuple(pooled)]
    assert own[0].id.round < own[1].id.round
    assert not restored.process.blocks_to_propose and len(restored.mempool.pool) == 0
