"""A committee with f of its 3f+1 members crashed (ISSUE 32): what the
transport, the wave commit and the sync rule do when a peer is dead —
the program's side of the cell ``narwhal10-wan.poisson512-crash3``."""

import time

import pytest

from dag_rider_tpu import Config
from dag_rider_tpu.consensus import Simulation
from dag_rider_tpu.core.types import BroadcastMessage, Vertex, VertexID
from dag_rider_tpu.obs import spans
from dag_rider_tpu.transport.net import GrpcTransport


def _book():
    snap = spans.snapshot()
    return snap["counts"], snap["spans"]


def _run_with_validator_3_dead(pump: str):
    sim = Simulation(Config(n=4, coin="round_robin", pump=pump))
    sim.submit_blocks(per_process=3)
    for p in sim.processes[:3]:
        p.start()
    sim.transport.pump(6000)
    return sim


@pytest.mark.parametrize("pump", ("scalar", "vector"))
def test_a_dead_leaders_wave_commits_nothing_and_a_later_chain_closes_it(pump):
    """Round-robin names validator 3 the leader of every fourth wave; it
    never started, so that wave is skipped at every live process and the
    next commit closes two waves. The books say so, and the order is the
    scalar oracle's."""
    counts0, spans0 = _book()
    sim = _run_with_validator_3_dead(pump)
    counts1, spans1 = _book()
    live = sim.processes[:3]
    skipped = sum(p.metrics.counters.get("waves_skipped", 0) for p in live)
    decided = sum(p.metrics.counters["waves_decided"] for p in live)
    assert skipped >= 3 and decided >= 3 * 3
    assert counts1["pump.wave_skip"] - counts0.get("pump.wave_skip", 0) == skipped
    assert counts1["pump.wave_commit"] - counts0.get("pump.wave_commit", 0) == decided
    chain0 = spans0.get("pump.chain_waves", {"count": 0, "total_ns": 0})
    chain1 = spans1["pump.chain_waves"]
    assert chain1["count"] - chain0["count"] == decided
    # every wave up to the last decided one was closed by exactly one commit
    assert chain1["total_ns"] - chain0["total_ns"] == sum(p.decided_wave for p in live)
    assert chain1["max_ns"] >= 2
    oracle = _run_with_validator_3_dead("scalar")
    for i in range(3):
        mine, want = sim.delivered_ids(i), oracle.delivered_ids(i)
        k = min(len(mine), len(want))
        assert k > 20 and mine[:k] == want[:k]
    assert not any(vid.source == 3 and vid.round > 0 for vid in sim.delivered_ids(0))


def _dead_peer_pair(**kw):
    victim = GrpcTransport(1, "127.0.0.1:0", {})
    addr = f"127.0.0.1:{victim.bound_port}"
    victim.subscribe(1, lambda m: None)
    victim.close()  # the peer starts dead
    return addr, GrpcTransport(0, "127.0.0.1:0", {1: addr}, rpc_timeout_s=0.3, **kw)


def _until(cond, bound_s, tick=None):
    deadline = time.time() + bound_s
    while time.time() < deadline and not cond():
        if tick is not None:
            tick()
        time.sleep(0.02)
    return cond()


def test_a_peer_held_down_costs_a_probe_not_a_chain_and_is_found_again_on_its_address():
    addr, t0 = _dead_peer_pair(retries=2, retry_backoff_s=0.01)
    msg = BroadcastMessage(
        vertex=Vertex(id=VertexID(1, 0), strong_edges=(VertexID(0, 1),)), round=1, sender=0
    )
    counters = t0.metrics.counters
    try:
        assert _until(lambda: t0.peer_status() == {1: "down"}, 15, lambda: t0.broadcast(msg))
        assert counters["net_peer_down"] == 1
        time.sleep(0.3)  # what was under way when it tripped runs out
        sends, retries, errors = (
            counters["net_sends"], counters["net_retries"], counters["net_send_errors"]
        )
        dropped = counters.get("net_down_peer_drops", 0)
        t_burst = time.monotonic()
        for _ in range(200):
            t0.broadcast(msg)
        burst_s = time.monotonic() - t_burst
        time.sleep(0.5)
        probes = counters["net_sends"] - sends
        # one probe a second at most, each a single attempt with no chain behind it
        assert probes <= burst_s + 0.5 + 1
        assert counters["net_retries"] == retries
        assert counters["net_send_errors"] - errors <= probes
        assert counters["net_down_peer_drops"] - dropped >= 200 - probes
        assert t0.peer_status() == {1: "down"} and counters["net_peer_down"] == 1

        revived = GrpcTransport(1, addr, {})
        if revived.bound_port == 0:
            revived.close()
            pytest.skip("ephemeral port reused by another process")
        got = []
        try:
            revived.subscribe(1, got.append)
            assert _until(lambda: t0.peer_status() == {1: "up"}, 15, lambda: t0.broadcast(msg))
            assert counters["net_peer_recovered"] == 1
            # the shield is off: every frame goes out again
            sends = counters["net_sends"]
            for _ in range(20):
                t0.broadcast(msg)
            assert counters["net_sends"] - sends == 20
            assert _until(lambda: revived.pump() >= 0 and len(got) >= 20, 10)
        finally:
            revived.close()
    finally:
        t0.close()


def test_the_books_name_what_a_dead_peer_cost():
    counts0, _ = _book()
    addr, t0 = _dead_peer_pair(retries=2, retry_backoff_s=0.01)
    msg = BroadcastMessage(
        vertex=Vertex(id=VertexID(1, 0), strong_edges=(VertexID(0, 1),)), round=1, sender=0
    )
    try:
        assert _until(lambda: t0.peer_status() == {1: "down"}, 15, lambda: t0.broadcast(msg))
        time.sleep(1.1)
        t0.broadcast(msg)  # a probe is due: it goes to a sender
        time.sleep(0.3)
    finally:
        t0.close()
    counts1, _ = _book()
    grew = {k: counts1.get(k, 0) - counts0.get(k, 0) for k in counts1}
    assert grew["net.peer_down"] == 1
    assert grew["net.retry"] >= 2 * 3  # three chains of two retries tripped it
    assert 1 <= grew["net.to_down_peer"] < grew["net.messages"]
