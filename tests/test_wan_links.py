"""A delay on every link (ISSUE 28): ``WanFault``'s per-link windows
against a hand-written matrix, the old uniform window unchanged, one
delay thread a transport however many messages wait, and the cluster's
layout writing a verifier, its address and a region per node."""

from __future__ import annotations

import json
import random
import threading

import pytest

from dag_rider_tpu.cluster.directory import build_cluster
from dag_rider_tpu.transport.net import WanFault, _DelayQueue

#: one-way ms, one direction of each pair filled in
MATRIX = {
    "east": {"east": 0.5, "west": 31.0, "north": 55.0},
    "west": {"west": 0.5, "north": 85.0},
    "north": {"north": 0.5},
}
REGIONS = ["east", "west", "north", "east"]


def fault(index: int, **kw) -> WanFault:
    return WanFault(
        seed=9,
        region=REGIONS[index],
        peer_regions={j: r for j, r in enumerate(REGIONS) if j != index},
        one_way_ms=MATRIX,
        **kw,
    )


def test_each_link_is_delayed_by_its_regions_entry_either_direction():
    want = {(0, 1): 31.0, (0, 2): 55.0, (0, 3): 0.5, (1, 0): 31.0, (1, 2): 85.0,
            (2, 0): 55.0, (2, 1): 85.0, (2, 3): 55.0, (3, 0): 0.5}
    for (a, b), ms in want.items():
        f = fault(a)
        assert f.window_ms(b) == (ms, ms)
        assert [f(b) for _ in range(3)] == [pytest.approx(ms / 1e3)] * 3


def test_jitter_widens_each_links_window_by_its_fraction_and_draws_from_the_seed():
    f, again = fault(0, jitter=0.02), fault(0, jitter=0.02)
    assert f.window_ms(2) == (pytest.approx(53.9), pytest.approx(56.1))
    drawn = [f(peer) for peer in (1, 2, 3, 2, 1)]
    assert drawn == [again(peer) for peer in (1, 2, 3, 2, 1)]
    for peer, d in zip((1, 2, 3, 2, 1), drawn):
        lo, hi = f.window_ms(peer)
        assert lo / 1e3 <= d <= hi / 1e3
    assert len(set(drawn)) == 5  # a draw a message, not a constant a link


def test_the_uniform_window_is_the_case_of_one_link_class_and_draws_as_before():
    f = WanFault(seed=4, delay_ms=(5.0, 20.0), delay_rate=0.5, drop=0.1)
    assert f.window_ms(1) == f.window_ms(7) == (5.0, 20.0)
    rng = random.Random(4)
    want = []
    for _ in range(200):
        if rng.random() < 0.1:
            want.append(-1.0)
        elif rng.random() < 0.5:
            want.append(rng.uniform(5.0, 20.0) / 1e3)
        else:
            want.append(0.0)
    assert [f(peer % 3) for peer in range(200)] == want
    assert {-1.0, 0.0} < set(want)


def test_a_matrix_needs_the_regions_and_an_entry_for_every_link():
    with pytest.raises(ValueError, match="region"):
        WanFault(one_way_ms=MATRIX)
    with pytest.raises(ValueError, match="no delay"):
        WanFault(region="east", peer_regions={1: "south"}, one_way_ms=MATRIX)
    with pytest.raises(ValueError, match="jitter"):
        fault(0, jitter=1.0)


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self) -> float:
        return self.now


def test_one_thread_releases_every_held_message_in_the_order_they_fall_due():
    clock = FakeClock()
    batches, woke = [], threading.Event()

    def on_due(due):
        batches.append(due)
        if any(item == "wake" for _, item in due):
            woke.set()

    def delay_threads():
        return sum(1 for t in threading.enumerate() if t.name == "net-delay")

    queue = _DelayQueue(on_due, clock)
    before = delay_threads()
    rng = random.Random(1)
    delays = [rng.uniform(0.010, 0.300) for _ in range(2000)]
    for k, d in enumerate(delays):
        queue.push(d, k)
    assert delay_threads() == before + 1  # not a thread a message
    assert len(queue) == 2000 and batches == []
    clock.now += 0.150
    # a push that is the new head wakes the thread to look at the clock
    queue.push(0.0, "wake")
    assert woke.wait(5.0)
    released = [(ns, k) for due in batches for ns, k in due if k != "wake"]
    in_order = sorted((d, k) for k, d in enumerate(delays))
    assert [k for _, k in released] == [k for d, k in in_order if d <= 0.150]
    assert all(ns == pytest.approx(0.150e9) for ns, _ in released)
    # what fell due together came in one hand-over
    assert len(batches) == 1 and len(batches[0]) == len(released) + 1
    woke.clear()
    clock.now += 1.0
    queue.push(0.0, "wake")
    assert woke.wait(5.0)
    released = [k for due in batches for _, k in due if k != "wake"]
    assert released == [k for _, k in in_order]
    assert delay_threads() == before + 1
    queue.close()
    assert delay_threads() == before  # close() waits for its thread
    queue.push(0.0, "after close")
    assert len(queue) == 0 and len(batches) == 2


def _pair(send_fault=None):
    """Two transports on loopback ports, each the other's only peer."""
    from dag_rider_tpu.transport.auth import FrameAuth
    from dag_rider_tpu.transport.net import GrpcTransport

    auth = FrameAuth.derive(b"m" * 32, 2)
    b = GrpcTransport(1, "127.0.0.1:0", {}, auth=auth[1])
    a = GrpcTransport(
        0, "127.0.0.1:0", {1: f"127.0.0.1:{b.bound_port}"}, auth=auth[0],
        send_fault=send_fault,
    )
    return a, b


def _echo(k: int):
    from dag_rider_tpu.core.types import BroadcastMessage

    return BroadcastMessage(
        vertex=None, round=k, sender=0, kind="echo", origin=1, digest=b"d" * 32
    )


def test_a_delayed_send_waits_in_the_transports_one_queue():
    def delay_threads():
        return sum(1 for t in threading.enumerate() if t.name == "net-delay")

    before = delay_threads()
    a, b = _pair(WanFault(seed=1, delay_ms=(5_000.0, 5_000.0)))
    try:
        for k in range(1, 51):
            a.broadcast(_echo(k))
        assert len(a._held) == 50
        assert delay_threads() == before + 1
        assert a.metrics.snapshot()["net_wan_delays"] == 50
        assert a.metrics.snapshot().get("net_sends", 0) == 0  # none leaves early
    finally:
        a.close()
        b.close()
    assert len(a._held) == 0
    assert delay_threads() == before  # no thread outlives its transport


def test_frames_for_a_peer_that_fall_due_together_share_one_rpc_and_all_arrive():
    import time

    from dag_rider_tpu.obs import spans

    a, b = _pair(WanFault(seed=1, delay_ms=(30.0, 30.0)))
    got = []
    b.subscribe(1, got.append)
    try:
        book = spans.snapshot()
        rpcs0 = book["spans"].get("net.send", {"count": 0})["count"]
        frames0 = book["counts"].get("net.messages", 0)
        held0 = book["spans"].get("net.delay", {"count": 0})["count"]
        # the delay thread is kept from looking until all 40 are due
        with a._held._cond:
            for k in range(1, 41):
                a.broadcast(_echo(k))
            time.sleep(0.06)
        deadline = time.monotonic() + 10.0
        while len(got) < 40 and time.monotonic() < deadline:
            b.pump()
            time.sleep(0.005)
        assert sorted(m.round for m in got) == list(range(1, 41))
        assert all(m.kind == "echo" and m.sender == 0 for m in got)
        book = spans.snapshot()
        assert book["counts"]["net.messages"] - frames0 == 40
        assert book["spans"]["net.send"]["count"] - rpcs0 == 1
        assert book["spans"]["net.delay"]["count"] - held0 == 40
        assert a.metrics.snapshot()["net_sends"] == 40
        # a frame due alone goes as it always did
        a.broadcast(_echo(99))
        while len(got) < 41 and time.monotonic() < deadline:
            b.pump()
            time.sleep(0.005)
        assert got[-1].round == 99
        assert spans.snapshot()["spans"]["net.send"]["count"] - rpcs0 == 2
    finally:
        a.close()
        b.close()


# -- the layout -------------------------------------------------------------


def test_build_cluster_writes_a_verifier_an_address_and_a_region_per_node(tmp_path):
    spec = build_cluster(
        str(tmp_path / "c"), 4, seed=2,
        wan={"seed": 2, "one_way_ms": MATRIX, "jitter": 0.02},
        regions=REGIONS,
        verifiers={0: {"kind": "remote", "address": "unix:/tmp/v0.sock"},
                   2: {"kind": "none"}},
    )
    nodes = []
    for nf in spec.nodes:
        with open(nf.config) as fh:
            cfg = json.load(fh)
        assert cfg["files"]["span_book"] == nf.span_book
        nodes.append(cfg["node"])
    assert [c["verifier"] for c in nodes] == ["remote", "cpu", "none", "cpu"]
    assert nodes[0]["verifier_address"] == "unix:/tmp/v0.sock"
    assert all("verifier_address" not in c for c in nodes[1:])
    for c in nodes:
        assert c["wan"]["regions"] == REGIONS and c["wan"]["one_way_ms"] == MATRIX
        assert c["wan"]["jitter"] == 0.02 and c["wan"]["seed"] == 2


def test_build_cluster_refuses_a_layout_it_cannot_write(tmp_path):
    with pytest.raises(ValueError, match="regions for n=4"):
        build_cluster(str(tmp_path / "a"), 4, wan={"one_way_ms": MATRIX}, regions=REGIONS[:3])
    with pytest.raises(ValueError, match="one_way_ms"):
        build_cluster(str(tmp_path / "b"), 4, wan={"delay_ms": [1, 2]}, regions=REGIONS)
    with pytest.raises(ValueError, match="address"):
        build_cluster(str(tmp_path / "c"), 4, verifiers={1: {"kind": "remote"}})
    with pytest.raises(ValueError, match="node 4"):
        build_cluster(str(tmp_path / "d"), 4, verifiers={4: {"kind": "cpu"}})


def test_a_node_reads_its_links_from_the_layouts_wan_keys(tmp_path):
    from dag_rider_tpu.node import Node

    spec = build_cluster(
        str(tmp_path / "c"), 4, seed=2,
        wan={"seed": 2, "one_way_ms": MATRIX, "jitter": 0.0}, regions=REGIONS,
    )
    with open(spec.nodes[1].config) as fh:
        node = Node(json.load(fh)["node"])
    try:
        links = node.net._send_fault
        assert [links.window_ms(p)[0] for p in (0, 2, 3)] == [31.0, 85.0, 31.0]
    finally:
        node.net.close()
    # the old keys keep their meaning: one window whatever the peer
    spec = build_cluster(str(tmp_path / "d"), 4, seed=2, wan={"delay_ms": [5, 20]})
    with open(spec.nodes[1].config) as fh:
        node = Node(json.load(fh)["node"])
    try:
        assert node.net._send_fault.window_ms(0) == node.net._send_fault.window_ms(3) == (5.0, 20.0)
    finally:
        node.net.close()
