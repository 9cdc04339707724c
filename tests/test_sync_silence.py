"""``Config.sync_silence_rounds`` (ISSUE 28): a node over real sockets
steps every ~2 ms, so ``sync_patience`` passes of silence are every
WAN round's ordinary pause; a sync request then also waits for a
silence as long as the process's own recent round time."""

import pytest

from dag_rider_tpu.config import Config
from dag_rider_tpu.consensus import process as process_mod
from dag_rider_tpu.consensus.process import Process
from dag_rider_tpu.core.types import Block
from dag_rider_tpu.transport.memory import InMemoryTransport


class Clock:
    def __init__(self):
        self.now = 1_000.0

    def monotonic(self) -> float:
        return self.now


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(process_mod, "_time", c)
    return c


def stuck_process(silence_rounds: float) -> Process:
    """Process 0 of 4 with a block to propose and a round it cannot
    complete alone: waiting, with nothing arriving."""
    cfg = Config(
        n=4, coin="round_robin", propose_empty=False, sync_patience=3,
        sync_request_cooldown_s=0.0, sync_silence_rounds=silence_rounds,
    )
    p = Process(cfg, 0, InMemoryTransport())
    p.defer_steps = True
    p.submit(Block((b"tx",)))
    p.submit(Block((b"tx2",)))
    p.start()
    p.step()
    assert p.round == 1 and p.blocks_to_propose
    return p


def requests(p: Process) -> int:
    return p.metrics.counters.get("sync_requested", 0)


def test_passes_alone_count_where_the_rule_is_off(clock):
    p = stuck_process(0.0)
    for _ in range(3):
        p.step()
    assert requests(p) == 1


def test_a_process_that_knows_no_pace_yet_waits_a_seconds_silence(clock):
    p = stuck_process(1.0)  # one round advanced: no round time yet
    assert p._round_s is None
    for _ in range(100):
        clock.now += 0.002
        p.step()
    assert requests(p) == 0
    clock.now += 0.9
    p.step()
    assert requests(p) == 1


def test_a_request_waits_for_a_silence_of_the_process_own_round_time(clock):
    p = stuck_process(1.0)
    p._round_s = 0.400  # it has been advancing a round every 400 ms
    p.metrics.inc("msgs_received")  # traffic reached it just now
    p.step()
    for _ in range(50):  # 50 passes at 2 ms: 100 ms of an ordinary pause
        clock.now += 0.002
        p.step()
    assert requests(p) == 0
    clock.now += 0.250  # 350 ms: still inside one round time
    p.step()
    assert requests(p) == 0
    clock.now += 0.060  # 410 ms of silence
    p.step()
    assert requests(p) == 1
    # traffic again: the silence starts over
    p.metrics.inc("msgs_received")
    p.step()
    for _ in range(10):
        clock.now += 0.002
        p.step()
    assert requests(p) == 1


def test_an_echo_heard_by_the_reliable_broadcast_stage_is_no_silence(clock):
    from dag_rider_tpu.transport.rbc import RbcTransport

    cfg = Config(
        n=4, coin="round_robin", propose_empty=False, sync_patience=3,
        sync_request_cooldown_s=0.0, sync_silence_rounds=1.0,
    )
    rbc = RbcTransport(InMemoryTransport(), 0, cfg.n, cfg.f)
    p = Process(cfg, 0, rbc)
    p.defer_steps = True
    p.submit(Block((b"tx",)))
    p.submit(Block((b"tx2",)))
    p.start()
    p.step()
    p._round_s = 0.400
    for _ in range(300):  # 600 ms in which only echoes arrive
        clock.now += 0.002
        rbc.last_frame_at = clock.now
        p.step()
    assert requests(p) == 0
    clock.now += 0.5  # and then nothing at all
    p.step()
    assert requests(p) == 1


def test_a_process_that_hears_its_peers_but_does_not_advance_is_behind(clock):
    p = stuck_process(1.0)
    p._round_s = 0.100
    for _ in range(190):  # 380 ms of traffic and no round advanced
        clock.now += 0.002
        p.metrics.inc("msgs_received")
        p.step()
        p.step()  # a pass in which nothing new arrived
        p.step()
        p.step()
    assert requests(p) == 0
    for _ in range(20):  # past four silences of 100 ms
        clock.now += 0.002
        p.metrics.inc("msgs_received")
        for _ in range(4):
            p.step()
    assert requests(p) >= 1


def test_the_round_time_follows_the_process_own_pace(clock):
    cfg = Config(n=4, coin="round_robin", sync_silence_rounds=1.0)
    p = Process(cfg, 0, InMemoryTransport())
    assert p._round_s is None
    p.round = 1
    clock.now += 3.0  # round 1 began when the process was made: no pace
    p._note_round_time()
    assert p._round_s is None
    p.round = 2
    clock.now += 0.5
    p._note_round_time()
    assert p._round_s == pytest.approx(0.5)
    p.round = 3
    clock.now += 0.1
    p._note_round_time()
    assert p._round_s == pytest.approx(0.75 * 0.5 + 0.25 * 0.1)


def test_a_node_over_sockets_turns_the_rule_on_and_the_simulator_does_not(tmp_path):
    import json

    from dag_rider_tpu.cluster.directory import build_cluster
    from dag_rider_tpu.node import Node

    assert Config(n=4).sync_silence_rounds == 0.0
    spec = build_cluster(str(tmp_path / "c"), 4, seed=1)
    with open(spec.nodes[0].config) as fh:
        node = Node(json.load(fh)["node"])
    try:
        assert node.ccfg.sync_silence_rounds == 2.0
    finally:
        node.net.close()
