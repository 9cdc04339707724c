"""Scalar-vs-vector consensus pump equivalence (round 12).

The vectorized pump is an EXECUTION STRATEGY, not a protocol change: for
every schedule the scalar path can see, the vector path must produce
byte-identical per-process delivery sequences (same vertex ids, same
digests, same order). This suite pins that contract three ways:

- unit: the batch codec roundtrips, and every numpy host twin in
  ops/dag_kernels.py agrees with its jitted sibling on random inputs
  (the twins are what the vector drain/ordering actually call on the
  1-core host; the jitted forms remain the device reference);
- transport: pump_grouped preserves per-destination FIFO, treats
  control messages as barriers, and falls back per-message when no
  batch handler is registered;
- end-to-end fuzz: paired simulations (identical seeds, transports,
  adversaries — only cfg.pump differs) across committee sizes, Byzantine
  scenarios from the round-11 suite, and the Bracha RBC stage, compared
  delivery-log to delivery-log.
"""

from __future__ import annotations

import numpy as np
import pytest

from dag_rider_tpu.config import Config
from dag_rider_tpu.consensus import Process, Simulation
from dag_rider_tpu.consensus.adversary import ByzantineProcess, make_behavior
from dag_rider_tpu.core import codec
from dag_rider_tpu.core.types import Block, BroadcastMessage, Vertex, VertexID
from dag_rider_tpu.transport import InMemoryTransport
from dag_rider_tpu.transport.faults import FaultPlan, FaultyTransport

# ---------------------------------------------------------------------------
# knob plumbing
# ---------------------------------------------------------------------------


def test_pump_defaults_to_vector(monkeypatch):
    monkeypatch.delenv("DAGRIDER_PUMP", raising=False)
    assert Config(n=4).pump == "vector"


def test_pump_env_resolution(monkeypatch):
    monkeypatch.setenv("DAGRIDER_PUMP", "scalar")
    assert Config(n=4).pump == "scalar"


def test_pump_explicit_beats_env(monkeypatch):
    monkeypatch.setenv("DAGRIDER_PUMP", "scalar")
    assert Config(n=4, pump="vector").pump == "vector"


def test_pump_validation():
    with pytest.raises(ValueError):
        Config(n=4, pump="simd")


# ---------------------------------------------------------------------------
# batch codec
# ---------------------------------------------------------------------------


def _val(sender: int, rnd: int) -> BroadcastMessage:
    v = Vertex(
        id=VertexID(rnd, sender),
        block=Block((f"b{sender}-{rnd}".encode(),)),
        strong_edges=tuple(VertexID(rnd - 1, s) for s in range(3)),
        weak_edges=(VertexID(max(0, rnd - 2), 3),) if rnd > 1 else (),
    )
    return BroadcastMessage(vertex=v, round=rnd, sender=sender)


def test_encode_decode_many_roundtrip():
    msgs = [_val(s, r) for r in (1, 2, 3) for s in range(4)]
    # a control message in the middle: the batch frame is kind-agnostic
    msgs.insert(
        3,
        BroadcastMessage(
            vertex=None,
            round=2,
            sender=1,
            kind="echo",
            origin=0,
            digest=b"\x00" * 32,
        ),
    )
    out = codec.decode_many(codec.encode_many(msgs))
    assert len(out) == len(msgs)
    for a, b in zip(msgs, out):
        assert (a.kind, a.round, a.sender, a.origin, a.digest) == (
            b.kind,
            b.round,
            b.sender,
            b.origin,
            b.digest,
        )
        if a.vertex is None:
            assert b.vertex is None
        else:
            assert a.vertex.id == b.vertex.id
            assert a.vertex.digest() == b.vertex.digest()


def test_encode_decode_many_empty():
    assert codec.decode_many(codec.encode_many([])) == []


def test_decode_many_rejects_trailing_bytes():
    blob = codec.encode_many([_val(0, 1)])
    with pytest.raises(ValueError):
        codec.decode_many(blob + b"x")


def test_decode_many_rejects_bad_magic():
    with pytest.raises(ValueError):
        codec.decode_many(b"XXXX\x00\x00\x00\x00")


# ---------------------------------------------------------------------------
# numpy host twins == jitted kernels
# ---------------------------------------------------------------------------


def test_host_twins_match_jitted_kernels():
    from dag_rider_tpu.ops import dag_kernels as dk

    rng = np.random.default_rng(7)
    n, quorum = 8, 6
    for k in (1, 2, 4):
        stack = rng.random((k, n, n)) < 0.3
        jit_reach = np.asarray(dk.reach_chain(stack))
        np.testing.assert_array_equal(jit_reach, dk.reach_chain_np(stack))
        for hi in range(n):
            np.testing.assert_array_equal(
                np.asarray(dk.leader_reach(stack, hi)),
                Process._reach_from(stack, hi),
            )
    for _ in range(8):
        row = rng.random(n) < 0.7
        assert bool(
            dk.round_complete(row, quorum=quorum)
        ) == dk.round_complete_np(row, quorum=quorum)
        sp = rng.random((5, n)) < 0.6
        np.testing.assert_array_equal(
            np.asarray(dk.strong_edge_quorum(sp, quorum=quorum)),
            dk.strong_edge_quorum_np(sp, quorum=quorum),
        )
        ex = rng.random((6, n)) < 0.5
        wp = rng.random((5, 6, n)) < 0.1
        np.testing.assert_array_equal(
            np.asarray(dk.admission_mask(sp, ex[2], wp, ex)),
            dk.admission_mask_np(sp, ex[2], wp, ex),
        )


@pytest.mark.parametrize("pump", ["scalar", "vector"])
def test_the_pump_runs_a_committee_without_importing_jax(pump):
    """A validator's own process holds no jax (its chip is a sidecar's):
    a committee's path through a few waves, the leader chain's
    strong-path query included, must not import it."""
    import subprocess
    import sys

    script = (
        "import sys\n"
        "from dag_rider_tpu.config import Config\n"
        "from dag_rider_tpu.consensus import Simulation\n"
        "from dag_rider_tpu.core.types import VertexID\n"
        f"cfg = Config(n=4, coin='round_robin', propose_empty=True, pump={pump!r})\n"
        "sim = Simulation(cfg)\n"
        "while min(p.round for p in sim.processes) < 13:\n"
        "    sim.run(max_messages=16)\n"
        "p = sim.processes[0]\n"
        "assert p.decided_wave >= 2 and sim.deliveries[0]\n"
        "assert p._leader_path(VertexID(9, 0), VertexID(5, 1))\n"
        "assert 'jax' not in sys.modules, 'the pump imported jax'\n"
    )
    done = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, timeout=120
    )
    assert done.returncode == 0, done.stderr


# ---------------------------------------------------------------------------
# pump_grouped transport semantics
# ---------------------------------------------------------------------------


def test_pump_grouped_batches_val_runs_and_barriers_controls():
    tp = InMemoryTransport()
    events = []
    tp.subscribe(0, lambda m: events.append(("one", m.kind, m.round)))
    tp.subscribe(1, lambda m: events.append(("other", m.kind, m.round)))
    tp.subscribe_many(
        0, lambda ms: events.append(("batch", [m.round for m in ms]))
    )
    for r in (1, 2):
        tp.enqueue(0, _val(1, r))
    ctrl = BroadcastMessage(
        vertex=None, round=2, sender=1, kind="echo", origin=1, digest=b"d"
    )
    tp.enqueue(0, ctrl)
    tp.enqueue(1, _val(0, 3))  # no batch handler: per-message fallback
    tp.enqueue(0, _val(1, 4))
    assert tp.pump_grouped() == 5
    assert events == [
        ("batch", [1, 2]),  # VAL run, per-dest FIFO preserved
        ("one", "echo", 2),  # control barrier in exact queue position
        ("other", "val", 3),  # fallback path
        ("batch", [4]),
    ]


def test_subscribe_many_requires_existing_subscription():
    tp = InMemoryTransport()
    with pytest.raises(KeyError):
        tp.subscribe_many(0, lambda ms: None)


# ---------------------------------------------------------------------------
# FaultyTransport grouped-pump path (round 13 satellite)
# ---------------------------------------------------------------------------


def _run_faulty(transport, pump: str, *, flushes: int = 0):
    cfg = Config(n=4, coin="round_robin", pump=pump, propose_empty=False)
    kwargs = {"transport": transport} if transport is not None else {}
    sim = Simulation(cfg, **kwargs)
    sim.submit_blocks(per_process=8)
    sim.run(max_messages=40_000)
    for _ in range(flushes):
        transport.flush_delayed()
        sim.run(max_messages=40_000)
    sim.check_agreement()
    return _delivery_logs(sim, range(cfg.n))


def test_faulty_grouped_zero_plan_byte_identical():
    """A delay-free FaultyTransport grows the grouped-pump seam and the
    fan-out sentinel forward; under an all-zero plan the vector run is
    byte-identical to one over a bare InMemoryTransport."""
    tp = FaultyTransport(FaultPlan(seed=3))
    assert callable(getattr(tp, "pump_grouped", None))
    wrapped = _run_faulty(tp, "vector")
    plain = _run_faulty(None, "vector")
    assert any(wrapped)
    assert wrapped == plain
    # the sentinel write-through reached the inner transport
    assert tp.fanout_sentinel is True
    assert tp.inner.fanout_sentinel is True


def test_faulty_grouped_duplicate_plan_live():
    """Delay-free fault plans ride the grouped path: rolls land per
    message inside the batch wrapper, stats count them, and dedup keeps
    agreement byte-identical across processes."""
    tp = FaultyTransport(FaultPlan(duplicate=0.3, seed=5))
    assert callable(getattr(tp, "pump_grouped", None))
    logs = _run_faulty(tp, "vector")
    assert any(logs)
    assert tp.stats["duplicated"] > 0


def test_faulty_delay_plan_falls_back_byte_identical():
    """Fallback contract: a plan that can HOLD a message never grows
    pump_grouped (the Simulation's callable-probe then picks per-message
    pumping), and the vector run's delivery log equals the scalar run's
    under the same plan and seed — same rolls, same schedule, same
    bytes."""
    tp_vec = FaultyTransport(FaultPlan(delay=0.2, seed=9))
    assert getattr(tp_vec, "pump_grouped", None) is None
    vec = _run_faulty(tp_vec, "vector", flushes=8)
    assert tp_vec.stats["delayed"] > 0
    tp_sca = FaultyTransport(FaultPlan(delay=0.2, seed=9))
    sca = _run_faulty(tp_sca, "scalar", flushes=8)
    assert any(vec)
    assert vec == sca


def test_faulty_wan_topology_falls_back():
    from dag_rider_tpu.transport.faults import WanTopology

    tp = FaultyTransport(
        FaultPlan(seed=1), topology=WanTopology.regions(4)
    )
    assert getattr(tp, "pump_grouped", None) is None


# ---------------------------------------------------------------------------
# end-to-end equivalence fuzz
# ---------------------------------------------------------------------------


def _delivery_logs(sim: Simulation, honest) -> list:
    return [
        [(v.id, v.digest()) for v in sim.deliveries[i]] for i in honest
    ]


def _run_clean(n: int, seed: int, pump: str, *, rbc: bool, target: int):
    cfg = Config(
        n=n, coin="round_robin", propose_empty=True, gc_depth=24, pump=pump
    )
    sim = Simulation(cfg, rbc=rbc)
    for i in range(n):
        for k in range(2):
            sim.processes[i].submit(
                Block((f"s{seed}-p{i}-b{k}".encode().ljust(32, b"."),))
            )
    chunk = n * (n - 1) * (2 * n if rbc else 1)
    for _ in range(100 * target):
        sim.run(max_messages=chunk)
        if max(p.round for p in sim.processes) >= target:
            break
    else:
        raise AssertionError("failed to reach target round")
    sim.check_agreement()
    return _delivery_logs(sim, range(n))


@pytest.mark.parametrize("n", [4, 16, 32])
@pytest.mark.parametrize("seed", [0, 1])
def test_clean_equivalence(n, seed):
    target = 12 if n == 4 else 8
    scalar = _run_clean(n, seed, "scalar", rbc=False, target=target)
    vector = _run_clean(n, seed, "vector", rbc=False, target=target)
    assert any(scalar)  # non-vacuous: something was delivered
    assert scalar == vector


@pytest.mark.parametrize("seed", [0, 1])
def test_clean_equivalence_under_rbc(seed):
    scalar = _run_clean(4, seed, "scalar", rbc=True, target=12)
    vector = _run_clean(4, seed, "vector", rbc=True, target=12)
    assert any(scalar)
    assert scalar == vector


def _run_adversary(
    n: int, seed: int, pump: str, adversary: str, *, rbc: bool, cycles: int
):
    """Mirror of scenarios.run_scenario's core loop with cfg.pump pinned:
    seeded behaviors at the low f indices, a seeded fault transport, a
    fixed virtual-time schedule — only the pump flavor differs between
    the paired calls, so the delivery logs must match byte for byte."""
    cfg = Config(
        n=n,
        propose_empty=True,
        pump=pump,
        sync_request_cooldown_s=0.0,
        sync_serve_cooldown_s=0.0,
    )
    byz = tuple(range(cfg.f))
    behaviors = {
        i: make_behavior(adversary, seed=seed + 1000 + i) for i in byz
    }
    tp = FaultyTransport(FaultPlan(seed=seed))

    def factory(pcfg, i, ptp, **kwargs):
        if i in behaviors:
            return ByzantineProcess(
                pcfg, i, ptp, behavior=behaviors[i], **kwargs
            )
        return Process(pcfg, i, ptp, **kwargs)

    sim = Simulation(cfg, transport=tp, rbc=rbc, process_factory=factory)
    honest = [i for i in range(n) if i not in set(byz)]
    for i in honest:
        for k in range(2):
            sim.processes[i].submit(
                Block((f"s{seed}-p{i}-b{k}".encode().ljust(32, b"."),))
            )
    chunk = 2 * n * n * (2 * n if rbc else 1)
    for _ in range(cycles):
        if sim.run(max_messages=chunk) == 0:
            for _ in range(cfg.sync_patience or 4):
                sim.run(max_messages=chunk)
        tp.advance(0.01)
    for _ in range(6):
        tp.flush_delayed()
        sim.run(max_messages=2 * chunk)
    return _delivery_logs(sim, honest)


@pytest.mark.parametrize(
    "adversary",
    [
        "equivocate",
        "withhold",
        "invalid_edges",
        "garbage_coin",
        "equivocate_split",
    ],
)
@pytest.mark.parametrize("seed", [0, 1])
def test_adversary_equivalence(adversary, seed):
    scalar = _run_adversary(
        4, seed, "scalar", adversary, rbc=False, cycles=36
    )
    vector = _run_adversary(
        4, seed, "vector", adversary, rbc=False, cycles=36
    )
    assert any(scalar)
    assert scalar == vector


def test_adversary_equivalence_under_rbc():
    scalar = _run_adversary(
        4, 0, "scalar", "equivocate", rbc=True, cycles=36
    )
    vector = _run_adversary(
        4, 0, "vector", "equivocate", rbc=True, cycles=36
    )
    assert any(scalar)
    assert scalar == vector


# ---------------------------------------------------------------------------
# the lockstep driver's merged dispatch under the round-batched pump
# ---------------------------------------------------------------------------


class _CountingShared:
    """One host verifier for every view that counts how it is called:
    ``merged`` by the lockstep driver (one dedup'd batch a pump cycle),
    ``per_view`` by a view's own ``_drain_verify``."""

    def __init__(self, registry):
        from dag_rider_tpu.verifier.cpu import CPUVerifier

        self.inner = CPUVerifier(registry)
        self.registry = registry
        self.merged: list = []  # sizes
        self.per_view = 0

    def verify_batch(self, vertices):
        self.per_view += 1
        return self.inner.verify_batch(vertices)

    def verify_rounds(self, rounds):
        self.merged.extend(len(r) for r in rounds)
        return [self.inner.verify_batch(r) for r in rounds]


class _TwoCallShared(_CountingShared):
    """The same with the device verifier's two calls, so that
    ``Simulation.run`` takes its pipelined branch (a dispatch window,
    ``defer_delivery``, the ordering walks overlapped with the tail)."""

    def dispatch_batch(self, vertices):
        self.merged.append(len(vertices))
        return list(vertices)

    def resolve_batch(self, handle):
        return self.inner.verify_batch(handle)


def _cell_shape(n: int, pump, shared_cls):
    """``committee256``'s stack at size n: signed vertices, one shared
    verifier with dedup, the threshold-BLS coin, ``propose_empty``,
    ``gc_depth`` 24, a mempool in front of every view."""
    from dag_rider_tpu.config import MempoolConfig
    from dag_rider_tpu.consensus.scenarios import coin_factory
    from dag_rider_tpu.verifier.base import KeyRegistry, VertexSigner

    cfg = Config(
        n=n, coin="threshold_bls", propose_empty=True, gc_depth=24,
        wave_length=4, pump=pump,
    )
    registry, seeds = KeyRegistry.generate(n)
    shared = shared_cls(registry)
    signers = [VertexSigner(s) for s in seeds]
    sim = Simulation(
        cfg,
        verifier_factory=lambda i: shared,
        signer_factory=lambda i: signers[i],
        coin_factory=coin_factory("threshold_bls", n, cfg.f),
    )
    ticks = iter(range(10**9))
    mempools = sim.attach_mempools(
        MempoolConfig(), clock=lambda: 0.001 * next(ticks)
    )
    return sim, shared, mempools


def _drive_cell_shape(sim, mempools, rounds: int):
    """The committee driver's cycle — submit, ``build_blocks``,
    ``run(n * n)`` — until every view has passed ``rounds``; yields
    after each cycle."""
    n = sim.cfg.n
    for cycle in range(40 * rounds):
        for i, (p, mp) in enumerate(zip(sim.processes, mempools)):
            mp.submit((f"c{cycle}-v{i}".encode().ljust(32, b"."),), client=f"c{i}")
            for b in mp.build_blocks(force=True, staged=len(p.blocks_to_propose)):
                p.submit(b)
        sim.run(max_messages=n * n)
        yield cycle
        if min(p.round for p in sim.processes) >= rounds:
            return
    raise AssertionError("failed to reach the target round")


@pytest.fixture
def registered_default(monkeypatch):
    """``pump=None`` means the registry's default, whatever lane runs us."""
    monkeypatch.delenv("DAGRIDER_PUMP", raising=False)


@pytest.mark.parametrize("shared_cls", [_CountingShared, _TwoCallShared])
def test_round_reaches_the_shared_verifier_as_one_merged_batch(
    shared_cls, registered_default
):
    """Under the round-batched pump a delivered vertex waits in the
    view's inbox; the driver has to run the inbox checks before it
    gathers the cycle's batch, or the merged dispatch is empty and each
    of the n views verifies its own batch from ``step()``."""
    n = 8
    sim, shared, mempools = _cell_shape(n, None, shared_cls)
    assert all(p._vector for p in sim.processes)
    seen = 0
    for _ in _drive_cell_shape(sim, mempools, rounds=9):
        calls = shared.merged[seen:]
        seen = len(shared.merged)
        # the round's n unique vertices and the few the spare messages
        # carry, in one batch or two
        assert 1 <= len(calls) <= 2 and sum(calls) <= 2 * n, calls
        assert all(not p._inbox and not p._pending_verify for p in sim.processes)
    assert shared.per_view == 0  # no view ever verified for itself
    assert sum(shared.merged) >= 8 * n
    sim.check_agreement()
    assert min(len(d) for d in sim.deliveries) > n  # a wave committed


@pytest.mark.parametrize(
    "n, shared_cls, pump",
    [
        (8, _CountingShared, "vector"),
        (8, _TwoCallShared, "vector"),
        (16, _TwoCallShared, None),  # the default, at the larger size
    ],
)
def test_cell_shape_delivers_the_scalar_pumps_log(
    n, shared_cls, pump, registered_default
):
    """The committee cell's shape — mempools, the threshold-BLS coin and
    one shared verifier, which the fuzz above does not pair — delivers
    the same log at every view whichever pump runs it."""
    logs = {}
    for side in ("scalar", pump):
        sim, shared, mempools = _cell_shape(n, side, shared_cls)
        assert all(p._vector == (side != "scalar") for p in sim.processes)
        for _ in _drive_cell_shape(sim, mempools, rounds=9):
            pass
        assert shared.per_view == 0
        logs[side] = (_delivery_logs(sim, range(n)), list(shared.merged))
    assert any(logs["scalar"][0])
    assert min(len(log) for log in logs["scalar"][0]) > n
    assert logs[pump][0] == logs["scalar"][0]
    # and asked the shared verifier the same questions, call for call
    assert logs[pump][1] == logs["scalar"][1]
