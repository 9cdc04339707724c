"""Verifier chaos suite (round-9 tentpole acceptance).

The containment machinery (VerifierPipeline._contain — the one window —
and the PrepEngine block-pool boundary) is tested against the faults it claims to absorb, injected by
verifier/faults.py at every seam the round-7 placement hooks expose:

- faults OFF (an armed injector whose plan never fires) must be
  byte-identical to never arming — the structural no-silent-fallback
  check, same discipline as test_prep.py's;
- an injected prep/dispatch/resolve fault must poison exactly one
  window: salvage the in-flight chunks, re-arm the staging ring,
  quarantine the failing chunk — and the full mask must still equal the
  CPU oracle once the fault clears (a bounded ``max_faults`` budget is
  the deterministic spelling of "the fault clears");
- unbounded faults must DRAIN, not wedge: with a clean quarantine tier
  the masks stay correct; without one the poisoned chunks fail closed to
  all-False but the caller still gets a full-length mask;
- the Simulation commit order under verify-stack chaos must equal the
  fault-free CPU order (the masks are a pure function of vertex bytes,
  so containment must be invisible downstream).

Transport-side: FaultyTransport must compose with any two-method
Transport (round-9 satellite — before, it reached into
InMemoryTransport internals) and its stats must surface in the
per-process metrics snapshot.
"""

import random

import pytest

from test_pipeline import N, _random_rounds, _signed_pool

from dag_rider_tpu.core.types import BroadcastMessage, Vertex, VertexID
from dag_rider_tpu.transport.base import Transport
from dag_rider_tpu.transport.faults import FaultPlan, FaultyTransport
from dag_rider_tpu.verifier.base import KeyRegistry, VertexSigner
from dag_rider_tpu.verifier.cpu import CPUVerifier
from dag_rider_tpu.verifier.faults import (
    VerifierFaultInjector,
    VerifierFaultPlan,
)
from dag_rider_tpu.verifier.pipeline import VerifierPipeline
from dag_rider_tpu.verifier.tpu import TPUVerifier


@pytest.fixture(scope="module")
def keys():
    return KeyRegistry.generate(N)


# -- faults off: arming is invisible ----------------------------------


def test_faults_off_is_byte_identical(keys):
    """An armed injector whose plan never fires must not change a single
    mask bit or gauge, and disarm() must restore the class seams."""
    reg, _ = keys
    cpu = CPUVerifier(reg)
    rng = random.Random(901)
    pool = _signed_pool(keys, 48, seed=901)
    rounds = _random_rounds(pool, rng)
    want = [cpu.verify_batch(r) for r in rounds]
    assert any(not all(m) for m in want if m), "no corruption landed"

    v = TPUVerifier(reg)
    pipe = VerifierPipeline(v, depth=2, fixed_bucket=16, warmup=False)
    inj = VerifierFaultInjector(VerifierFaultPlan())  # every p = 0.0
    inj.arm(v)
    try:
        assert pipe.verify_rounds(rounds) == want
    finally:
        inj.disarm()
    assert inj.faults_injected == 0
    assert all(c == 0 for c in inj.stats.values())
    rs = pipe.resilience_stats()
    assert rs["poisoned_windows"] == rs["quarantined"] == 0
    assert rs["quarantine_rejected"] == 0
    # disarm really popped the instance shadows — class path is back
    assert "_prep_block" not in v.__dict__
    assert "dispatch_prepped" not in v.__dict__
    assert "resolve_batch" not in v.__dict__
    assert pipe.verify_rounds(rounds) == want


# -- bounded faults: contained, then byte-identical --------------------


@pytest.mark.parametrize(
    "kind", ["prep_raise", "dispatch_raise", "resolve_raise"]
)
def test_pipeline_contains_fault_and_recovers(keys, kind):
    """One injected fault per seam kind: the window is poisoned exactly
    once, the failing chunk quarantined, and the concatenated mask still
    equals the CPU oracle — no valid vertex is rejected once the fault
    clears (max_faults=1), and the window is clean for the next run."""
    reg, _ = keys
    cpu = CPUVerifier(reg)
    pool = _signed_pool(keys, 48, seed=902)
    want = cpu.verify_batch(pool)
    assert any(not ok for ok in want), "no corruption landed"

    base = TPUVerifier(reg)
    pipe = VerifierPipeline(base, depth=2, fixed_bucket=16, warmup=False)
    plan = VerifierFaultPlan(**{kind: 1.0}, max_faults=1, seed=902)
    inj = VerifierFaultInjector(plan)
    inj.arm(base)
    try:
        assert pipe.verify_batch(pool) == want
    finally:
        inj.disarm()
    assert inj.exhausted() and inj.stats[kind] == 1
    rs = pipe.resilience_stats()
    assert rs["poisoned_windows"] == 1
    assert rs["quarantined"] >= 1
    assert rs["quarantine_rejected"] == 0
    # containment gauges surface in stats() once something was contained
    s = pipe.stats()
    assert s["poisoned_windows"] == 1 and s["quarantined"] >= 1
    # the ring was re-armed: a clean pass right after is byte-identical
    assert pipe.verify_batch(pool) == want


@pytest.mark.parametrize("sharded", [False, True])
def test_streamed_rounds_contain_faults(keys, sharded):
    """VerifierPipeline.verify_rounds contains a resolve fault over the
    single-chip and the mesh-sharded verifier alike — the one window
    lives above the round-7 placement hooks."""
    reg, _ = keys
    cpu = CPUVerifier(reg)
    rng = random.Random(903 + sharded)
    pool = _signed_pool(keys, 48, seed=903 + sharded)
    rounds = _random_rounds(pool, rng)
    want = [cpu.verify_batch(r) for r in rounds]

    if sharded:
        from dag_rider_tpu.parallel.mesh import make_mesh
        from dag_rider_tpu.parallel.sharded_verifier import ShardedTPUVerifier

        v = ShardedTPUVerifier(reg, make_mesh(8))
    else:
        v = TPUVerifier(reg)
    pipe = VerifierPipeline(v, depth=2, fixed_bucket=16, warmup=False)
    inj = VerifierFaultInjector(
        VerifierFaultPlan(resolve_raise=1.0, max_faults=2, seed=903)
    )
    inj.arm(v)
    try:
        assert pipe.verify_rounds(rounds) == want
    finally:
        inj.disarm()
    assert inj.faults_injected == 2
    rs = pipe.resilience_stats()
    assert rs["poisoned_windows"] >= 1
    assert rs["quarantined"] >= 1
    assert rs["quarantine_rejected"] == 0
    # clean pass after disarm: ring re-armed, no residue
    assert pipe.verify_rounds(rounds) == want


# -- a bare verifier raises; the stacks over it answer in full ----------


@pytest.mark.parametrize("stack", ["pipeline", "ladder"])
def test_second_chunk_fault_raises_bare_and_is_answered_by_a_stack(keys, stack):
    """A dispatch fault on the second of three chunks: a bare
    TPUVerifier has no window to contain it and raises out of
    verify_batch; under VerifierPipeline (containment) and under a
    ResilientVerifier over the bare verifier with a CPU floor (the
    ladder) the caller gets a full-length mask equal to the oracle's."""
    from dag_rider_tpu.verifier.faults import VerifierFault
    from dag_rider_tpu.verifier.resilient import ResilientVerifier

    reg, _ = keys
    pool = _signed_pool(keys, 48, seed=907)
    want = CPUVerifier(reg).verify_batch(pool)
    assert any(not ok for ok in want), "no corruption landed"

    def arm(v):
        """Shadow dispatch_prepped, as the injector does, to fail on
        its second call only."""
        calls = []
        orig = v.dispatch_prepped

        def dispatch_prepped(prepped):
            calls.append(prepped.count)
            if len(calls) == 2:
                raise VerifierFault("injected dispatch fault, chunk 2")
            return orig(prepped)

        v.dispatch_prepped = dispatch_prepped
        return calls

    bare = TPUVerifier(reg)
    bare.fixed_bucket = 16
    calls = arm(bare)
    with pytest.raises(VerifierFault):
        bare.verify_batch(pool)
    assert calls == [16, 16], "the fault was not on the second chunk"
    assert "poisoned_windows" not in bare.stats()

    v = TPUVerifier(reg)
    v.fixed_bucket = 16
    arm(v)
    if stack == "pipeline":
        top = VerifierPipeline(v, depth=2, warmup=False)
    else:
        top = ResilientVerifier(
            [v, CPUVerifier(reg)], retries=0, probe_interval_s=0.01
        )
    assert top.verify_batch(pool) == want
    rs = top.resilience_stats()
    if stack == "pipeline":
        assert rs["poisoned_windows"] == 1 and rs["quarantined"] >= 1
        assert rs["quarantine_rejected"] == 0
    else:
        assert rs["fallbacks"] == 1 and rs["fallback_tier"] == 1
        assert rs["poisoned_windows"] == rs["quarantined"] == 0


# -- unbounded faults: drain, never wedge ------------------------------


def test_unbounded_faults_drain_via_quarantine_tier(keys):
    """Every resolve raises, forever. With a clean quarantine tier every
    chunk is re-verified there, so the mask still equals the oracle —
    the pipeline drains instead of wedging."""
    reg, _ = keys
    cpu = CPUVerifier(reg)
    pool = _signed_pool(keys, 48, seed=904)
    want = cpu.verify_batch(pool)

    base = TPUVerifier(reg)
    pipe = VerifierPipeline(base, depth=2, fixed_bucket=16, warmup=False)
    pipe.quarantine_verifier = CPUVerifier(reg)
    inj = VerifierFaultInjector(VerifierFaultPlan(resolve_raise=1.0, seed=904))
    inj.arm(base)
    try:
        assert pipe.verify_batch(pool) == want
    finally:
        inj.disarm()
    rs = pipe.resilience_stats()
    assert rs["quarantined"] == 3  # ceil(48/16): every chunk quarantined
    assert rs["quarantine_rejected"] == 0
    assert pipe._pending() == 0, "window did not drain"


def test_unbounded_faults_without_tier_fail_closed_full_length(keys):
    """Same storm with NO quarantine tier: the quarantine retry hits the
    same faulting verifier and fail-closes. The caller still gets a
    full-length mask (drains, never wedges) and every bit is False —
    fail closed, never fail open."""
    reg, _ = keys
    pool = _signed_pool(keys, 48, seed=905)
    base = TPUVerifier(reg)
    pipe = VerifierPipeline(base, depth=2, fixed_bucket=16, warmup=False)
    inj = VerifierFaultInjector(VerifierFaultPlan(resolve_raise=1.0, seed=905))
    inj.arm(base)
    try:
        mask = pipe.verify_batch(pool)
    finally:
        inj.disarm()
    assert mask == [False] * len(pool)
    rs = pipe.resilience_stats()
    assert rs["quarantine_rejected"] == 3
    assert pipe._pending() == 0, "window did not drain"
    # and the fault clearing un-rejects them: nothing is permanent
    cpu = CPUVerifier(reg)
    assert pipe.verify_batch(pool) == cpu.verify_batch(pool)


# -- simulation: chaos is invisible in the commit order ----------------


@pytest.mark.parametrize("kind", ["dispatch_raise", "resolve_raise"])
def test_sim_commit_order_under_chaos_matches_fault_free(keys, kind):
    """Acceptance: a verify-stack fault mid-consensus must not change
    the commit order — containment re-verifies the poisoned chunks, the
    masks stay a pure function of vertex bytes, and the delivered log
    equals the fault-free CPU run's. The resilience gauges surface in
    the per-process metrics snapshot."""
    from dag_rider_tpu.config import Config
    from dag_rider_tpu.consensus.simulator import Simulation

    reg, seeds = keys
    signers = [VertexSigner(s) for s in seeds]

    def run(factory, dedup=True):
        cfg = Config(n=N, coin="round_robin", propose_empty=True)
        sim = Simulation(
            cfg,
            verifier_factory=factory,
            signer_factory=lambda i: signers[i],
        )
        sim.dedup = dedup
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

    shared = TPUVerifier(reg)
    shared.fixed_bucket = 16
    # one fault, then clean: quarantine re-verifies on the (now clean)
    # same verifier, so the masks — and the order — cannot move
    inj = VerifierFaultInjector(
        VerifierFaultPlan(**{kind: 1.0}, max_faults=1, seed=906)
    )
    inj.arm(shared)
    try:
        # dedup off: bursts keep all n*(n-1) copies, so cycles genuinely
        # chunk past the bucket (same shape as test_pipeline's run)
        dev_log, sim = run(lambda i: shared, dedup=False)
    finally:
        inj.disarm()
    assert inj.faults_injected == 1, "chaos never hit the verify path"
    k = min(len(cpu_log), len(dev_log))
    assert k > 10 and cpu_log[:k] == dev_log[:k]
    snap = sim.processes[0].metrics.snapshot()
    assert snap.get("verify_quarantined", 0) >= 1
    assert "verify_retries" in snap and "sidecar_rpc_failures" in snap


# -- transport chaos satellites ----------------------------------------


class _PushTransport(Transport):
    """Minimal push-style transport: broadcast delivers synchronously to
    every other subscriber. Nothing beyond the two-method interface —
    the wrapper must compose with exactly this."""

    def __init__(self):
        self.handlers = {}

    def subscribe(self, index, handler):
        self.handlers[index] = handler

    def broadcast(self, msg):
        for i, h in self.handlers.items():
            if i != msg.sender:
                h(msg)


def test_faulty_transport_wraps_generic_transport():
    """Round-9 satellite: FaultyTransport over ANY Transport. Faults are
    rolled at delivery via the subscribe-captured handlers, delayed
    messages flush to the REAL handlers without a second roll, and the
    pump passthroughs are inert for a push-style inner."""
    plan = FaultPlan(delay=1.0, seed=1)
    tp = FaultyTransport(plan, inner=_PushTransport())
    got = {1: [], 2: []}
    tp.subscribe(1, got[1].append)
    tp.subscribe(2, got[2].append)
    v = Vertex(id=VertexID(1, 0), strong_edges=(VertexID(0, 1),))
    tp.broadcast(BroadcastMessage(vertex=v, round=1, sender=0))
    # delay=1.0: both deliveries held, none dropped or duplicated
    assert got[1] == [] and got[2] == []
    assert tp.stats["delayed"] == 2 and tp.stats["dropped"] == 0
    # push-style inner: nothing to pump, but the two held messages ARE
    # pending deliveries (round 11: sync patience reads this gauge to
    # tell "throttled" from "partitioned")
    assert tp.pump_one() is False and tp.pump() == 0 and tp.pending == 2
    # flush reaches the real handlers; delay=1.0 would hold them forever
    # if the flush re-rolled the plan
    assert tp.flush_delayed() == 2
    assert tp.pending == 0
    assert len(got[1]) == 1 and len(got[2]) == 1
    assert got[1][0].vertex == v

    # drop=1.0 over the same generic inner: counted, never delivered
    tp2 = FaultyTransport(FaultPlan(drop=1.0, seed=2), inner=_PushTransport())
    sunk = []
    tp2.subscribe(1, sunk.append)
    tp2.broadcast(BroadcastMessage(vertex=v, round=1, sender=0))
    assert sunk == [] and tp2.stats["dropped"] == 1


def test_transport_fault_stats_surface_in_metrics_snapshot():
    """Round-9 satellite: a chaos run's FaultyTransport.stats land in
    every process's metrics snapshot as transport_* counters."""
    from dag_rider_tpu.config import Config
    from dag_rider_tpu.consensus.simulator import Simulation

    plan = FaultPlan(duplicate=0.3, seed=5)
    tp = FaultyTransport(plan)
    sim = Simulation(
        Config(n=4, coin="round_robin"), transport=tp
    )
    sim.submit_blocks(per_process=2)
    sim.run(max_messages=4000)
    sim.check_agreement()
    assert tp.stats["duplicated"] > 0
    for p in sim.processes:
        snap = p.metrics.snapshot()
        assert snap["transport_duplicated"] == tp.stats["duplicated"]
        assert snap["transport_dropped"] == 0
    # clean-transport runs keep their snapshots free of transport_* keys
    clean = Simulation(Config(n=4, coin="round_robin"))
    clean.submit_blocks(per_process=1)
    clean.run(max_messages=1000)
    assert not any(
        k.startswith("transport_")
        for k in clean.processes[0].metrics.snapshot()
    )
