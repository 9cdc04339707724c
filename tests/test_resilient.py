"""Degradation-ladder tests (round-9 tentpole): retry, fallback,
health-probe promotion, and sidecar kill-and-restart recovery.

The ladder's contract: fail-closed per ATTEMPT (no attempt ever admits a
vertex it could not check), reject only after the WHOLE chain is
exhausted, and promote a recovered tier automatically — so a transient
backend failure costs latency, never valid vertices, and the commit
order downstream is identical to a fault-free run.
"""

import time

import pytest

from test_pipeline import N, _signed_pool

from dag_rider_tpu.verifier.base import (
    KeyRegistry,
    Verifier,
    VerifierUnavailableError,
    VertexSigner,
)
from dag_rider_tpu.verifier.cpu import CPUVerifier
from dag_rider_tpu.verifier.faults import (
    VerifierFaultInjector,
    VerifierFaultPlan,
)
from dag_rider_tpu.verifier.pipeline import VerifierPipeline
from dag_rider_tpu.verifier.resilient import (
    ResilientVerifier,
    default_verify_fallback,
    default_verify_retry,
)
from dag_rider_tpu.verifier.sidecar import RemoteVerifier, VerifierSidecarServer
from dag_rider_tpu.verifier.tpu import TPUVerifier


@pytest.fixture(scope="module")
def keys():
    return KeyRegistry.generate(N)


class _FlakyTier(Verifier):
    """CPU-oracle tier with a kill switch: raises while ``broken``,
    answers its ping accordingly. The controllable stand-in for a
    sidecar/device tier."""

    def __init__(self, reg):
        self._cpu = CPUVerifier(reg)
        self.broken = False
        self.calls = 0
        self.probes = 0

    def ping(self) -> bool:
        self.probes += 1
        return not self.broken

    def verify_batch(self, vertices):
        self.calls += 1
        if self.broken:
            raise VerifierUnavailableError("tier down")
        return self._cpu.verify_batch(vertices)


# -- ladder mechanics ---------------------------------------------------


def test_ladder_retries_falls_back_and_promotes(keys):
    """A tier failure is retried, then the call falls to the floor (same
    mask — no valid vertex rejected); the downed tier is probed in the
    background and promoted the moment it answers again."""
    reg, _ = keys
    pool = _signed_pool(keys, 24, seed=11)
    want = CPUVerifier(reg).verify_batch(pool)
    flaky = _FlakyTier(reg)
    ladder = ResilientVerifier(
        [flaky, CPUVerifier(reg)],
        retries=1,
        backoff_s=0.001,
        probe_interval_s=0.02,
    )
    assert ladder.verify_batch(pool) == want
    assert ladder.last_tier == 0 and ladder.fallbacks_total == 0

    flaky.broken = True
    assert ladder.verify_batch(pool) == want, "fallback changed the mask"
    assert ladder.last_tier == 1
    assert ladder.retries_total == 1  # one re-attempt on tier 0
    assert ladder.fallbacks_total == 1
    assert ladder.tier_health() == [False, True]
    # while down, calls skip the broken tier entirely
    calls_before = flaky.calls
    assert ladder.verify_batch(pool) == want
    assert flaky.calls == calls_before

    flaky.broken = False
    deadline = time.time() + 10
    while time.time() < deadline and not ladder.tier_health()[0]:
        time.sleep(0.01)
    assert ladder.tier_health() == [True, True], "probe never promoted"
    assert flaky.probes >= 1
    assert ladder.verify_batch(pool) == want
    assert ladder.last_tier == 0

    rs = ladder.resilience_stats()
    assert rs["retries"] == 1 and rs["fallbacks"] == 1
    assert rs["exhausted"] == 0
    assert rs["tier_health"] == [1, 1]


def test_ladder_exhaustion_fails_closed_then_recovers(keys):
    """Whole-ladder failure rejects the batch (all-False, full length)
    but does NOT brick the verifier: tiers marked down are still tried
    when nothing is healthy, so the first call after the fault clears
    succeeds — no valid vertex is permanently rejected."""
    reg, _ = keys
    pool = _signed_pool(keys, 12, seed=13)
    want = CPUVerifier(reg).verify_batch(pool)
    flaky = _FlakyTier(reg)
    flaky.ping = None  # exercise the empty-verify probe path
    flaky.broken = True
    ladder = ResilientVerifier(
        [flaky], retries=0, backoff_s=0.001, probe_interval_s=0.02
    )
    assert ladder.verify_batch(pool) == [False] * len(pool)
    assert ladder.exhausted_total == 1
    assert ladder.last_tier == 1  # len(tiers) = whole ladder exhausted
    # verify_rounds fail-closes with the same shape contract
    assert ladder.verify_rounds([pool[:3], [], pool[3:5]]) == [
        [False] * 3,
        [],
        [False] * 2,
    ]
    flaky.broken = False
    # even before any probe lands, the stale down mark must not brick
    # the verifier: all-down falls back to trying every tier
    assert ladder.verify_batch(pool) == want
    assert ladder.last_tier == 0
    deadline = time.time() + 10
    while time.time() < deadline and not ladder.tier_health()[0]:
        time.sleep(0.01)
    assert ladder.tier_health() == [True]


def test_ladder_wires_pipeline_quarantine_to_next_tier(keys):
    """Constructor wiring: a pipeline tier's quarantined chunks go to
    the ladder's NEXT tier. Under an unbounded resolve-fault storm the
    CPU floor answers every quarantine, so the mask stays correct and
    the ladder itself never even sees an exception — containment one
    level below the ladder."""
    reg, _ = keys
    pool = _signed_pool(keys, 48, seed=15)
    want = CPUVerifier(reg).verify_batch(pool)
    base = TPUVerifier(reg)
    pipe = VerifierPipeline(base, depth=2, fixed_bucket=16, warmup=False)
    floor = CPUVerifier(reg)
    ladder = ResilientVerifier([pipe, floor], retries=0)
    assert pipe.quarantine_verifier is floor

    inj = VerifierFaultInjector(VerifierFaultPlan(resolve_raise=1.0, seed=15))
    inj.arm(base)
    try:
        assert ladder.verify_batch(pool) == want
    finally:
        inj.disarm()
    assert ladder.last_tier == 0 and ladder.fallbacks_total == 0
    rs = ladder.resilience_stats()
    assert rs["quarantined"] == 3 and rs["quarantine_rejected"] == 0
    assert rs["poisoned_windows"] >= 1


# -- sidecar: retry, failure classes, kill-and-restart -----------------


def test_remote_retry_distinguishes_transport_from_invalid(keys):
    """Round-9 satellite: sidecar_rpc_failures counts TRANSPORT failures
    only — a batch of invalid signatures is a verdict (mask bits), not
    an rpc failure; an injected RPC fault is retried (reconnect +
    backoff) and succeeds once the fault clears."""
    reg, _ = keys
    pool = _signed_pool(keys, 12, seed=17)
    want = CPUVerifier(reg).verify_batch(pool)
    assert any(not ok for ok in want), "no corruption landed"

    server = VerifierSidecarServer(CPUVerifier(reg))
    remote = RemoteVerifier(
        server.address, retries=2, backoff_s=0.001, seed=1
    )
    try:
        # invalid signatures: False bits, ZERO rpc failures
        assert remote.verify_batch(pool) == want
        assert remote.rpc_failures == 0 and remote.retries_total == 0

        # transport faults: two injected failures, absorbed by retries
        inj = VerifierFaultInjector(
            VerifierFaultPlan(rpc_error=1.0, max_faults=2, seed=5)
        )
        inj.arm_remote(remote)
        try:
            assert remote.verify_batch(pool) == want
            assert remote.rpc_failures == 2 and remote.retries_total == 2
            assert remote.stats() == {
                "sidecar_rpc_failures": 2,
                "retries": 2,
            }
        finally:
            inj.disarm()

        # exhaustion: default contract fail-closes; the ladder flag
        # raises instead so a chain can take over
        storm = VerifierFaultInjector(
            VerifierFaultPlan(rpc_error=1.0, seed=6)
        )
        storm.arm_remote(storm_target := remote)
        try:
            assert storm_target.verify_batch(pool) == [False] * len(pool)
            assert storm_target.ping() is False
            storm_target.raise_on_unavailable = True
            with pytest.raises(VerifierUnavailableError):
                storm_target.verify_batch(pool)
        finally:
            storm.disarm()
            remote.raise_on_unavailable = False
        assert remote.ping() is True
        assert remote.verify_batch(pool) == want
    finally:
        remote.close()
        server.stop()


def test_sidecar_kill_and_restart_ladder_recovers(keys):
    """Round-9 satellite: kill the sidecar mid-stream — the ladder falls
    to its CPU floor with an identical mask; restart the sidecar on the
    SAME address — the background probe reconnects and promotes it, and
    the next call rides the sidecar again."""
    reg, _ = keys
    pool = _signed_pool(keys, 32, seed=19)
    want = CPUVerifier(reg).verify_batch(pool)

    server = VerifierSidecarServer(CPUVerifier(reg))
    port = server.bound_port
    remote = RemoteVerifier(
        server.address, timeout=2.0, retries=1, backoff_s=0.001, seed=2
    )
    ladder = ResilientVerifier(
        [remote, CPUVerifier(reg)],
        retries=0,
        backoff_s=0.001,
        probe_interval_s=0.05,
    )
    assert remote.raise_on_unavailable, "ladder must force raise semantics"
    revived = None
    try:
        assert ladder.verify_batch(pool) == want
        assert ladder.last_tier == 0

        server.stop()  # kill mid-stream
        assert ladder.verify_batch(pool) == want, "kill cost valid vertices"
        assert ladder.last_tier == 1 and ladder.fallbacks_total >= 1
        assert remote.rpc_failures >= 1
        rs = ladder.resilience_stats()
        assert rs["sidecar_health"] == 0
        assert rs["sidecar_rpc_failures"] >= 1

        revived = VerifierSidecarServer(
            CPUVerifier(reg), listen_addr=f"127.0.0.1:{port}"
        )
        if revived.bound_port == 0:
            pytest.skip("ephemeral port reused by another process")
        deadline = time.time() + 15
        while time.time() < deadline and not ladder.tier_health()[0]:
            time.sleep(0.02)
        assert ladder.tier_health()[0], "sidecar tier never promoted back"
        assert ladder.verify_batch(pool) == want
        assert ladder.last_tier == 0
        assert ladder.resilience_stats()["sidecar_health"] == 1
    finally:
        remote.close()
        if revived is not None:
            revived.stop()
        else:
            server.stop()


def test_sim_commit_order_with_sidecar_failover(keys):
    """Acceptance: a sidecar killed MID-CONSENSUS must not move the
    commit order — the ladder's floor computes the same masks, so the
    delivered log equals the fault-free CPU run's, and the resilience
    gauges land in the per-process metrics snapshot."""
    from dag_rider_tpu.config import Config
    from dag_rider_tpu.consensus.simulator import Simulation

    reg, seeds = keys
    signers = [VertexSigner(s) for s in seeds]

    def run(factory, mid_run=None):
        cfg = Config(n=N, coin="round_robin", propose_empty=True)
        sim = Simulation(
            cfg,
            verifier_factory=factory,
            signer_factory=lambda i: signers[i],
        )
        sim.submit_blocks(per_process=2)
        for cycle in range(10):
            sim.run(max_messages=N * (N - 1))
            if mid_run is not None and cycle == 3:
                mid_run()
        sim.check_agreement()
        log = [
            (v.id.round, v.id.source, v.digest())
            for v in sim.deliveries[0]
        ]
        return log, sim

    cpu_log, _ = run(lambda i: CPUVerifier(reg))
    assert len(cpu_log) > 10, "CPU reference run delivered too little"

    server = VerifierSidecarServer(CPUVerifier(reg))
    remote = RemoteVerifier(
        server.address, timeout=2.0, retries=0, backoff_s=0.001, seed=3
    )
    # long probe interval: the sidecar stays down for the rest of the
    # run, so every post-kill cycle exercises the floor
    ladder = ResilientVerifier(
        [remote, CPUVerifier(reg)],
        retries=0,
        backoff_s=0.001,
        probe_interval_s=60.0,
    )
    try:
        lad_log, sim = run(lambda i: ladder, mid_run=server.stop)
    finally:
        remote.close()
        server.stop()
    assert ladder.fallbacks_total >= 1, "the kill never hit the verify path"
    k = min(len(cpu_log), len(lad_log))
    assert k > 10 and cpu_log[:k] == lad_log[:k]
    snap = sim.processes[0].metrics.snapshot()
    assert snap.get("verify_fallback_tier") == 1
    assert snap.get("sidecar_health") == 0
    assert snap.get("sidecar_rpc_failures", 0) >= 1


# -- knobs --------------------------------------------------------------


def test_verify_knob_env_defaults_and_validation(monkeypatch):
    monkeypatch.delenv("DAGRIDER_VERIFY_RETRY", raising=False)
    assert default_verify_retry() == 1
    monkeypatch.setenv("DAGRIDER_VERIFY_RETRY", "3")
    assert default_verify_retry() == 3
    monkeypatch.setenv("DAGRIDER_VERIFY_RETRY", "-1")
    with pytest.raises(ValueError):
        default_verify_retry()

    monkeypatch.delenv("DAGRIDER_VERIFY_FALLBACK", raising=False)
    assert default_verify_fallback() == ""
    for off in ("0", "off", "none", "false"):
        monkeypatch.setenv("DAGRIDER_VERIFY_FALLBACK", off)
        assert default_verify_fallback() == ""
    monkeypatch.setenv("DAGRIDER_VERIFY_FALLBACK", "CPU")
    assert default_verify_fallback() == "cpu"
    monkeypatch.setenv("DAGRIDER_VERIFY_FALLBACK", "gpu")
    with pytest.raises(ValueError):
        default_verify_fallback()

    with pytest.raises(ValueError):
        ResilientVerifier([])
