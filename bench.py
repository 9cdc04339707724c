"""Benchmark: vertex-signatures verified/sec on one chip (north star).

Prints ONE JSON line (the last JSON line on stdout is authoritative):
  {"metric": "vertex_sigs_per_sec", "value": N, "unit": "sigs/s",
   "vs_baseline": N / 50000, "backend": ..., "n": ...,
   "wave_commit_p50_ms": ..., "phases": {...}, "ladder": {...}}

BASELINE.json north star: >= 50,000 vertex-signatures verified/sec on a
single TPU v5e chip at committee size n=256. The measured quantity is the
steady-state end-to-end Verifier throughput: host prep (SHA-512 challenge
scalars, byte parsing) + one device dispatch per whole-round batch —
exactly what the consensus hot path pays per DAG round. ``ladder`` holds
BASELINE.md rungs #3/#4: a time-boxed 64-node consensus-in-the-loop
simulation with the device verifier (Metrics sigs_per_sec +
wave_commit_p50_ms), and the 256-node threshold-coin timing including one
Byzantine share (batched RLC recovery).

One process, which owns the chip: ``python bench.py`` measures phase by
phase (n=256 verify -> n=64 verify -> wave pipeline -> ladder rungs),
re-printing a cumulative JSON line after every phase and flushed
``[bench +T.Ts] stage`` markers on stderr, inside the wall budget
``DAGRIDER_BENCH_SECONDS`` (phases are skipped when the deadline nears).
It exits non-zero when jax's platform is not ``tpu``: a CPU timing is
never written under ``vertex_sigs_per_sec``.
"""

from __future__ import annotations

import json
import os
import sys
import time

BASELINE = 50_000.0
_REPO = os.path.dirname(os.path.abspath(__file__))
_T0 = time.monotonic()


def _mark(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


# ----------------------------------------------------------------------
# Measure (phased, deadline-aware, cumulative JSON after each phase)
# ----------------------------------------------------------------------

def _quorum(n: int) -> int:
    return 2 * ((n - 1) // 3) + 1


def _signed_round(signers, n: int, rnd: int, quorum: int):
    """One round's signed vertex batch (the unit every bench phase uses).

    The consensus pipeline computes the digest at r_deliver admission
    (process.on_message), which also fills the signing-bytes memo;
    pre-touching here keeps the verify phases measuring the Verifier
    seam, same as in production.
    """
    from dag_rider_tpu.core.types import Block, Vertex, VertexID

    vs = []
    for i in range(n):
        v = Vertex(
            id=VertexID(rnd, i),
            block=Block((f"r{rnd}-tx-{i}".encode() * 2,)),
            strong_edges=tuple(
                VertexID(rnd - 1, s) for s in range(min(n, quorum))
            ),
        )
        v = signers[i].sign_vertex(v)
        v.digest()
        vs.append(v)
    return vs


def _sign_rounds_worker(args):
    """Sign a slice of rounds in a spawn worker (pure-Python Ed25519 —
    no jax import, so workers start fast and are fork-safety-clean).
    Deterministic: output depends only on (seeds, n, round numbers)."""
    seeds, n, rnds = args
    from dag_rider_tpu.verifier.base import VertexSigner

    signers = [VertexSigner(s) for s in seeds]
    quorum = _quorum(n)
    return [(r, _signed_round(signers, n, r, quorum)) for r in rnds]


def _build_batches(n: int, rounds: int):
    from dag_rider_tpu.verifier.base import KeyRegistry, VertexSigner
    from dag_rider_tpu.verifier.tpu import TPUVerifier

    reg, seeds = KeyRegistry.generate(n)
    signers = [VertexSigner(s) for s in seeds]
    quorum = _quorum(n)
    workers = min(8, os.cpu_count() or 1)
    if n * rounds >= 2048 and workers >= 4:
        # The n=256 headline phase signs ~16k vertices at ~2.6 ms each —
        # 42 s of the cold-start budget single-threaded (round-3 weak
        # #8). Host signing is embarrassingly parallel and deterministic;
        # spawn (not fork: the parent may hold the chip) + workers that
        # import no jax — they must stay so, a child that touches jax
        # contends for the parent's chip — cut it to ~1/workers.
        # Signature memos ride the pickles, so digest() stays pre-warmed
        # like the serial path. (This branch needs >= 4 cores: it never
        # ran on the one-core box rounds 1-21 were built on.)
        import concurrent.futures as cf
        import multiprocessing as mp

        chunks = [
            list(range(w + 1, rounds + 1, workers)) for w in range(workers)
        ]
        by_round = {}
        try:
            with cf.ProcessPoolExecutor(
                max_workers=workers, mp_context=mp.get_context("spawn")
            ) as ex:
                for part in ex.map(
                    _sign_rounds_worker,
                    [(seeds, n, c) for c in chunks if c],
                ):
                    for r, vs in part:
                        by_round[r] = vs
            batches = [by_round[r + 1] for r in range(rounds)]
        except Exception as e:  # noqa: BLE001 — a broken pool must not
            # cost the headline phase; serial signing is the pre-change
            # behavior and always works
            _mark(f"parallel signing failed ({e!r}); falling back to serial")
            batches = [
                _signed_round(signers, n, r + 1, quorum)
                for r in range(rounds)
            ]
    else:
        batches = [
            _signed_round(signers, n, r + 1, quorum) for r in range(rounds)
        ]
    return TPUVerifier(reg), batches, signers


def _sim_rung(
    n: int,
    box_s: float,
    verifier,
    signers,
    *,
    bucket: int,
    chunk: int,
    coin: str = "round_robin",
    gc_depth: int = 24,
    pipelined: bool = True,
    target_per_view: int = 0,
    max_s: float = 0.0,
):
    """Time-boxed consensus-in-the-loop simulation (BASELINE configs #3/#4
    live halves): n processes, shared device verifier (coalesced + async
    pipelined dispatch — Simulation.run), signed vertices, optional
    threshold-BLS coin. Returns the ladder entry dict."""
    import time as _t

    from dag_rider_tpu.config import Config
    from dag_rider_tpu.consensus.simulator import Simulation

    # the verifier is SHARED across rungs (and with the deferred
    # merged headline phase): borrow its state under try/finally so
    # an exception inside the box cannot leak a sim-sized bucket or
    # a disabled pipeline into whoever runs next (driderlint:release)
    prev_bucket = getattr(verifier, "fixed_bucket", None)
    prev_enabled = getattr(verifier, "pipeline_enabled", True)
    try:
        verifier.fixed_bucket = bucket
        cfg = Config(
            n=n, coin="round_robin", propose_empty=True, gc_depth=gc_depth
        )
        coin_factory = None
        entry_coin = coin
        if coin == "threshold_bls":
            # Shared aggregation oracle: the (f+1)-of-n combine + pairing
            # check is a pure function of the observed shares (identical at
            # every process), so the sim evaluates it once per wave — the
            # same amortization as the shared Verifier. Per-process share
            # SIGNING stays real; the standalone coin cost is measured
            # honestly by the coin256 rung.
            from dag_rider_tpu.consensus.coin import ThresholdCoin
            from dag_rider_tpu.crypto import threshold as th

            f = (n - 1) // 3
            keys = th.ThresholdKeys.generate(n, f + 1)
            oracle = ThresholdCoin(keys, 0, n)

            def coin_factory(i: int):
                c = ThresholdCoin(keys, i, n)
                c._shares = oracle._shares
                c._sigma = oracle._sigma
                c._tried_at = oracle._tried_at
                # shared books must not be pruned by whichever process's GC
                # floor runs first — a (slightly) lagging sibling still reads
                # them; a production per-process coin prunes by its OWN
                # floor, which cannot outrun its own queries
                c.prune_below = lambda wave: None
                return c

            cfg = Config(
                n=n, coin="threshold_bls", propose_empty=True, gc_depth=gc_depth
            )
        sim = Simulation(
            cfg,
            coin_factory=coin_factory,
            verifier_factory=lambda i: verifier,
            signer_factory=lambda i: signers[i],
        )
        sim.submit_blocks(per_process=2)
        # AOT-compile the rung's program shape OUTSIDE the timed box (no-op
        # when already warmed this run or served from the persistent cache)
        warm0 = getattr(verifier, "warmup_compile_s", 0.0)
        if hasattr(verifier, "warmup"):
            verifier.warmup()
        if not pipelined:
            # Explicit A/B switch: Simulation.run (and the verifier's own
            # chunk streaming) sees pipeline_enabled False and takes the
            # synchronous depth-1 path — the before/after evidence for how
            # much the dispatch/delivery overlap cuts wave-commit p50
            # (round-4 VERDICT #4; replaces the round-5 None shadow).
            verifier.pipeline_enabled = False
        tot0 = (
            getattr(verifier, "total_prepare_s", 0.0),
            getattr(verifier, "total_dispatch_s", 0.0),
            getattr(verifier, "total_dispatches", 0),
            getattr(verifier, "total_sigs_dispatched", 0),
        )
        # host-prep engine row counters BEFORE the box, for a rung-local
        # parallel fraction (prep_stats' own fraction is engine-lifetime)
        ps0 = (
            verifier.prep_stats()
            if callable(getattr(verifier, "prep_stats", None))
            else None
        )
        t0 = _t.monotonic()
        pumped = 0
        while True:
            el = _t.monotonic() - t0
            if el >= box_s:
                # optional extension past the box until the rung's own
                # spec is met (BASELINE config #3: >= 10k vertices per
                # view) — bounded by max_s so it cannot eat the ladder
                if (
                    not target_per_view
                    or el >= max_s
                    or max((len(d) for d in sim.deliveries), default=0)
                    >= target_per_view
                ):
                    break
            pumped += sim.run(max_messages=chunk)
        dt = _t.monotonic() - t0
    finally:
        verifier.pipeline_enabled = prev_enabled
        verifier.fixed_bucket = prev_bucket
    sigs = sum(p.metrics.verify_sigs_total for p in sim.processes)
    waves = [
        s for p in sim.processes for s in p.metrics.wave_commit_seconds
    ]
    waves.sort()
    # the end-to-end cadence (wall time between consecutive decided
    # waves, ~4 rounds of verify+consensus each) — the quantity the
    # round-3 staged proxy modeled; wave_commit_p50_ms below is only
    # the decide+ordering walk
    intervals = [
        s for p in sim.processes for s in p.metrics.wave_interval_seconds
    ]
    intervals.sort()
    delivered = sum(len(d) for d in sim.deliveries)
    # one delta per counter — sigs_device and the breakdown's
    # sigs_dispatched MUST stay the same number
    # the depth-K window Simulation.run streamed dispatches through
    # (None on the pipeline-off side — its gauges then read empty)
    pipe = getattr(sim, "_verify_pipe", None)
    d_prep = getattr(verifier, "total_prepare_s", 0.0) - tot0[0]
    d_disp = getattr(verifier, "total_dispatch_s", 0.0) - tot0[1]
    d_count = getattr(verifier, "total_dispatches", 0) - tot0[2]
    d_sigs = getattr(verifier, "total_sigs_dispatched", 0) - tot0[3]
    if ps0 is not None:
        ps1 = verifier.prep_stats()
        d_rows = ps1["rows_total"] - ps0["rows_total"]
        d_rows_par = ps1["rows_parallel"] - ps0["rows_parallel"]
        prep_gauges = {
            "prep_workers": ps1["workers"],
            "prep_parallel_fraction": (
                round(d_rows_par / d_rows, 3) if d_rows > 0 else 0.0
            ),
        }
    else:
        prep_gauges = {"prep_workers": 1, "prep_parallel_fraction": 0.0}
    # round-9 resilience gauges: containment/ladder counters of this
    # rung's verify stack (all zero on a clean run — the chaos rung and
    # ladder deployments are where they move)
    rs_fn = getattr(
        pipe if pipe is not None else verifier, "resilience_stats", None
    )
    rs = rs_fn() if callable(rs_fn) else {}
    res_gauges = {
        "verify_retries": rs.get("retries", 0),
        "verify_fallback_tier": rs.get("fallback_tier", 0),
        "verify_quarantined": rs.get("quarantined", 0),
        "poisoned_windows": rs.get("poisoned_windows", 0),
        "sidecar_rpc_failures": rs.get("sidecar_rpc_failures", 0),
    }
    # round-12 host-pump gauges: which pump flavor drove the run and
    # what the host paid per round at the consensus seam (the quantity
    # the vectorized pump exists to move)
    snap0 = sim.processes[0].metrics.snapshot()
    pump_gauges = {
        k: snap0[k]
        for k in ("pump_path", "pump_msgs_per_s", "host_pump_ms_per_round")
        if k in snap0
    }
    return {
        "nodes": n,
        "coin": entry_coin,
        "pipelined": pipelined,
        # Explicit, non-interchangeable counters (pre-round-5 entries
        # used one ambiguous sigs_verified/sigs_per_sec pair):
        # *_applied = per-process verdicts applied, the aggregate a real
        # n-node cluster performs (under dedup, fanned out from unique
        # device checks); *_device = what THIS chip actually verified.
        # Without dedup the two coincide.
        "dedup": sim.dedup,
        "seconds": round(dt, 1),
        "messages": pumped,
        "sigs_applied": sigs,
        "sigs_applied_per_sec": round(sigs / dt, 1),
        "sigs_device": d_sigs,
        "sigs_device_per_sec": round(d_sigs / dt, 1),
        "vertices_delivered_total": delivered,
        # per-view DAG size (BASELINE config #3's "10k-vertex DAG" is
        # per view, not summed across the n copies)
        "vertices_delivered_per_view": max(
            (len(d) for d in sim.deliveries), default=0
        ),
        "max_round": max(p.round for p in sim.processes),
        # bounded-memory evidence: cumulative DAG size vs live window
        "vertices_live_max": max(
            len(p.dag.vertices) for p in sim.processes
        ),
        "vertices_pruned_total": sum(
            p.dag.pruned_count for p in sim.processes
        ),
        "wave_commit_p50_ms": (
            round(1e3 * waves[len(waves) // 2], 2) if waves else None
        ),
        "wave_interval_p50_ms": (
            round(1e3 * intervals[len(intervals) // 2], 2)
            if intervals
            else None
        ),
        # where the wall time went at the verifier seam (VERDICT r04 #2:
        # a shortfall must be attributable): host prep vs device
        # dispatch+sync vs everything else (admission, ordering, coin,
        # message pump)
        "verifier_breakdown": {
            "prepare_s": round(d_prep, 2),
            # LOWER BOUND on pipelined runs (ADVICE r5 #1): device time
            # hidden under the delivery-flush window or later chunks'
            # host prep never blocks resolve and books ~0 here — only
            # UNHIDDEN device time is measured
            "device_s": round(d_disp, 2),
            "host_other_s": round(max(0.0, dt - d_prep - d_disp), 2),
            "dispatches": d_count,
            "sigs_dispatched": d_sigs,
            "ms_per_dispatch": (
                round(1e3 * d_disp / d_count, 1) if d_count else None
            ),
            # depth-K window gauges (verifier/pipeline.py): configured
            # depth, in-flight high-water, and the share of seam wall
            # time the host spent working instead of blocked in resolve
            # — the amortization evidence future BENCH rounds track
            "queue_depth": getattr(pipe, "depth", 1) if pipe else 1,
            "queue_depth_max": (
                getattr(pipe, "depth_hwm", 0) if pipe else 0
            ),
            "overlap_fraction": (
                round(pipe.overlap_fraction(), 3)
                if pipe is not None and pipe.overlap_fraction() is not None
                else 0.0
            ),
            # AOT lower+compile seconds this rung paid OUTSIDE the box
            # (0.0 on a warm program / persistent-cache process)
            "warmup_compile_s": round(
                getattr(verifier, "warmup_compile_s", 0.0) - warm0, 2
            ),
            # mesh placement gauges (ShardedTPUVerifier; 1/0/0.0 on the
            # single-chip path): devices the dispatch laid out over,
            # per-shard rows of the last dispatch, and its shard fill
            # imbalance (0.0 = every shard carried equal real rows)
            "mesh_devices": getattr(verifier, "mesh_devices", 1),
            "shard_batch": getattr(verifier, "last_shard_batch", 0),
            "shard_imbalance": round(
                getattr(verifier, "last_shard_imbalance", 0.0), 3
            ),
            # parallel host-prep engine gauges (verifier/prep.py):
            # configured worker count + share of this rung's prepped
            # rows that took the row-block parallel path
            **prep_gauges,
            # fault-containment / degradation-ladder gauges (round 9)
            **res_gauges,
            # host consensus-pump gauges (round 12)
            **pump_gauges,
        },
    }


def _vec_ab_rung(n: int, budget_s: float, target_round: int) -> dict:
    """Scalar-vs-vector host pump A/B (round 12). Two null-verifier sims
    run the SAME protocol to the same target round, one per pump flavor;
    the vector path must produce byte-identical per-view delivery
    sequences (id + digest) — it is an execution strategy, not a
    protocol change — and the msgs/s ratio is the rung's headline.
    Raises AssertionError on commit-order divergence. Also the tier1-vec
    CI smoke (tests/test_bench_rungs.py)."""
    import time as _t

    from dag_rider_tpu.config import Config
    from dag_rider_tpu.consensus.simulator import Simulation

    sides: dict = {}
    orders: dict = {}
    for path in ("scalar", "vector"):
        cfg = Config(
            n=n,
            coin="round_robin",
            propose_empty=True,
            gc_depth=24,
            pump=path,
        )
        sim = Simulation(cfg)
        sim.submit_blocks(per_process=2)
        t0 = _t.monotonic()
        pumped = 0
        while (
            max(p.round for p in sim.processes) < target_round
            and _t.monotonic() - t0 < budget_s
        ):
            pumped += sim.run(max_messages=n * (n - 1))
        dt = _t.monotonic() - t0
        sim.check_agreement()
        snap0 = sim.processes[0].metrics.snapshot()
        orders[path] = [
            [(v.id, v.digest()) for v in d] for d in sim.deliveries
        ]
        sides[path] = {
            "seconds": round(dt, 2),
            "messages": pumped,
            "msgs_per_sec": round(pumped / dt, 1),
            "max_round": max(p.round for p in sim.processes),
            "vertices_delivered_total": sum(
                len(d) for d in sim.deliveries
            ),
            **{
                k: snap0[k]
                for k in ("pump_msgs_per_s", "host_pump_ms_per_round")
                if k in snap0
            },
        }
    identical = orders["scalar"] == orders["vector"]
    entry = {
        "nodes": n,
        "target_round": target_round,
        "scalar": sides["scalar"],
        "vector": sides["vector"],
        # the equivalence gate: same deliveries, same order, same
        # bytes, at every view
        "commit_order_identical": identical,
        "speedup": round(
            sides["vector"]["msgs_per_sec"]
            / max(sides["scalar"]["msgs_per_sec"], 1e-9),
            2,
        ),
    }
    if not identical:
        raise AssertionError(
            f"sim{n}_vec: vector pump diverged from scalar commit order"
        )
    return entry


def _trace_ab_rung(
    n: int, budget_s: float, target_round: int, reps: int = 9
) -> dict:
    """Trace-off vs trace-on A/B (round 16). Null-verifier sims run the
    SAME protocol to the same target round, one side with no log and one
    with the full obs bundle (ring recorder + flight watch + lifecycle/
    phase spans at sample rate 1.0); tracing must produce byte-identical
    per-view delivery sequences — events observe, they never feed
    consensus state — and the msgs/s delta is the rung's headline,
    gated at < 5% overhead. A single pump to round ~40 is sub-second,
    where one scheduler blip reads as ±30% — the headline is the median
    of per-rep PAIRED CPU-time ratios: each rep runs both sides
    back-to-back (alternating which goes first, so a co-tenant burst
    arriving mid-pair biases reps in both directions instead of always
    penalizing the second side), `time.process_time` excludes
    preemption, and the median rejects the burst-poisoned tail. Commit
    order is checked on EVERY repetition and raises AssertionError on
    divergence. Also the tier1-obs CI smoke (tests/test_bench_rungs.py)."""
    import time as _t

    from dag_rider_tpu import obs
    from dag_rider_tpu.config import Config
    from dag_rider_tpu.consensus.simulator import Simulation

    sides: dict = {}
    orders: dict = {}
    ring_stats: dict = {}
    deadline = _t.monotonic() + 2.0 * budget_s

    def one_run(path: str) -> dict:
        cfg = Config(
            n=n,
            coin="round_robin",
            propose_empty=True,
            gc_depth=24,
        )
        tracing = obs.build_tracing(sample_rate=1.0) if path == "on" else None
        sim = Simulation(
            cfg, log=tracing.log if tracing is not None else None
        )
        sim.submit_blocks(per_process=2)
        t0 = _t.monotonic()
        c0 = _t.process_time()
        pumped = 0
        while (
            max(p.round for p in sim.processes) < target_round
            and _t.monotonic() - t0 < budget_s
        ):
            pumped += sim.run(max_messages=n * (n - 1))
        dt = _t.monotonic() - t0
        cpu = _t.process_time() - c0
        sim.check_agreement()
        order = [[(v.id, v.digest()) for v in d] for d in sim.deliveries]
        if path in orders:
            if orders[path] != order:
                raise AssertionError(
                    f"trace_overhead: {path} side not reproducible at n={n}"
                )
        else:
            orders[path] = order
        if tracing is not None:
            ring_stats.update(
                trace_events=len(tracing.recorder),
                trace_dropped=tracing.recorder.dropped,
            )
        return {
            "seconds": round(dt, 2),
            "cpu_seconds": round(cpu, 3),
            "messages": pumped,
            "msgs_per_sec": round(pumped / dt, 1),
            "max_round": max(p.round for p in sim.processes),
            "vertices_delivered_total": sum(
                len(d) for d in sim.deliveries
            ),
        }

    ratios = []
    for rep in range(max(1, reps)):
        pair = {}
        first = ("off", "on") if rep % 2 == 0 else ("on", "off")
        for path in first:
            run = one_run(path)
            pair[path] = run["cpu_seconds"]
            best = sides.get(path)
            if best is None or run["msgs_per_sec"] > best["msgs_per_sec"]:
                sides[path] = run
        # paired CPU ratio: both runs of a rep share the box's load
        # state, so the ratio is far less noisy than either side's
        # absolute msgs/s on a busy host
        ratios.append(pair["on"] / max(pair["off"], 1e-9))
        if rep > 0 and _t.monotonic() > deadline:
            break  # both sides have >= 2 samples; stay inside the box
    ratios.sort()
    median_ratio = ratios[len(ratios) // 2]
    identical = orders["off"] == orders["on"]
    overhead_pct = round(100.0 * (median_ratio - 1.0), 2)
    entry = {
        "nodes": n,
        "target_round": target_round,
        "off": sides["off"],
        "on": sides["on"],
        **ring_stats,
        # the equivalence gate: same deliveries, same order, same
        # bytes, at every view — tracing is observation, not protocol
        "commit_order_identical": identical,
        "overhead_pct": overhead_pct,
        "overhead_ok": overhead_pct < 5.0,
    }
    if not identical:
        raise AssertionError(
            f"trace_overhead: tracing diverged commit order at n={n}"
        )
    return entry


def _finality_rung(
    n: int = 64,
    wall_s: float = 10.0,
    rate: float = 2000.0,
    drain_s: float = 30.0,
) -> dict:
    """ladder.finality rung (ISSUE 16): submit→deliver finality with
    pipelined waves + eager optimistic delivery, in two halves.

    Half 1 — the byte-identity gate: knobs-off vs knobs-on lockstep
    sims over a seeded n × adversary matrix must produce byte-identical
    per-view delivery sequences (id + digest), the eager reconciliation
    books must balance (delivered == reconciled) and the expected-zero
    rollback counter must read zero on every honest process. RAISES
    AssertionError on any divergence — a recorded entry IS a passed
    gate.

    Half 2 — the wall-clock headline: a mempool-fronted load run at
    ``n`` with everything on (wave pipeline, eager delivery, adaptive
    batch deadline) against a knobs-off twin. Each transaction's
    end-to-end latency is decomposed at observation time into
    queueing (submit → block built, the batcher's hold) and wave lag
    (block built → a_deliver, DAG admission + commit + flush), so the
    attribution components sum to the measured total per sample — the
    means are checked to sum exactly (float slack only). The eager
    stream's submit→early-surface p50 rides alongside as the optimistic
    finality number, and ``p50_under_1s`` records the sub-second
    acceptance gate at the knobs-on side."""
    import time as _t

    from dag_rider_tpu.config import Config, MempoolConfig
    from dag_rider_tpu.consensus.adversary import (
        ByzantineProcess,
        make_behavior,
    )
    from dag_rider_tpu.consensus.process import Process
    from dag_rider_tpu.consensus.simulator import Simulation
    from dag_rider_tpu.mempool.loadgen import (
        ClusterLoadDriver,
        LoadGenerator,
    )
    from dag_rider_tpu.utils.metrics import Histogram

    # -- half 1: identity gate over the seeded matrix ----------------------

    def one_side(sz, seed, adversary, knobs_on, cycles):
        cfg = Config(
            n=sz,
            coin="round_robin",
            propose_empty=True,
            wave_pipeline=knobs_on,
            eager_deliver=knobs_on,
            # lockstep pump: wall-clock sync throttles would starve the
            # anti-entropy recovery the withhold adversary forces
            sync_request_cooldown_s=0.0,
            sync_serve_cooldown_s=0.0,
            sync_patience=1,
        )
        nbyz = cfg.f if adversary else 0
        behaviors = {
            i: make_behavior(adversary, seed=seed + 1000 + i)
            for i in range(nbyz)
        }

        def factory(pcfg, i, ptp, **kwargs):
            if i in behaviors:
                return ByzantineProcess(
                    pcfg, i, ptp, behavior=behaviors[i], **kwargs
                )
            return Process(pcfg, i, ptp, **kwargs)

        sim = Simulation(
            cfg, process_factory=factory if behaviors else None
        )
        sim.submit_blocks(per_process=2)
        for _ in range(cycles):
            sim.run(max_messages=sz * (sz - 1))
        logs = [
            [(v.id.round, v.id.source, v.digest()) for v in d]
            for d in sim.deliveries
        ]
        return logs, sim, nbyz

    matrix = (
        (4, 1, None, 12),
        (16, 5, "equivocate", 12),
        (16, 6, "withhold", 40),
        (32, 7, None, 8),
        (64, 8, None, 8),
    )
    identity = []
    for sz, seed, adversary, cycles in matrix:
        off_logs, _, nbyz = one_side(sz, seed, adversary, False, cycles)
        on_logs, sim, _ = one_side(sz, seed, adversary, True, cycles)
        if not any(off_logs[nbyz:]):
            raise AssertionError(
                f"finality identity n={sz} {adversary}: oracle "
                "delivered nothing — vacuous gate"
            )
        if off_logs != on_logs:
            raise AssertionError(
                f"finality identity n={sz} {adversary}: knobs-on "
                "commit order diverged from the oracle"
            )
        eager_del = eager_rec = 0
        for i, p in enumerate(sim.processes):
            if i < nbyz:
                continue
            snap = p.metrics.snapshot()
            if snap.get("eager_rollbacks_expected_zero", 0):
                raise AssertionError(
                    f"finality identity n={sz} {adversary}: eager "
                    "rollback counter nonzero on an honest process"
                )
            eager_del += snap.get("eager_delivered", 0)
            eager_rec += snap.get("eager_reconciled", 0)
        if eager_del != eager_rec:
            raise AssertionError(
                f"finality identity n={sz} {adversary}: eager books "
                f"unbalanced ({eager_del} surfaced, {eager_rec} "
                "reconciled)"
            )
        identity.append(
            {
                "n": sz,
                "seed": seed,
                "adversary": adversary or "clean",
                "delivered_view0": len(off_logs[nbyz]),
                "eager_delivered": eager_del,
            }
        )

    # -- half 2: wall-clock latency + attribution at n ---------------------

    class _AttribDriver(ClusterLoadDriver):
        """ClusterLoadDriver that splits every closed latency book into
        its two exhaustive components at the same timestamps the total
        uses, so component means sum to the total mean exactly."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.batch_wait = Histogram()
            self.wave_lag = Histogram()
            self.sum_batch = 0.0
            self.sum_wave = 0.0
            self.sum_total = 0.0
            self.n_attr = 0
            self._built_at = {}
            for mp in self.mempools:
                orig = mp.observe_delivered

                def wrapped(block, now=None, mp=mp, orig=orig):
                    t = mp.clock() if now is None else now
                    for tx in block.transactions:
                        t0 = mp._inflight.get(tx)
                        if t0 is None:
                            # not this view's transaction — leave the
                            # build stamp for the origin mempool's pass
                            continue
                        tb = self._built_at.pop(tx, None)
                        if tb is not None:
                            bw = max(0.0, tb - t0)
                            wl = max(0.0, t - tb)
                            self.batch_wait.observe(bw)
                            self.wave_lag.observe(wl)
                            self.sum_batch += bw
                            self.sum_wave += wl
                            self.sum_total += max(0.0, t - t0)
                            self.n_attr += 1
                    orig(block, now=now)

                mp.observe_delivered = wrapped

        def _flush_batches(self, t, force=False):
            now = None if self.wall else t
            for i, mp in enumerate(self.mempools):
                staged = len(self.sim.processes[i].blocks_to_propose)
                blocks = mp.build_blocks(
                    now=now, force=force, staged=staged
                )
                tb = mp.clock() if now is None else now
                for b in blocks:
                    for tx in b.transactions:
                        if tx in mp._inflight:
                            self._built_at[tx] = tb
                    self.sim.processes[i].submit(b)
                    self.submission_log.append((self.cycles, i, b))

    sides: dict = {}
    attribution: dict = {}
    eager_lat = Histogram()
    for path in ("off", "on"):
        on = path == "on"
        cfg = Config(
            n=n,
            coin="round_robin",
            propose_empty=True,
            gc_depth=24,
            wave_pipeline=on,
            eager_deliver=on,
        )
        sim = Simulation(cfg)
        gen = LoadGenerator(
            clients=32, rate=rate, tx_bytes=32, seed=16, profile="poisson"
        )
        drv = _AttribDriver(
            sim,
            gen,
            mcfg=MempoolConfig(
                cap=65536, batch_bytes=4096, adaptive_deadline=on
            ),
            wall=True,
        )
        if on:
            # submit→early-surface latency: the optimistic finality a
            # client acting on the speculative stream would see (books
            # stay open — the canonical a_deliver still closes them)
            for p, mp, esink in zip(
                sim.processes, drv.mempools, sim.eager_deliveries
            ):

                def early(v, mp=mp, esink=esink):
                    t = mp.clock()
                    for tx in v.block.transactions:
                        t0 = mp._inflight.get(tx)
                        if t0 is not None:
                            eager_lat.observe(max(0.0, t - t0))
                    esink.append(v)

                p.on_deliver_early = early
        entry = drv.run(wall_s, drain_s=drain_s)
        sim.check_agreement()
        if entry["audit"]["lost"] or entry["audit"]["duplicates"]:
            raise AssertionError(
                f"finality {path}: audit failed: {entry['audit']}"
            )
        entry["verifier"] = "none"
        if drv.n_attr:
            mean_batch = 1e3 * drv.sum_batch / drv.n_attr
            mean_wave = 1e3 * drv.sum_wave / drv.n_attr
            mean_total = 1e3 * drv.sum_total / drv.n_attr
            snap = sim.processes[0].metrics.snapshot()
            attribution[path] = {
                "samples": drv.n_attr,
                # queueing: submit → block built (the batcher's hold)
                "batch_wait_ms_mean": round(mean_batch, 3),
                "batch_wait_ms_p50": round(
                    1e3 * drv.batch_wait.percentile(50), 3
                ),
                # wave lag: block built → a_deliver (admission + DAG
                # rounds + wave commit + flush); the host pump floor
                # rides inside it and is reported for context
                "wave_lag_ms_mean": round(mean_wave, 3),
                "wave_lag_ms_p50": round(
                    1e3 * drv.wave_lag.percentile(50), 3
                ),
                "total_ms_mean": round(mean_total, 3),
                "host_pump_ms_per_round": snap.get(
                    "host_pump_ms_per_round"
                ),
                "deadline_ms_effective": snap.get("deadline_ms_effective"),
            }
            if abs(mean_total - (mean_batch + mean_wave)) > 0.05:
                raise AssertionError(
                    f"finality {path}: attribution components do not "
                    f"sum to the measured total ({mean_batch:.3f} + "
                    f"{mean_wave:.3f} != {mean_total:.3f} ms)"
                )
        sides[path] = entry

    p50_on = sides["on"].get("submit_deliver_p50_ms")
    entry = {
        "nodes": n,
        "wall_s": wall_s,
        "offered_rate": rate,
        "identity": identity,
        # half 1 raises on divergence, so reaching here means the gate
        # held across the whole matrix
        "commit_order_identical": True,
        "off": sides["off"],
        "on": sides["on"],
        "attribution": attribution,
        "p50_under_1s": bool(p50_on is not None and p50_on < 1000.0),
    }
    if len(eager_lat):
        entry["submit_eager_p50_ms"] = round(
            1e3 * eager_lat.percentile(50), 3
        )
    return entry


def _lanes_ab_rung(
    n: int = 64,
    sizes: tuple = (131072, 524288, 2097152),
    cycles: int = 6,
    sweep: tuple = (1, 2, 4),
) -> dict:
    """ladder.lanes rung (ISSUE 17): sharded dissemination lanes —
    digest-only ordering with parallel payload workers — in two halves.

    Half 1 — the byte-identity gate: lanes-on vs inline lockstep sims
    over a seeded n × adversary × pump matrix must produce the same
    per-view commit order (round, source) AND the same delivered
    payload bytes (sha256 over the length-prefixed transaction stream,
    post lane-store resolution). RAISES AssertionError on any
    divergence — a recorded entry IS a passed gate.

    Half 2 — the throughput headline at ``n`` with Ed25519-signed
    vertices (verifier="cpu" — the keyless sim passes vertex objects by
    reference, so inline dissemination there is literally free and an
    A/B against it would be meaningless): committed payload bytes per
    second of ordering-path (pump) time, lanes vs inline, as block
    weight grows 16x. The pump is the metric because it is the claim —
    lanes exist to keep payload weight OFF the consensus critical path;
    signature verification is already coalesced/offloaded outside the
    pump window on both sides. Each burst is submitted and (lanes side)
    flushed before pumping — steady-state pipelining, where worker
    lanes disseminate a burst while ordering runs. ``throughput_2x``
    records the >=2x acceptance gate at 4 workers and the top block
    size; ``pump_flat_1p3x`` records lanes' host_pump_ms_per_round
    staying within 1.3x across the 16x size growth (inline's grows with
    block weight — that gap IS the win). A worker sweep at the top size
    rides alongside."""
    import hashlib
    import time as _t

    from dag_rider_tpu.config import Config
    from dag_rider_tpu.consensus.adversary import (
        ByzantineProcess,
        make_behavior,
    )
    from dag_rider_tpu.consensus.process import Process
    from dag_rider_tpu.consensus.simulator import Simulation
    from dag_rider_tpu.core.types import Block

    # -- half 1: identity gate over the seeded matrix ----------------------

    def identity_side(sz, seed, adversary, pump, lanes, id_cycles):
        cfg = Config(
            n=sz,
            coin="round_robin",
            propose_empty=True,
            pump=pump,
            lanes=lanes,
            lane_batch_bytes=256,
            sync_request_cooldown_s=0.0,
            sync_serve_cooldown_s=0.0,
            sync_patience=1,
        )
        nbyz = cfg.f if adversary else 0
        behaviors = {
            i: make_behavior(adversary, seed=seed + 1000 + i)
            for i in range(nbyz)
        }

        def factory(pcfg, i, ptp, **kwargs):
            if i in behaviors:
                return ByzantineProcess(
                    pcfg, i, ptp, behavior=behaviors[i], **kwargs
                )
            return Process(pcfg, i, ptp, **kwargs)

        sim = Simulation(
            cfg, process_factory=factory if behaviors else None
        )
        sim.submit_blocks(2, tx_bytes=600)  # above the 256-byte floor
        for _ in range(id_cycles):
            sim.run(max_messages=sz * (sz - 1))
        orders, digests = [], []
        for view in sim.deliveries[nbyz:]:
            orders.append([(v.id.round, v.id.source) for v in view])
            h = hashlib.sha256()
            for v in view:
                for tx in v.block.transactions:
                    h.update(len(tx).to_bytes(4, "little"))
                    h.update(tx)
            digests.append(h.hexdigest())
        return orders, digests, sim, nbyz

    id_matrix = (
        (4, 21, None, 12),
        (16, 22, "equivocate", 12),
        (16, 23, "lane_withhold", 12),
        (32, 24, None, 10),
    )
    identity = []
    for sz, seed, adversary, id_cycles in id_matrix:
        for pump in ("scalar", "vector"):
            ref_o, ref_d, _, nbyz = identity_side(
                sz, seed, adversary, pump, False, id_cycles
            )
            lane_o, lane_d, sim, _ = identity_side(
                sz, seed, adversary, pump, True, id_cycles
            )
            if not any(ref_o):
                raise AssertionError(
                    f"lanes identity n={sz} {adversary} {pump}: oracle "
                    "delivered nothing — vacuous gate"
                )
            if ref_o != lane_o:
                raise AssertionError(
                    f"lanes identity n={sz} {adversary} {pump}: commit "
                    "order diverged from the inline oracle"
                )
            if ref_d != lane_d:
                raise AssertionError(
                    f"lanes identity n={sz} {adversary} {pump}: "
                    "delivered payload bytes diverged from the oracle"
                )
            certified = sum(
                p.metrics.counters.get("lane_batches_certified", 0)
                for p in sim.processes
            )
            if adversary != "lane_withhold" and not certified:
                raise AssertionError(
                    f"lanes identity n={sz} {adversary} {pump}: no "
                    "batch ever certified — blocks shipped inline, "
                    "vacuous gate"
                )
            identity.append(
                {
                    "n": sz,
                    "seed": seed,
                    "adversary": adversary or "clean",
                    "pump": pump,
                    "delivered_view0": len(ref_o[0]),
                    "lane_batches_certified": certified,
                }
            )

    # -- half 2: committed-bytes/s per pump-second at n --------------------

    def tput_side(size, lanes, workers):
        import gc

        # drain the previous side's multi-hundred-MB object graph before
        # timing this one — a generational collection landing mid-pump
        # charges the victim side a triple-digit-ms pause it didn't earn
        gc.collect()
        cfg = Config(
            n=n, lanes=lanes, lane_workers=workers, lane_batch_bytes=4096
        )
        sim = Simulation(cfg, verifier="cpu")
        p0 = sim.processes[0]
        acc = {"bytes": 0, "txs": 0}

        def on_dlv(v, acc=acc):
            for tx in v.block.transactions:
                acc["bytes"] += len(tx)
                acc["txs"] += 1

        p0.on_deliver = on_dlv
        # borrow the collector off for the timed box (restored in
        # finally): both sides get the same allocator behavior and no
        # side eats a mid-pump generational pause
        gc_was = gc.isenabled()
        gc.disable()
        try:
            t0 = _t.perf_counter()
            for c in range(cycles):
                for p in sim.processes:
                    p.submit(
                        Block(
                            (
                                f"c{c}-p{p.index}".encode().ljust(
                                    size, b"."
                                ),
                            )
                        )
                    )
                if lanes and sim.lane_bus is not None:
                    # steady-state pipelining: the worker lanes finish
                    # disseminating the burst before ordering pumps it
                    # (in sustained operation this overlaps the previous
                    # burst's ordering)
                    sim.lane_bus.flush()
                sim.run(max_messages=2 * n * n)
            sim.run(max_messages=4 * n * n)
            wall = _t.perf_counter() - t0
        finally:
            if gc_was:
                gc.enable()
        m = p0.metrics
        # land delivered bytes in the metrics seam so the snapshot
        # derives the committed_bytes_per_s gauge (the same path a
        # mempool-fronted node exercises)
        m.observe_mempool({"delivered_bytes": acc["bytes"]})
        snap = m.snapshot()
        return {
            "delivered_txs": acc["txs"],
            "delivered_bytes": acc["bytes"],
            "wall_s": round(wall, 2),
            "host_pump_ms_per_round": snap.get(
                "host_pump_ms_per_round"
            ),
            "committed_bytes_per_s": snap.get("committed_bytes_per_s"),
        }

    def best_of(runs, size, lanes, workers):
        # best-of-k by pump floor: the box this runs on shares its core,
        # and a neighbor's burst landing mid-pump inflates one run's
        # floor by triple-digit ms; the minimum is the reproducible cost
        best = None
        for _ in range(runs):
            side = tput_side(size, lanes, workers)
            if (
                best is None
                or side["host_pump_ms_per_round"]
                < best["host_pump_ms_per_round"]
            ):
                best = side
        return best

    ab = []
    for size in sizes:
        inline = best_of(2, size, False, 4)
        laned = best_of(2, size, True, 4)
        if inline["delivered_txs"] != laned["delivered_txs"]:
            raise AssertionError(
                f"lanes A/B size={size}: delivered tx counts diverged "
                f"({inline['delivered_txs']} vs {laned['delivered_txs']})"
            )
        ratio = (
            laned["committed_bytes_per_s"]
            / inline["committed_bytes_per_s"]
            if inline["committed_bytes_per_s"]
            else 0.0
        )
        ab.append(
            {
                "block_bytes": size,
                "inline": inline,
                "lanes": laned,
                "committed_bytes_ratio": round(ratio, 2),
            }
        )

    workers_sweep = []
    for w in sweep:
        side = tput_side(sizes[-1], True, w)
        workers_sweep.append(
            {
                "workers": w,
                "wall_s": side["wall_s"],
                "host_pump_ms_per_round": side["host_pump_ms_per_round"],
                "committed_bytes_per_s": side["committed_bytes_per_s"],
            }
        )

    lane_pumps = [e["lanes"]["host_pump_ms_per_round"] for e in ab]
    flatness = (
        max(lane_pumps) / min(lane_pumps) if min(lane_pumps) else 0.0
    )
    top = ab[-1]
    return {
        "nodes": n,
        "block_bytes": list(sizes),
        "cycles": cycles,
        "verifier": "cpu",
        "identity": identity,
        # half 1 raises on divergence, so reaching here means both
        # gates held across the whole matrix
        "commit_order_identical": True,
        "delivered_bytes_identical": True,
        "ab": ab,
        "workers_sweep": workers_sweep,
        "committed_bytes_ratio_top": top["committed_bytes_ratio"],
        "throughput_2x": top["committed_bytes_ratio"] >= 2.0,
        "lane_pump_flatness": round(flatness, 2),
        "pump_flat_1p3x": bool(flatness and flatness <= 1.3),
    }


def _agg_ladder_rung(sizes=(64, 256)) -> dict:
    """verify_n256_agg ladder rung (round 13): component costs of the
    aggregated round-certificate check at committee quorums vs the
    per-vertex ed25519 reference.

    Honesty notes on "flat in n": what is flat is the signature-OP count
    (one aggregate check per round regardless of n, vs n per-vertex
    verifies) and the per-vertex-AMORTIZED check cost (``agg_check_warm_s
    / n`` — the shared Miller squarings and the single final
    exponentiation amortize over a bigger round). The raw host check
    still grows with the pair count — sublinearly (4x pairs should cost
    well under 4x wall; that ratio is ``agg_check_growth``) but it
    grows; the device-work claim is carried by the op counts and the MSM
    seam, not by host pairing wall time."""
    import hashlib
    import time as _t

    from dag_rider_tpu.crypto import bls12381 as _bls
    from dag_rider_tpu.crypto import ed25519 as _ed
    from dag_rider_tpu.ops import bls_msm as _msm
    from dag_rider_tpu.verifier.base import CertSigner, KeyRegistry
    from dag_rider_tpu.verifier.cert import CertVerifier

    entry: dict = {"sizes": {}}
    for n in sizes:
        q = _quorum(n)
        reg, _seeds, sks = KeyRegistry.generate_with_cert(n)
        cv = CertVerifier(reg, q, msm="host")
        digests = [
            hashlib.sha256(b"agg-rung|%d|%d" % (n, i)).digest()
            for i in range(q)
        ]
        signers = [CertSigner(sk) for sk in sks[:q]]
        t0 = _t.monotonic()
        shares = [
            s.sign_digest(d) for s, d in zip(signers, digests)
        ]
        sign_s = _t.monotonic() - t0
        t0 = _t.monotonic()
        cert = cv.make_certificate(
            1, list(zip(range(q), digests, shares))
        )
        assemble_s = _t.monotonic() - t0
        # the device MSM seam must land on the host group-law point;
        # compile outside the timed box (each padded batch size is its
        # own program) and report the warm dispatch. The half is
        # skippable: on the 1-core fallback the compile alone can eat
        # minutes at the n=256 padding.
        size_entry_extra: dict = {}
        if os.environ.get("DAGRIDER_BENCH_AGG_DEVMSM", "1") == "1":
            pts = [_bls.g1_decompress(s) for s in shares]
            t0 = _t.monotonic()
            dev_pt = _msm.sum_points(pts)  # compile + run
            compile_s = _t.monotonic() - t0
            t0 = _t.monotonic()
            dev_pt = _msm.sum_points(pts)
            msm_device_s = _t.monotonic() - t0
            msm_match = _bls.g1_compress(dev_pt) == cert.agg_sig
            size_entry_extra = {
                "msm_device_ms": round(msm_device_s * 1000, 1),
                "msm_device_compile_s": round(compile_s, 1),
                "msm_match": msm_match,
            }
        else:
            msm_match = True
        # _check (not verify_certificate): the memo would turn the warm
        # timings into dict hits
        t0 = _t.monotonic()
        ok_cold = cv._check(cert)
        cold_s = _t.monotonic() - t0
        warms = []
        for _ in range(2):
            t0 = _t.monotonic()
            ok_warm = cv._check(cert)
            warms.append(_t.monotonic() - t0)
        warm_s = min(warms)
        if not (ok_cold and ok_warm and msm_match):
            raise AssertionError(
                f"agg rung n={n}: check/MSM disagreement "
                f"(cold={ok_cold} warm={ok_warm} msm={msm_match})"
            )
        # per-vertex reference: the n ed25519 verifies the round costs
        # every receiver without the certificate
        esk, epk = _ed.generate_keypair(
            hashlib.sha256(b"agg-rung-ed|%d" % n).digest()
        )
        msgs = [
            hashlib.sha256(b"agg-rung-msg|%d|%d" % (n, i)).digest()
            for i in range(n)
        ]
        esigs = [_ed.sign(esk, m) for m in msgs]
        _ed.verify(epk, msgs[0], esigs[0])  # warm the comb tables
        t0 = _t.monotonic()
        for m, s in zip(msgs, esigs):
            if not _ed.verify(epk, m, s):
                raise AssertionError("ed25519 reference verify failed")
        ref_s = _t.monotonic() - t0
        entry["sizes"][str(n)] = {
            "quorum": q,
            "pairs": q + 1,
            "share_sign_ms_per_vertex": round(sign_s / q * 1000, 2),
            "assemble_ms": round(assemble_s * 1000, 1),
            **size_entry_extra,
            "agg_check_cold_s": round(cold_s, 3),
            "agg_check_warm_s": round(warm_s, 3),
            "agg_check_ms_per_vertex": round(warm_s / n * 1000, 2),
            "per_vertex_ed25519_s": round(ref_s, 3),
            "per_vertex_ms_per_sig": round(ref_s / n * 1000, 2),
            "verify_ops_agg": 1,
            "verify_ops_per_vertex": n,
        }
    lo, hi = str(sizes[0]), str(sizes[-1])
    a, b = entry["sizes"][lo], entry["sizes"][hi]
    entry["pairs_growth"] = round(b["pairs"] / a["pairs"], 2)
    entry["agg_check_growth"] = round(
        b["agg_check_warm_s"] / a["agg_check_warm_s"], 2
    )
    entry["per_vertex_growth"] = round(
        b["per_vertex_ed25519_s"] / a["per_vertex_ed25519_s"], 2
    )
    # the acceptance headline: per-round verify cost amortized per
    # vertex stays ~flat (within 2x) on the agg path while the
    # per-vertex path pays linearly more ops
    entry["agg_ms_per_vertex_growth"] = round(
        b["agg_check_ms_per_vertex"] / a["agg_check_ms_per_vertex"], 2
    )
    entry["agg_per_vertex_flat_within_2x"] = (
        entry["agg_ms_per_vertex_growth"] <= 2.0
    )
    entry["verify_ops_growth_agg"] = 1.0
    return entry


def _cert_ab_rung(n: int, blocks: int = 6) -> dict:
    """Aggregated-certificate sim A/B (round 13): paired cert-on /
    cert-off runs — same committee, same blocks, same vector pump, same
    shared CPU-oracle verifier — compared delivery-log to delivery-log.
    ``sigs_device`` sums each process's requested verify dispatches
    (``verify_sigs_total``, counted BEFORE the in-process cluster's
    cross-process dedup — i.e. what every node's own device pays in a
    real deployment, n-1 per round per receiver); the certificate path
    must cut the cluster-wide count by ~n while the commit order stays
    byte-identical. Raises on divergence."""
    import time as _t

    from dag_rider_tpu.config import Config
    from dag_rider_tpu.consensus.simulator import Simulation
    from dag_rider_tpu.core.types import Block

    sides: dict = {}
    orders: dict = {}
    for mode in ("per_vertex", "agg"):
        cfg = Config(
            n=n, coin="round_robin", propose_empty=False, pump="vector"
        )
        sim = Simulation(cfg, verifier="cpu", cert=(mode == "agg"))
        for i in range(n):
            for k in range(blocks):
                sim.processes[i].submit(
                    Block((f"p{i}-blk{k}".encode().ljust(32, b"."),))
                )
        t0 = _t.monotonic()
        sim.run(max_messages=100 * n * n)
        dt = _t.monotonic() - t0
        sim.check_agreement()
        snaps = [p.metrics.snapshot() for p in sim.processes]
        orders[mode] = [
            [(v.id, v.digest()) for v in d] for d in sim.deliveries
        ]
        sides[mode] = {
            "seconds": round(dt, 2),
            "sigs_device": sum(
                s.get("verify_sigs_total", 0) for s in snaps
            ),
            "certs_assembled": sum(
                s.get("certs_assembled", 0) for s in snaps
            ),
            "certs_rejected": sum(
                s.get("certs_rejected", 0) for s in snaps
            ),
            "cert_timeouts": sum(
                s.get("cert_timeouts", 0) for s in snaps
            ),
            "sigs_saved": sum(s.get("sigs_saved", 0) for s in snaps),
            "cert_fastpath_fraction": round(
                sum(s.get("cert_fastpath_fraction", 0.0) for s in snaps)
                / len(snaps),
                4,
            ),
            "max_round": max(p.round for p in sim.processes),
            "vertices_delivered_total": sum(
                len(d) for d in sim.deliveries
            ),
        }
    identical = orders["per_vertex"] == orders["agg"]
    ref_sigs = max(sides["per_vertex"]["sigs_device"], 1)
    entry = {
        "nodes": n,
        "blocks_per_process": blocks,
        "per_vertex": sides["per_vertex"],
        "agg": sides["agg"],
        "commit_order_identical": identical,
        "sigs_device_drop": round(
            ref_sigs / max(sides["agg"]["sigs_device"], 1), 1
        ),
    }
    if not identical:
        raise AssertionError(
            f"sim{n}_agg: certificate path diverged from per-vertex "
            "commit order"
        )
    return entry


def _cert_phase2_rung(n: int = 256, span: int = 4) -> dict:
    """cert_phase2 ladder rung (ISSUE 12): the three stacked certificate
    optimizations priced against their own oracles.

    - sign: the round's quorum of share signatures, sequential host loop
      vs sign_many through the native cffi Montgomery kernels (the
      toolchain is warmed OUTSIDE the timed region — round-14 lesson:
      an unwarmed first call times the ~0.7s cffi compile, not the
      math). Acceptance: >=3x at the n=256 quorum. The device lane is
      the same seam on the field381 limb kernels; its local numbers are
      compile-dominated, so it rides behind DAGRIDER_BENCH_CERT2_DEV=1
      with byte-identity asserted whenever it runs.
    - assemble: aggregator-side cost with and without the pre-gossip
      self-check (DAGRIDER_CERT_SELFCHECK both ways).
    - span_replay: the cert-of-certs catch-up story — a fresh consumer
      settling R rounds through R/span combined checks; acceptance is
      pairing_checks/round < 1 with the spans restating exactly the
      per-round claims.
    - sim: live span-on / span-off / cert-off triple A/B at a small
      committee, byte-identical commit order required.
    """
    import hashlib
    import time as _t

    from dag_rider_tpu.crypto import bls12381 as _bls
    from dag_rider_tpu.verifier.base import CertSigner, KeyRegistry
    from dag_rider_tpu.verifier.cert import CertVerifier

    entry: dict = {"nodes": n, "span": span}

    # -- share signing: sequential vs batched native ---------------------
    q = _quorum(n)
    reg, _seeds, sks = KeyRegistry.generate_with_cert(n)
    digests = [
        hashlib.sha256(b"cert2-rung|%d|%d" % (n, i)).digest()
        for i in range(q)
    ]
    qsks = sks[:q]
    signers = [CertSigner(sk) for sk in qsks]
    t0 = _t.monotonic()
    seq = [s.sign_digest(d) for s, d in zip(signers, digests)]
    host_s = _t.monotonic() - t0
    from dag_rider_tpu.ops import native381 as _nat

    native_ready = _nat.available()  # compile OUTSIDE the timed region
    if native_ready:
        _bls.sign_many(qsks[:2], digests[:2], backend="native")  # warm
    t0 = _t.monotonic()
    batched = _bls.sign_many(qsks, digests, backend="native")
    native_s = _t.monotonic() - t0
    if batched != seq:
        raise AssertionError("cert2 rung: sign_many diverged from sign")
    entry["sign"] = {
        "quorum": q,
        "native_toolchain": native_ready,
        "host_ms_per_vertex": round(host_s / q * 1000, 2),
        "native_ms_per_vertex": round(native_s / q * 1000, 2),
        "native_speedup_x": round(host_s / max(native_s, 1e-9), 2),
    }
    if os.environ.get("DAGRIDER_BENCH_CERT2_DEV", "") == "1":
        dev_sks, dev_digests = qsks[:8], digests[:8]
        dev = _bls.sign_many(dev_sks, dev_digests, backend="device")
        t0 = _t.monotonic()
        dev = _bls.sign_many(dev_sks, dev_digests, backend="device")
        dev_s = _t.monotonic() - t0
        if dev != seq[:8]:
            raise AssertionError("cert2 rung: device sign diverged")
        entry["sign"]["device_ms_per_vertex_warm"] = round(
            dev_s / 8 * 1000, 2
        )
    else:
        entry["sign"]["device_note"] = (
            "device lane byte-identity is pinned by tests/"
            "test_cert_phase2.py; local wall time is compile-dominated "
            "(DAGRIDER_BENCH_CERT2_DEV=1 to time the warm dispatch)"
        )

    # -- assembly: self-check on vs off ----------------------------------
    cv = CertVerifier(reg, q, msm="host")
    entries_q = list(zip(range(q), digests, seq))
    t0 = _t.monotonic()
    cert = cv.make_certificate(1, entries_q)
    assemble_s = _t.monotonic() - t0
    t0 = _t.monotonic()
    if not cv._check(cert):
        raise AssertionError("cert2 rung: assembled certificate invalid")
    selfcheck_s = _t.monotonic() - t0
    entry["assemble"] = {
        "assemble_ms": round(assemble_s * 1000, 1),
        "selfcheck_ms": round(selfcheck_s * 1000, 1),
        "assemble_with_selfcheck_ms": round(
            (assemble_s + selfcheck_s) * 1000, 1
        ),
    }

    # -- span replay: R rounds settled in R/span combined checks ---------
    sn = 16
    sq = _quorum(sn)
    sreg, _sseeds, ssks = KeyRegistry.generate_with_cert(sn)
    maker = CertVerifier(sreg, sq, msm="host")
    epochs = 2
    rounds = span * epochs
    certs = []
    for r in range(1, rounds + 1):
        ds = [
            hashlib.sha256(b"cert2-span|%d|%d" % (r, i)).digest()
            for i in range(sq)
        ]
        shares = _bls.sign_many(ssks[:sq], ds, backend="native")
        certs.append(
            maker.make_certificate(r, list(zip(range(sq), ds, shares)))
        )
    spans = [
        maker.make_span(e * span + 1, certs[e * span : (e + 1) * span])
        for e in range(epochs)
    ]
    consumer = CertVerifier(sreg, sq, msm="host")
    t0 = _t.monotonic()
    if not all(consumer.verify_span(s) for s in spans):
        raise AssertionError("cert2 rung: span replay verify failed")
    span_s = _t.monotonic() - t0
    per_round = CertVerifier(sreg, sq, msm="host")
    t0 = _t.monotonic()
    if not all(per_round.verify_certificate(c) for c in certs):
        raise AssertionError("cert2 rung: per-round replay verify failed")
    round_s = _t.monotonic() - t0
    entry["span_replay"] = {
        "nodes": sn,
        "rounds": rounds,
        "pairing_checks_span": consumer.stats["pairing_checks"],
        "pairing_checks_per_round": round(
            consumer.stats["pairing_checks"] / rounds, 3
        ),
        "pairing_checks_per_round_cert_path": round(
            per_round.stats["pairing_checks"] / rounds, 3
        ),
        "span_replay_s": round(span_s, 3),
        "per_round_replay_s": round(round_s, 3),
        "replay_speedup_x": round(round_s / max(span_s, 1e-9), 2),
    }

    # -- live sim: span-on / span-off / cert-off triple A/B --------------
    from dag_rider_tpu.config import Config
    from dag_rider_tpu.consensus.simulator import Simulation
    from dag_rider_tpu.core.types import Block

    sides: dict = {}
    orders: dict = {}
    for mode in ("per_vertex", "cert", "span"):
        cfg = Config(
            n=sn,
            coin="round_robin",
            propose_empty=False,
            pump="vector",
            cert_span=span if mode == "span" else 0,
        )
        sim = Simulation(cfg, verifier="cpu", cert=(mode != "per_vertex"))
        for i in range(sn):
            for k in range(6):
                sim.processes[i].submit(
                    Block((f"c2-p{i}-b{k}".encode().ljust(32, b"."),))
                )
        t0 = _t.monotonic()
        sim.run(max_messages=100 * sn * sn)
        dt = _t.monotonic() - t0
        sim.check_agreement()
        snaps = [p.metrics.snapshot() for p in sim.processes]
        orders[mode] = [
            [(v.id, v.digest()) for v in d] for d in sim.deliveries
        ]
        side = {
            "seconds": round(dt, 2),
            "sigs_device": sum(
                s.get("verify_sigs_total", 0) for s in snaps
            ),
            "max_round": max(p.round for p in sim.processes),
        }
        if mode != "per_vertex":
            side["certs_assembled"] = sum(
                s.get("certs_assembled", 0) for s in snaps
            )
            side["pairing_checks"] = sim.cert_verifier.stats[
                "pairing_checks"
            ]
        if mode == "span":
            side["spans_assembled"] = sum(
                s.get("spans_assembled", 0) for s in snaps
            )
            side["span_rounds_settled"] = sum(
                s.get("span_rounds_settled", 0) for s in snaps
            )
        sides[mode] = side
    identical = orders["per_vertex"] == orders["cert"] == orders["span"]
    entry["sim"] = {
        "nodes": sn,
        "per_vertex": sides["per_vertex"],
        "cert": sides["cert"],
        "span": sides["span"],
        "commit_order_identical": identical,
    }
    if not identical:
        raise AssertionError(
            "cert_phase2: span path diverged from per-round/per-vertex "
            "commit order"
        )
    return entry


def _cluster_e2e_rung(
    n: int = 4,
    load_s: float = 6.0,
    rate: float = 300.0,
    transport: str = "uds",
    seed: int = 7,
    boot_s: float = 15.0,
) -> dict:
    """Ladder rung (ISSUE 19): the full stack as n separate OS processes
    over real sockets. Two cells:

    - **clean**: boot n nodes, drive seeded open-loop load through the
      wire-level Submit door, stop, audit. Reports committed-tx/s and
      wire submit→deliver p50/p99.
    - **kill_rejoin**: same load, but one node (seeded pick, never the
      client's primary) gets a genuine SIGKILL mid-load, then restarts
      from its checkpoint + WAL and rejoins via snapshot sync.

    Gates (the rung RAISES on any): both audits clean — commit-order
    agreement (rejoiner checked as an order-preserving embedding), zero
    lost accepted transactions, no duplicate delivery, liveness, empty
    flight recorders; byte-identical committed prefix across the steady
    nodes of each cell; the kill cell genuinely killed and restarted;
    and the clean cell committed something.
    """
    import shutil
    import tempfile
    import threading as _th

    from dag_rider_tpu.cluster import audit as _caudit
    from dag_rider_tpu.cluster import client as _cclient
    from dag_rider_tpu.cluster.directory import build_cluster
    from dag_rider_tpu.cluster.supervisor import (
        ClusterSupervisor,
        seeded_kill_plan,
    )

    def _cell(name: str, plan: list) -> dict:
        root = tempfile.mkdtemp(prefix=f"dagrider-bench-{name}-")
        spec = build_cluster(root, n, transport=transport, seed=seed)
        sup = ClusterSupervisor(spec)
        t0 = time.monotonic()
        sup.start_all()
        not_ready = sup.wait_ready(boot_s)
        if not_ready:
            sup.stop_all()
            raise AssertionError(
                f"cluster_e2e {name}: nodes {not_ready} not ready in "
                f"{boot_s}s (workspace kept at {root})"
            )
        boot_wall = time.monotonic() - t0
        load: dict = {}
        loader = _th.Thread(
            target=lambda: load.update(
                _cclient.drive_load(
                    spec, duration_s=load_s, rate=rate, seed=seed
                )
            ),
            daemon=True,
        )
        loader.start()
        executed = sup.run_plan(plan)
        loader.join(timeout=load_s + 60)
        if executed:
            sup.wait_ready(boot_s)
        _th.Event().wait(1.5)  # settle: let in-flight waves commit
        forced = sup.stop_all()
        report = _caudit.audit_cluster(
            spec, restarted=sup.restart_counts.keys()
        )
        # byte-identical committed prefix across the steady nodes (a
        # rejoiner's log — supervised restart or an audit-detected
        # mid-run state transfer — has a legitimate recovery gap and is
        # covered by the embedding check inside the audit)
        steady = [
            i for i in range(n) if i not in report["rejoined"]
        ] or list(range(n))
        recs = {
            i: _caudit._records(
                _caudit.read_delivery_log(spec.nodes[i].delivery_log)
            )
            for i in steady
        }
        k = min(len(r) for r in recs.values())
        prefix_identical = (
            len({tuple(r[:k]) for r in recs.values()}) == 1
        )
        entry = {
            "nodes": n,
            "transport": transport,
            "boot_s": round(boot_wall, 2),
            "load": load,
            "fault_plan": executed,
            "kills": dict(sup.kill_counts),
            "restarts": dict(sup.restart_counts),
            "forced_stops": forced,
            "ok": report["ok"],
            "violations": report["violations"],
            "accepted_tx": report["accepted_tx"],
            "delivered_tx": report["delivered_tx"],
            "in_flight_tx": report["in_flight_tx"],
            "lost_tx": report["lost_tx"],
            "duplicate_tx": report["duplicate_tx"],
            "decided_waves": report["decided_waves"],
            "flight_dump_files": report["flight_dump_files"],
            "committed_tx_per_sec": round(
                report["delivered_tx"] / load_s, 1
            ),
            "prefix_identical": prefix_identical,
            "common_prefix_len": k,
        }
        for key in (
            "submit_deliver_p50_ms",
            "submit_deliver_p99_ms",
            "latency_samples",
        ):
            if key in report:
                entry[key] = report[key]
        if report["ok"] and prefix_identical:
            shutil.rmtree(root, ignore_errors=True)
        else:
            entry["workspace"] = root  # kept for post-mortem
        return entry

    clean = _cell("clean", [])
    kill_at = max(1.0, min(2.0, load_s / 3))
    kill = _cell(
        "kill",
        seeded_kill_plan(
            seed, n, kill_at_s=kill_at, restart_after_s=1.5
        ),
    )
    entry = {"clean": clean, "kill_rejoin": kill}
    for name, cell in entry.items():
        if not cell["ok"]:
            raise AssertionError(
                f"cluster_e2e {name} audit failed: {cell['violations']}"
            )
        if not cell["prefix_identical"]:
            raise AssertionError(
                f"cluster_e2e {name}: steady commit prefixes diverge "
                f"(common len {cell['common_prefix_len']})"
            )
    if clean["delivered_tx"] <= 0:
        raise AssertionError(f"cluster_e2e clean committed nothing: {clean}")
    if not kill["kills"] or not kill["restarts"]:
        raise AssertionError(
            f"cluster_e2e kill cell never killed/restarted: {kill}"
        )
    if kill["lost_tx"]:
        raise AssertionError(
            f"cluster_e2e: {kill['lost_tx']} accepted transactions lost "
            f"across kill -9 + rejoin"
        )
    return entry


def _epoch_join_cell(
    n: int,
    load_s: float,
    rate: float,
    seed: int,
    boot_s: float,
    catchup_s: float = 120.0,
) -> dict:
    """Mid-run join from a span-attested snapshot, as real OS
    processes: n-1 nodes boot with epochs + span certs on, a rotate op
    is committed through the wire Submit door, and the last node starts
    only after the survivors have GC'd past its genesis — forcing a
    state transfer it can ONLY satisfy from the attested snapshot."""
    import math
    import shutil
    import tempfile
    import threading as _th

    from dag_rider_tpu.cluster import audit as _caudit
    from dag_rider_tpu.cluster import client as _cclient
    from dag_rider_tpu.cluster.directory import build_cluster
    from dag_rider_tpu.cluster.supervisor import ClusterSupervisor
    from dag_rider_tpu.core.codec import encode_epoch_op
    from dag_rider_tpu.core.types import EpochOp

    # k_span=2, NOT 4: round r's cert aggregator is r % n, so while the
    # joiner is absent every n-th round degrades to per-vertex verifies.
    # A span window aligned with that stride (k=n=4) always contains a
    # degraded round and never settles; k=2 keeps every other window
    # settling, so the donor has a live span chain to attest with
    k_span = 2
    gc_depth = 16
    root = tempfile.mkdtemp(prefix="dagrider-bench-epochjoin-")
    spec = build_cluster(
        root,
        n,
        transport="uds",
        seed=seed,
        gc_depth=gc_depth,
        # patience is quiescent pump ticks (~ms): socket-distributed
        # share aggregation needs seconds, not the in-process default
        node_overrides={"cert": "agg", "cert_patience": 2000},
    )
    sup = ClusterSupervisor(
        spec,
        env={
            "DAGRIDER_EPOCH": "1",
            "DAGRIDER_EPOCH_WAVES": "4",
            "DAGRIDER_CERT_SPAN": str(k_span),
            # share signing dominates the cert path at wall-clock round
            # rates; the compiled lane keeps certs (and therefore
            # spans) assembling at socket speed
            "DAGRIDER_CERT_SIGN": "native",
        },
    )
    joiner = n - 1
    for i in range(n - 1):
        sup.start(i)
    not_ready = sup.wait_ready(boot_s)
    if not_ready:
        sup.stop_all()
        raise AssertionError(
            f"epoch join: nodes {not_ready} not ready in {boot_s}s "
            f"(workspace kept at {root})"
        )
    # commit one rotate op through the wire front door, and ledger it so
    # the audit's zero-loss accounting covers control traffic too
    op = encode_epoch_op(EpochOp("rotate", joiner, seed, b""))
    cli = _cclient.SubmitClient(spec)
    verdict = None
    for _ in range(50):
        verdict = cli.submit(0, "epochctl", op)
        if verdict and (verdict["accepted"] or verdict["deduped"]):
            break
        _th.Event().wait(0.1)
    cli.close()
    if not verdict or not (verdict["accepted"] or verdict["deduped"]):
        sup.stop_all()
        raise AssertionError(f"epoch join: rotate op never acked: {verdict}")
    with open(spec.accepted_log, "a", buffering=1) as fh:
        fh.write(
            json.dumps(
                {
                    "tx": op.hex(),
                    "ts": time.time(),
                    "node": verdict["node"],
                    "client": "epochctl",
                }
            )
            + "\n"
        )
    load: dict = {}
    loader = _th.Thread(
        target=lambda: load.update(
            _cclient.drive_load(spec, duration_s=load_s, rate=rate, seed=seed)
        ),
        daemon=True,
    )
    loader.start()
    # start the joiner only once the survivors' committed frontier is
    # past gc_depth: its genesis rounds are pruned everywhere, so plain
    # window sync CANNOT answer — only the attested snapshot can. The
    # cert path runs at pairing speed, so rounds take ~1s of wall clock
    # here; the survivors keep advancing (empty-proposing) after the
    # load drains, hence the window is much wider than load_s.
    deadline = time.monotonic() + load_s + 90.0
    survivor_round = 0
    while time.monotonic() < deadline:
        log = _caudit.read_delivery_log(spec.nodes[0].delivery_log)
        survivor_round = max((rec["r"] for rec in log), default=0)
        if survivor_round > gc_depth + 8:
            break
        _th.Event().wait(0.25)
    if survivor_round <= gc_depth + 8:
        sup.stop_all()
        raise AssertionError(
            f"epoch join: survivors never committed past the joiner "
            f"horizon (round {survivor_round} <= {gc_depth + 8}; "
            f"workspace kept at {root})"
        )
    sup.start(joiner)
    loader.join(timeout=load_s + 60)
    not_ready = sup.wait_ready(boot_s)
    if not_ready:
        sup.stop_all()
        raise AssertionError(
            f"epoch join: joiner never ready (workspace kept at {root})"
        )
    # the survivors stay live (empty-proposing) after the load drains:
    # hold the cluster up until the joiner has COMMITTED past the
    # frontier it joined behind — boot (~10s of interpreter + jax),
    # nack accrual, the snapshot fetch/restore, and then a full wave
    # past the restored round all happen inside this window, at ~1s
    # per round of cert-path wall clock
    catch_deadline = time.monotonic() + catchup_s
    while time.monotonic() < catch_deadline:
        jlog = _caudit.read_delivery_log(spec.nodes[joiner].delivery_log)
        if jlog and max(rec["r"] for rec in jlog) >= survivor_round:
            break
        _th.Event().wait(0.5)
    _th.Event().wait(1.5)  # settle: let in-flight waves commit
    sup.stop_all()
    report = _caudit.audit_cluster(spec, restarted=[joiner])
    finals = {
        i: _caudit.read_final(spec.nodes[i].final_report) or {}
        for i in range(n)
    }
    epochs = {
        i: int(finals[i].get("metrics", {}).get("epoch_current", 0))
        for i in range(n)
    }
    jm = finals[joiner].get("metrics", {})
    spans_verified = int(jm.get("snapshot_spans_verified", 0))
    pairing = int(jm.get("snapshot_pairing_checks", 0))
    join_round = int(finals[joiner].get("round", 0))
    budget = math.ceil(max(1, join_round) / k_span)
    entry = {
        "nodes": n,
        "survivor_round_at_join": survivor_round,
        "load": load,
        "ok": report["ok"],
        "violations": report["violations"],
        "accepted_tx": report["accepted_tx"],
        "delivered_tx": report["delivered_tx"],
        "lost_tx": report["lost_tx"],
        "duplicate_tx": report["duplicate_tx"],
        "joiner_delivered": report["log_lengths"].get(joiner, 0),
        "epochs": epochs,
        "snapshot_spans_verified": spans_verified,
        "snapshot_pairing_checks": pairing,
        "pairing_budget": budget,
    }
    ok = (
        report["ok"]
        and entry["joiner_delivered"] > 0
        and spans_verified > 0
        and pairing <= budget
        and min(epochs.values()) >= 1
        and len(set(epochs.values())) == 1
    )
    if ok:
        shutil.rmtree(root, ignore_errors=True)
    else:
        entry["workspace"] = root  # kept for post-mortem
    if not report["ok"]:
        raise AssertionError(f"epoch join audit failed: {report['violations']}")
    if entry["joiner_delivered"] <= 0:
        raise AssertionError(f"epoch join: joiner committed nothing: {entry}")
    if spans_verified <= 0:
        raise AssertionError(
            f"epoch join: joiner never verified a span — state transfer "
            f"took the unattested path: {entry}"
        )
    if pairing > budget:
        raise AssertionError(
            f"epoch join: {pairing} pairing checks over the "
            f"ceil(round/k_span)={budget} budget: {entry}"
        )
    if min(epochs.values()) < 1 or len(set(epochs.values())) != 1:
        raise AssertionError(f"epoch join: epochs disagree: {epochs}")
    return entry


def _epoch_rotate_ab_cell(seed: int) -> dict:
    """Key-rotation acceptance, in-process with REAL per-process
    threshold coins (independent share books, shared initial dealer
    keys): an epoch boundary rotates every share key in lockstep, the
    cluster keeps deciding waves on the rotated keys, and the committed
    prefix up to the boundary is byte-identical to a static-membership
    run fed the same transactions — including the control op itself
    (zero lost acked txs)."""
    from dag_rider_tpu import Config
    from dag_rider_tpu.consensus import Simulation
    from dag_rider_tpu.consensus.coin import ThresholdCoin
    from dag_rider_tpu.core import codec
    from dag_rider_tpu.core.types import Block, EpochOp
    from dag_rider_tpu.crypto import threshold as th

    n, wl = 4, 4
    keys = th.ThresholdKeys.generate(n, (n - 1) // 3 + 1, seed=b"bench-ab")
    op = codec.encode_epoch_op(EpochOp("rotate", 0, seed, b""))

    def run(epoch_on: bool) -> Simulation:
        cfg = Config(
            n=n,
            coin="threshold_bls",
            propose_empty=True,
            epoch=epoch_on,
            epoch_waves=4,
            epoch_rotate="seed",
        )
        sim = Simulation(
            cfg, coin_factory=lambda i: ThresholdCoin(keys, i, n)
        )
        sim.submit_blocks(per_process=2)
        sim.processes[0].submit(Block((op,)))
        for _ in range(900):
            done = min(p.decided_wave for p in sim.processes) >= 5 and (
                not epoch_on
                or min(p.epoch_mgr.epoch for p in sim.processes) >= 1
            )
            if done:
                break
            sim.run(max_messages=300)
        else:
            raise AssertionError(
                f"epoch rotate_ab: run(epoch={epoch_on}) never settled"
            )
        sim.check_agreement()
        return sim

    rot = run(True)
    static = run(False)
    rotations = min(
        p.metrics.counters["epoch_rotations"] for p in rot.processes
    )
    if rotations < 1:
        raise AssertionError("epoch rotate_ab: a process never rotated keys")
    cut = rot.processes[0].epoch_mgr.history[-1].boundary_wave * wl

    def prefix(sim):
        return [
            (v.id.round, v.id.source, v.digest())
            for v in sim.deliveries[0]
            if v.id.round <= cut
        ]

    if prefix(rot) != prefix(static):
        raise AssertionError(
            "epoch rotate_ab: pre-boundary prefix diverges from the "
            "static-membership run"
        )
    delivered = {
        tx
        for v in rot.deliveries[0]
        if v.block is not None
        for tx in v.block.transactions
    }
    if op not in delivered:
        raise AssertionError("epoch rotate_ab: control op lost")
    return {
        "boundary_wave": cut // wl,
        "decided_waves": min(p.decided_wave for p in rot.processes),
        "rotations_min": rotations,
        "prefix_identical": True,
        "prefix_len": len(prefix(rot)),
        "control_op_committed": True,
    }


def _epoch_flatness_cell(seed: int) -> dict:
    """Three sequenced epochs under GC: vertices_live_max must settle —
    the retained window is bounded by waves+depth, not by history."""
    from dag_rider_tpu import Config
    from dag_rider_tpu.consensus import Simulation
    from dag_rider_tpu.core import codec
    from dag_rider_tpu.core.types import Block, EpochOp

    cfg = Config(
        n=4,
        coin="round_robin",
        propose_empty=True,
        epoch=True,
        epoch_waves=2,
        gc_depth=16,
        epoch_gc=0,
    )
    sim = Simulation(cfg)
    sim.submit_blocks(per_process=2)
    marks = []
    for k in range(3):
        sim.processes[0].submit(
            Block((codec.encode_epoch_op(EpochOp("rotate", 0, seed + k, b"")),))
        )
        for _ in range(900):
            if min(p.epoch_mgr.epoch for p in sim.processes) >= k + 1:
                break
            sim.run(max_messages=300)
        else:
            raise AssertionError(f"epoch flatness: epoch {k + 1} never settled")
        marks.append(
            max(
                p.metrics.counters["vertices_live_max"]
                for p in sim.processes
            )
        )
    if marks[-1] > marks[0] + cfg.n * cfg.wave_length:
        raise AssertionError(
            f"epoch flatness: vertices_live_max grew across epochs: {marks}"
        )
    bound = cfg.n * (
        cfg.epoch_waves * cfg.wave_length
        + cfg.gc_depth
        + 4 * cfg.wave_length
    )
    if marks[-1] > bound:
        raise AssertionError(
            f"epoch flatness: high-water {marks[-1]} over bound {bound}"
        )
    return {
        "epochs": 3,
        "vertices_live_max_per_epoch": marks,
        "bound": bound,
        "flat": True,
    }


def _epoch_rung(
    n: int = 4,
    load_s: float = 8.0,
    rate: float = 250.0,
    seed: int = 7,
    boot_s: float = 20.0,
    catchup_s: float = 120.0,
    cells: tuple = ("join", "rotate_ab", "flatness"),
) -> dict:
    """Ladder rung (ISSUE 20): epoch reconfiguration + span-attested
    snapshot sync. Three cells, each RAISING on a missed gate:

    - **join**: a late node catches up mid-load from a span-attested
      snapshot within <= ceil(round / k_span) pairing checks, its
      commit log embeds byte-identically into the survivor order, and
      every node lands in the same epoch >= 1.
    - **rotate_ab**: an epoch boundary rotates real threshold-coin
      share keys in lockstep with zero lost acked txs and a pre-
      boundary prefix byte-identical to a static-membership run.
    - **flatness**: vertices_live_max stays flat across >= 3 settled
      epochs — the GC floor advances with the boundary.
    """
    entry: dict = {}
    if "join" in cells:
        entry["join"] = _epoch_join_cell(
            n, load_s, rate, seed, boot_s, catchup_s=catchup_s
        )
    if "rotate_ab" in cells:
        entry["rotate_ab"] = _epoch_rotate_ab_cell(seed)
    if "flatness" in cells:
        entry["flatness"] = _epoch_flatness_cell(seed)
    return entry


def _measure() -> None:
    budget = float(os.environ.get("DAGRIDER_BENCH_SECONDS", "300"))
    t_start = time.monotonic()

    def left() -> float:
        return budget - (time.monotonic() - t_start)

    _mark(f"measure: python up (budget {budget:.0f}s), importing jax")
    import jax

    import numpy as np
    import jax.numpy as jnp

    t0 = time.monotonic()
    backend = jax.default_backend()
    device_kind = getattr(jax.devices()[0], "device_kind", "?")
    jnp.zeros((8,), dtype=jnp.int32).sum().block_until_ready()
    init_s = time.monotonic() - t0
    _mark(f"measure: backend '{backend}' ({device_kind}) up in {init_s:.1f}s")

    result = {
        "metric": "vertex_sigs_per_sec",
        "value": 0.0,
        "unit": "sigs/s",
        "vs_baseline": 0.0,
        "backend": backend,
        "device_kind": device_kind,
        "n": 0,
        "phases": {"backend_init_s": round(init_s, 1)},
        "ladder": {},
    }

    def emit() -> None:
        print(json.dumps(result), flush=True)

    built = {}  # n -> (verifier, batches); reused by the wave phase

    def merged_phase(n: int) -> None:
        """Merged multi-round throughput at committee n — all built rounds
        in ONE padded device dispatch via verify_rounds (the steady-state
        consensus shape amortizes the per-dispatch fixed cost across
        consecutive rounds)."""
        if n not in built:
            return
        if left() < 45:
            # the merged bucket is a SECOND program compile — on a CPU
            # fallback it can eat minutes and starve later rungs
            _mark(f"skipping merged_n{n} (left {left():.0f}s)")
            return
        verifier, batches, _ = built[n]
        rounds = batches[1:]
        _mark(f"merged_n{n}: compiling merged bucket ({sum(len(b) for b in rounds)} sigs)")
        masks = verifier.verify_rounds(rounds)  # compile + warm this bucket
        if not all(all(m) for m in masks):
            _mark(f"merged_n{n}: verification failed, discarding phase")
            return
        # Best of 3: the round-3 captures swung ~±20% run to run on the
        # headline (PROFILE.md); repeated timed dispatches cost ~0.3 s
        # each and isolate the steady state from one unlucky round-trip.
        times = []
        for _ in range(3):
            t0 = time.monotonic()
            masks = verifier.verify_rounds(rounds)
            times.append(time.monotonic() - t0)
        dt = min(times)
        total = sum(len(m) for m in masks)
        sigs = total / dt
        result["phases"][f"verify_n{n}_merged"] = {
            "rounds": len(rounds),
            "sigs": total,
            "sigs_per_sec": round(sigs, 1),
            "dispatch_ms": round(1e3 * dt, 2),
            "dispatch_ms_median": round(
                1e3 * sorted(times)[len(times) // 2], 2
            ),
        }
        _mark(f"merged_n{n}: {sigs:,.0f} sigs/s ({len(rounds)} rounds/dispatch)")
        if sigs > result["value"] and n >= result["n"]:
            result["value"] = round(sigs, 1)
            result["vs_baseline"] = round(sigs / BASELINE, 3)
            result["n"] = n
        emit()

    def verify_phase(n: int, timed_rounds: int, built_rounds: int = 0) -> bool:
        """One committee size: build, compile/warm, measure. Returns ok.

        built_rounds (>= timed_rounds) controls how many signed rounds are
        constructed — the merged phase wants a big burst to dispatch, but
        per-round timing needs only a few synchronizing samples (each is a
        full device round-trip; 63 of them would burn ~4 s of budget for
        no extra information).
        """
        built_rounds = max(built_rounds, timed_rounds)
        tag = f"verify_n{n}"
        _mark(f"{tag}: building {1 + built_rounds} signed rounds")
        t0 = time.monotonic()
        verifier, batches, signers = _build_batches(n, 1 + built_rounds)
        built[n] = (verifier, batches, signers)
        build_s = time.monotonic() - t0
        _mark(f"{tag}: build done in {build_s:.1f}s; compiling (warm batch)")
        t0 = time.monotonic()
        mask = verifier.verify_batch(batches[0])
        if not all(mask):
            _mark(f"{tag}: WARM BATCH FAILED TO VERIFY — aborting phase")
            return False
        compile_s = time.monotonic() - t0
        _mark(f"{tag}: compile+warm done in {compile_s:.1f}s; timing")
        total = 0
        t0 = time.monotonic()
        prep_s = 0.0
        for k, b in enumerate(batches[1 : 1 + timed_rounds]):
            mask = verifier.verify_batch(b)
            prep_s += verifier.last_prepare_s
            total += len(b)
            if not all(mask):
                _mark(f"{tag}: timed batch {k} failed")
                return False
            _mark(f"{tag}: timed batch {k} done")
        dt = time.monotonic() - t0
        sigs = total / dt
        _mark(
            f"{tag}: {sigs:,.0f} sigs/s  (host prep {1e3 * prep_s / timed_rounds:.1f}"
            f" ms/round, device+prep {1e3 * dt / timed_rounds:.1f} ms/round)"
        )
        result["phases"][tag] = {
            "build_s": round(build_s, 1),
            "compile_s": round(compile_s, 1),
            "sigs_per_sec": round(sigs, 1),
            "host_prep_ms_per_round": round(1e3 * prep_s / timed_rounds, 2),
            "round_ms": round(1e3 * dt / timed_rounds, 2),
        }
        # The headline is pinned to the LARGEST measured committee (the
        # north star is defined at n=256) — never a smaller-n number that
        # happens to be faster.
        if n >= result["n"]:
            result["value"] = round(sigs, 1)
            result["vs_baseline"] = round(sigs / BASELINE, 3)
            result["n"] = n
        emit()
        return True

    # Phase order depends on the backend (round-3 postmortem: the official
    # record must carry the *headline* even when the run truncates):
    #  - device backends: n=256 build+compile+merged FIRST — the north
    #    star is defined at n=256, so it lands before any rung can eat
    #    the budget.
    #  - CPU fallback: n=64 first (n=256 would burn the whole fallback
    #    window compiling; DAGRIDER_BENCH_N256_MIN gates it off).
    n256_min = float(os.environ.get("DAGRIDER_BENCH_N256_MIN", "150"))
    # On-device: 63 built rounds so the merged phase dispatches a ~16k-
    # signature program (measured 50.6k sigs/s at 16384, 57.7k at 32768 —
    # PROFILE.md round 3). The CPU fallback shrinks this (round-4 VERDICT
    # #6: the fallback must still *measure the north-star committee size*,
    # which it can afford only with a small merged burst).
    n256_rounds = int(os.environ.get("DAGRIDER_BENCH_N256_ROUNDS", "63"))
    headline_first = backend != "cpu" and left() > n256_min

    if headline_first:
        # n=256 (the north-star committee size) first, with only 4
        # synchronizing per-round timing samples.
        if verify_phase(256, timed_rounds=4, built_rounds=n256_rounds):
            merged_phase(256)
        if left() > 30:
            verify_phase(64, timed_rounds=4)
    else:
        # n=64 first: small program compiles fast; guarantees a number.
        # The merged phase is DEFERRED to the end of the stage on this
        # path (cpu_merged_n below): its second program compile must not
        # starve the host-consensus/coin rungs of the fallback window.
        verify_phase(64, timed_rounds=4)
        cpu_merged_n = 64
        if left() > n256_min:
            if verify_phase(256, timed_rounds=4, built_rounds=n256_rounds):
                cpu_merged_n = 256
        else:
            _mark(f"skipping n=256 (only {left():.0f}s left)")

    # -- phase C: wave-commit pipeline latency at the measured n
    if left() > 30 and result["n"]:
        n = result["n"]
        _mark("wave pipeline: warm + timing")
        from dag_rider_tpu.ops import dag_kernels

        quorum = _quorum(n)
        rng = np.random.default_rng(7)
        strong_wave = jnp.asarray(
            rng.random((3, n, n)) < min(1.0, (quorum + 0.5) / n)
        )
        exists_r4 = jnp.ones(n, dtype=bool)
        leader = jnp.int32(1)
        commit_fn = jax.jit(
            lambda s, e, l: dag_kernels.wave_commit_votes(s, e, l, quorum=quorum)
        )
        jax.block_until_ready(commit_fn(strong_wave, exists_r4, leader))
        # reuse the already-built, already-warm batches from verify_phase;
        # the 4 rounds of a wave arrive as one merged dispatch (the
        # steady-state consensus shape — Simulation.run coalescing)
        verifier, batches, _ = built[n]
        verifier.verify_rounds(batches[:4])  # warm the wave-burst bucket
        strong_np = np.asarray(strong_wave)
        wave_ms = []
        for w in range(6):
            t0 = time.monotonic()
            verifier.verify_rounds(batches[:4])
            jax.block_until_ready(commit_fn(strong_wave, exists_r4, leader))
            reach = np.eye(n, dtype=bool)
            for r in range(3):
                reach = (
                    reach.astype(np.int32) @ strong_np[r].astype(np.int32)
                ) > 0
            wave_ms.append(1e3 * (time.monotonic() - t0))
        wave_ms.sort()
        # staged proxy (verify-4-rounds + commit kernels); the sim256
        # rung overwrites the top-level field with the end-to-end number
        p50 = round(wave_ms[len(wave_ms) // 2], 2)
        result["phases"]["wave_pipeline_p50_ms"] = p50
        result["wave_commit_p50_ms"] = p50
        _mark(f"wave pipeline p50 (staged proxy): {p50} ms")
        emit()

    # -- ladder rung #3 live half: n=256 consensus-in-the-loop with the
    # threshold coin (the north-star committee size — round-3 VERDICT #3
    # wants the END-TO-END wave_commit_p50 and sigs/s at n=256, not the
    # staged proxy). Reuses the headline phase's verifier+signers (their
    # comb tables and the 16k-bucket program are already built/compiled).
    sim256_budget = float(os.environ.get("DAGRIDER_BENCH_SIM256_S", "60"))
    if sim256_budget > 0 and 256 in built and left() > sim256_budget + 35:
        _mark(f"ladder sim256: time-boxed {sim256_budget:.0f}s consensus run")
        verifier, _, signers = built[256]
        # One round's coalesced burst is 256*255 = 65,280 sigs. The
        # default 16384 bucket chunks it into 4 dispatches through the
        # SAME program the merged headline phase compiled (no extra
        # compile in the driver's budget); a long local capture can set
        # DAGRIDER_BENCH_SIM256_BUCKET=65280 to pay one bigger compile
        # and run ONE dispatch per round — with the pipeline overlapping
        # host prep, in-loop throughput approaches the merged phase's.
        sim256_bucket = int(
            os.environ.get("DAGRIDER_BENCH_SIM256_BUCKET", "16384")
        )
        # the verifier is SHARED with the (possibly deferred) merged
        # phase — restore its bucket after the rungs, or a 512-bucket
        # sim leaves verify_rounds chunking the "merged" dispatch
        prev_bucket = verifier.fixed_bucket
        # try/finally (ADVICE r5 #3): an exception anywhere in the two
        # rungs must not leak a sim-sized bucket into the deferred
        # merged headline phase sharing this verifier
        try:
            if sim256_bucket != 16384:
                # a non-default bucket is a NEW program shape — compile
                # it OUTSIDE the timed box (the 16384 default reuses the
                # merged headline phase's program; sim64 pre-warms the
                # same way)
                _mark(
                    f"ladder sim256: pre-warming bucket-{sim256_bucket} program"
                )
                verifier.fixed_bucket = sim256_bucket
                verifier.warmup()  # AOT: jit().lower().compile() at the shape
                verifier.verify_batch(built[256][1][0][:9])  # host-prep warm
            entry = _sim_rung(
                256,
                sim256_budget,
                verifier,
                signers,
                bucket=sim256_bucket,
                chunk=256 * 255,
                coin="threshold_bls",
            )
            entry["bucket"] = sim256_bucket
            result["ladder"]["sim256"] = entry
            # the official end-to-end p50 at the north-star committee size
            if entry["wave_commit_p50_ms"] is not None:
                result["wave_commit_p50_ms"] = entry["wave_commit_p50_ms"]
            _mark(
                f"ladder sim256: {entry['sigs_applied']} applied sigs "
                f"({entry['sigs_applied_per_sec']:,.0f}/s; device "
                f"{entry['sigs_device_per_sec']:,.0f}/s), "
                f"{entry['vertices_delivered_total']} delivered, "
                f"round {entry['max_round']}, "
                f"wave p50 {entry['wave_commit_p50_ms']} ms"
            )
            emit()
            # before/after overlap evidence (round-4 VERDICT #4): the
            # same rung with the dispatch/delivery pipeline forced OFF —
            # the p50 delta is what the overlap buys at the north-star
            # committee
            sync_budget = float(
                os.environ.get("DAGRIDER_BENCH_SIM256_SYNC_S", "25")
            )
            if sync_budget > 0 and left() > sync_budget + 30:
                _mark(f"ladder sim256_sync: {sync_budget:.0f}s, pipeline OFF")
                entry = _sim_rung(
                    256,
                    sync_budget,
                    verifier,
                    signers,
                    bucket=sim256_bucket,  # same program as the A side
                    chunk=256 * 255,
                    coin="threshold_bls",
                    pipelined=False,
                )
                entry["bucket"] = sim256_bucket
                result["ladder"]["sim256_sync"] = entry
                _mark(
                    f"ladder sim256_sync: wave p50 "
                    f"{entry['wave_commit_p50_ms']} ms "
                    f"({entry['sigs_applied_per_sec']:,.0f} applied sigs/s)"
                )
                emit()
        finally:
            verifier.fixed_bucket = prev_bucket
    else:
        _mark(f"skipping ladder sim256 (left {left():.0f}s)")

    # -- ladder rung #3: 64-node consensus-in-the-loop, device verifier
    # (35 s box: enough for ~50 rounds at the round-4 host path; the
    # budget must also fit sim256 + verify1024 + msm)
    sim_budget = float(os.environ.get("DAGRIDER_BENCH_SIM_S", "35"))
    if sim_budget > 0 and left() > sim_budget + 25:
        _mark(f"ladder sim64: time-boxed {sim_budget:.0f}s consensus run")
        from dag_rider_tpu.verifier.base import KeyRegistry, VertexSigner
        from dag_rider_tpu.verifier.tpu import TPUVerifier

        n = 64
        reg, seeds = KeyRegistry.generate(n)
        shared = TPUVerifier(reg)
        # All 64 processes share this verifier, so the simulator
        # coalesces every pump cycle's batches into ONE device dispatch
        # (Simulation.run); the fixed bucket keeps that single program
        # shape compiled once, however burst sizes wander. Round-sized
        # chunks (64*63 = 4032 <= the 4096 bucket) keep it one dispatch
        # per DAG round — round-3 ran 500-message chunks, paying the
        # fixed dispatch cost 8x per round.
        signers = [VertexSigner(s) for s in seeds]
        # With dispatch dedup a round's unique burst is only n sigs, so
        # a CPU-backend run (tests) sets this rung's bucket to 128.
        sim_bucket = int(os.environ.get("DAGRIDER_BENCH_SIM_BUCKET", "4096"))
        shared.fixed_bucket = sim_bucket
        warm_all = _signed_round(signers, n, 1, _quorum(n))
        shared.warmup()  # AOT-compile the fixed-bucket program
        shared.verify_batch(warm_all[:9])  # warm host prep + native lib
        _mark(f"ladder sim64: fixed-bucket({sim_bucket}) program pre-warmed")
        entry = _sim_rung(
            n,
            sim_budget,
            shared,
            signers,
            bucket=sim_bucket,
            chunk=4032,
            # BASELINE config #3 says a 10k-vertex DAG; keep pumping past
            # the box until a view holds 10k vertices (bounded so the
            # remaining ladder rungs still fit)
            target_per_view=10_000,
            max_s=max(sim_budget, min(240.0, left() - 150.0)),
        )
        result["ladder"]["sim64"] = entry
        if result.get("wave_commit_p50_ms") is None and entry[
            "wave_commit_p50_ms"
        ]:
            result["wave_commit_p50_ms"] = entry["wave_commit_p50_ms"]
        _mark(
            f"ladder sim64: {entry['sigs_applied']} applied sigs in "
            f"{entry['seconds']:.0f}s ({entry['sigs_applied_per_sec']:,.0f}/s), "
            f"{entry['vertices_delivered_total']} delivered, "
            f"round {entry['max_round']}"
        )
        emit()
    else:
        _mark(f"skipping ladder sim64 (only {left():.0f}s left)")

    # -- host-path consensus rung (CPU fallback evidence): the full
    # 64-node protocol loop with a null verifier — admission, waves,
    # ordering, GC — pure host throughput. On the device path this is
    # covered by sim64/sim256; the CPU fallback sets
    # DAGRIDER_BENCH_HOSTSIM_S so the official record still carries a
    # consensus number when the chip is unreachable.
    def host_rung(n: int, secs: float, pump: str | None = None) -> None:
        tag = f"sim{n}_host" + (f"_{pump}" if pump else "")
        _mark(f"ladder {tag}: {secs:.0f}s null-verifier consensus")
        from dag_rider_tpu.config import Config
        from dag_rider_tpu.consensus.simulator import Simulation

        cfg = Config(
            n=n,
            coin="round_robin",
            propose_empty=True,
            gc_depth=24,
            # None defers to DAGRIDER_PUMP / scalar (Config default)
            pump=pump,
        )
        sim = Simulation(cfg)
        sim.submit_blocks(per_process=2)
        t0 = time.monotonic()
        pumped = 0
        while time.monotonic() - t0 < secs:
            pumped += sim.run(max_messages=n * (n - 1))
        dt = time.monotonic() - t0
        sim.check_agreement()
        snap0 = sim.processes[0].metrics.snapshot()
        result["ladder"][tag] = {
            "nodes": n,
            "verifier": "none",
            "pump": sim.processes[0].cfg.pump,
            "seconds": round(dt, 1),
            "messages": pumped,
            "msgs_per_sec": round(pumped / dt, 1),
            "max_round": max(p.round for p in sim.processes),
            "vertices_delivered_total": sum(
                len(d) for d in sim.deliveries
            ),
            "vertices_live_max": max(
                len(p.dag.vertices) for p in sim.processes
            ),
            "agreement": True,
            # host-pump accounting (round 12): ms of pump+step per
            # round advanced, and delivered msgs per pump-wall second
            **{
                k: snap0[k]
                for k in (
                    "pump_path",
                    "pump_msgs_per_s",
                    "host_pump_ms_per_round",
                )
                if k in snap0
            },
        }
        host_ivals = sorted(
            s
            for p in sim.processes
            for s in p.metrics.wave_interval_seconds
        )
        # always present (null when no 2nd wave decided) — same schema
        # as the _sim_rung entries
        result["ladder"][tag]["wave_interval_p50_ms"] = (
            round(1e3 * host_ivals[len(host_ivals) // 2], 2)
            if host_ivals
            else None
        )
        _mark(
            f"ladder {tag}: {pumped / dt:,.0f} msg/s, round "
            f"{result['ladder'][tag]['max_round']}, agreement ok"
        )
        emit()

    hostsim_s = float(os.environ.get("DAGRIDER_BENCH_HOSTSIM_S", "0"))
    if hostsim_s > 0 and left() > hostsim_s + 10:
        host_rung(64, hostsim_s)
    # n=256 host consensus: consensus behavior at the committee size the
    # baseline is defined at
    hostsim256_s = float(os.environ.get("DAGRIDER_BENCH_HOSTSIM256_S", "0"))
    if hostsim256_s > 0 and left() > hostsim256_s + 10:
        host_rung(256, hostsim256_s)

    # -- ladder rung (round 12): scalar-vs-vector host pump A/B
    # (bench._vec_ab_rung, the tier1-vec CI smoke). Off by default; a
    # local capture sets DAGRIDER_BENCH_SIM256VEC_S high and _N=256 for
    # the committee size.
    vecab_s = float(os.environ.get("DAGRIDER_BENCH_SIM256VEC_S", "0"))
    vecab_n = int(os.environ.get("DAGRIDER_BENCH_SIM256VEC_N", "256"))
    vecab_round = int(os.environ.get("DAGRIDER_BENCH_SIM256VEC_ROUND", "12"))
    if vecab_s > 0 and left() > 2 * vecab_s + 10:
        tag = f"sim{vecab_n}_vec"
        _mark(f"ladder {tag}: scalar-vs-vector A/B to round {vecab_round}")
        entry = _vec_ab_rung(vecab_n, vecab_s, vecab_round)
        result["ladder"][tag] = entry
        _mark(
            f"ladder {tag}: scalar "
            f"{entry['scalar']['msgs_per_sec']:,.0f} msg/s vs vector "
            f"{entry['vector']['msgs_per_sec']:,.0f} msg/s "
            f"({entry['speedup']}x), commit order identical"
        )
        emit()

    # -- ladder rung (round 16): trace-off vs trace-on A/B
    # (bench._trace_ab_rung, the tier1-obs CI smoke). Off by default; a
    # local capture sets DAGRIDER_BENCH_TRACE_S for the per-side budget.
    trab_s = float(os.environ.get("DAGRIDER_BENCH_TRACE_S", "0"))
    trab_n = int(os.environ.get("DAGRIDER_BENCH_TRACE_N", "16"))
    trab_round = int(os.environ.get("DAGRIDER_BENCH_TRACE_ROUND", "60"))
    if trab_s > 0 and left() > 2 * trab_s + 10:
        _mark(f"ladder trace_overhead: off-vs-on A/B to round {trab_round}")
        entry = _trace_ab_rung(trab_n, trab_s, trab_round)
        result["ladder"]["trace_overhead"] = entry
        _mark(
            f"ladder trace_overhead: off "
            f"{entry['off']['msgs_per_sec']:,.0f} msg/s vs on "
            f"{entry['on']['msgs_per_sec']:,.0f} msg/s "
            f"({entry['overhead_pct']}% overhead, "
            f"gate {'ok' if entry['overhead_ok'] else 'FAIL'}), "
            "commit order identical"
        )
        emit()

    # -- ladder rungs (round 13): aggregated round certificates. Two
    # halves — verify_n256_agg prices the aggregate-check components at
    # the n=64/n=256 quorums against the per-vertex ed25519 reference,
    # and sim{n}_agg runs the cert-on/cert-off sim A/B (byte-identical
    # commit order, cluster-wide sigs_device drop). Off by default (the
    # host pairing halves eat ~1 min); a local capture sets
    # DAGRIDER_BENCH_AGG=1 (+ _AGG_N for the sim committee size) and
    # gets BENCH_r06.json when both halves pass.
    agg_on = os.environ.get("DAGRIDER_BENCH_AGG", "") == "1"
    agg_n = int(os.environ.get("DAGRIDER_BENCH_AGG_N", "64"))
    if agg_on and left() > 30:
        agg_ok = sim_ok = False
        try:
            _mark("ladder verify_n256_agg: aggregate-check components")
            entry = _agg_ladder_rung()
            result["ladder"]["verify_n256_agg"] = entry
            agg_ok = entry["agg_per_vertex_flat_within_2x"]
            _mark(
                "ladder verify_n256_agg: check "
                f"{entry['sizes']['64']['agg_check_warm_s']}s@64 -> "
                f"{entry['sizes']['256']['agg_check_warm_s']}s@256 "
                f"({entry['agg_check_growth']}x wall for "
                f"{entry['pairs_growth']}x pairs; per-vertex amortized "
                f"{entry['agg_ms_per_vertex_growth']}x)"
            )
            emit()
        except Exception as e:  # noqa: BLE001 — rung is best-effort
            _mark(f"ladder verify_n256_agg FAILED: {e!r}")
        try:
            tag = f"sim{agg_n}_agg"
            _mark(f"ladder {tag}: cert-on/cert-off sim A/B")
            entry = _cert_ab_rung(agg_n)
            result["ladder"][tag] = entry
            sim_ok = (
                entry["commit_order_identical"]
                and entry["sigs_device_drop"] >= 10.0
            )
            _mark(
                f"ladder {tag}: sigs_device "
                f"{entry['per_vertex']['sigs_device']} -> "
                f"{entry['agg']['sigs_device']} "
                f"({entry['sigs_device_drop']}x drop), commit order "
                "identical"
            )
            emit()
        except Exception as e:  # noqa: BLE001 — rung is best-effort
            _mark(f"ladder {tag} FAILED: {e!r}")
        if agg_ok and sim_ok:
            rec = {
                "verify_n256_agg": result["ladder"]["verify_n256_agg"],
                f"sim{agg_n}_agg": result["ladder"][f"sim{agg_n}_agg"],
                "backend": result.get("backend", "cpu"),
                "device_kind": result.get("device_kind", "cpu"),
                "ok": True,
                "skipped": False,
            }
            from dag_rider_tpu import config as _cfg

            out_path = os.path.join(
                _REPO, _cfg.env_str("DAGRIDER_AGG_OUT")
            )
            with open(out_path, "w") as fh:
                json.dump(rec, fh, indent=1)
                fh.write("\n")
            _mark(f"ladder agg: wrote {out_path}")

    # -- ladder rung (ISSUE 12): certificate path phase 2 — batched
    # share signing, the pairing seam, and cert-of-certs replay, each
    # against its oracle. Off by default (the n=256 host signing oracle
    # alone is ~a minute); a local capture sets DAGRIDER_BENCH_CERT2=1
    # and gets BENCH_r07.json (DAGRIDER_CERT2_OUT) when the acceptance
    # gates pass: native signing >=3x, span replay < 1 product check
    # per round, triple-A/B commit order byte-identical.
    c2_on = os.environ.get("DAGRIDER_BENCH_CERT2", "") == "1"
    if c2_on and left() > 30:
        try:
            _mark(
                "ladder cert_phase2: batched signing / span replay / "
                "triple sim A/B"
            )
            entry = _cert_phase2_rung()
            result["ladder"]["cert_phase2"] = entry
            c2_ok = (
                entry["sign"]["native_speedup_x"] >= 3.0
                and entry["span_replay"]["pairing_checks_per_round"] < 1.0
                and entry["sim"]["commit_order_identical"]
            )
            _mark(
                "ladder cert_phase2: native sign "
                f"{entry['sign']['native_speedup_x']}x, span replay "
                f"{entry['span_replay']['pairing_checks_per_round']} "
                "checks/round, commit order identical"
            )
            emit()
            if c2_ok:
                rec = {
                    "cert_phase2": entry,
                    "backend": result.get("backend", "cpu"),
                    "device_kind": result.get("device_kind", "cpu"),
                    "ok": True,
                    "skipped": False,
                }
                from dag_rider_tpu import config as _cfg

                out_path = os.path.join(
                    _REPO, _cfg.env_str("DAGRIDER_CERT2_OUT")
                )
                with open(out_path, "w") as fh:
                    json.dump(rec, fh, indent=1)
                    fh.write("\n")
                _mark(f"ladder cert_phase2: wrote {out_path}")
        except Exception as e:  # noqa: BLE001 — rung is best-effort
            _mark(f"ladder cert_phase2 FAILED: {e!r}")

    # -- ladder rung #9 (round 10): mempool-fronted end-to-end commit
    # pipeline — client transactions through admission/batching/consensus
    # to a_deliver on the WALL clock, so committed-tx/s and the
    # submit→a_deliver percentiles are what a cluster client would see.
    # Null verifier on purpose: the crypto seam has its own rungs; this
    # one prices the ingestion + ordering pipeline. The chaos variant
    # reruns a tight pool under phase-aligned bursts THROUGH an
    # unreliable transport (delay + duplicate faults) on the virtual
    # clock — the acceptance gate is shed-not-crash: audit lost == 0
    # and duplicates == 0 WITH shed > 0, agreement intact.
    mp_secs = float(os.environ.get("DAGRIDER_BENCH_MEMPOOL_S", "20"))
    mp_n = int(os.environ.get("DAGRIDER_BENCH_MEMPOOL_N", "256"))
    mp_rate = float(os.environ.get("DAGRIDER_BENCH_MEMPOOL_RATE", "4000"))
    # the drain (commit the tail of in-flight blocks) is wall-bounded
    # separately: ~16 DAG rounds at n=256 is minutes of host pumping on
    # a slow core, and the rung must never eat the remaining ladder
    mp_drain = float(os.environ.get("DAGRIDER_BENCH_MEMPOOL_DRAIN_S", "30"))
    if mp_secs > 0 and left() > mp_secs + mp_drain + 20:
        from dag_rider_tpu.config import Config as _MpCfg
        from dag_rider_tpu.config import MempoolConfig as _MpMCfg
        from dag_rider_tpu.consensus.simulator import Simulation as _MpSim
        from dag_rider_tpu.mempool.loadgen import (
            ClusterLoadDriver,
            LoadGenerator,
        )

        _mark(
            f"ladder mempool_e2e: n={mp_n}, {mp_rate:,.0f} tx/s offered, "
            f"{mp_secs:.0f}s wall"
        )
        try:
            sim = _MpSim(
                _MpCfg(
                    n=mp_n,
                    coin="round_robin",
                    propose_empty=True,
                    gc_depth=24,
                )
            )
            gen = LoadGenerator(
                clients=32,
                rate=mp_rate,
                tx_bytes=32,
                seed=10,
                profile="poisson",
            )
            drv = ClusterLoadDriver(
                sim,
                gen,
                mcfg=_MpMCfg(cap=65536, batch_bytes=4096),
                wall=True,
            )
            entry = drv.run(mp_secs, drain_s=mp_drain)
            sim.check_agreement()
            entry["verifier"] = "none"
            entry["agreement"] = True
            result["ladder"]["mempool_e2e"] = entry
            if entry["audit"]["lost"] or entry["audit"]["duplicates"]:
                raise AssertionError(f"mempool audit failed: {entry['audit']}")
            _mark(
                f"ladder mempool_e2e: {entry['committed_tx_per_sec']:,.0f} "
                f"committed tx/s ({entry['committed_tx']} committed / "
                f"{entry['offered_tx']} offered), fill "
                f"{entry['batch_fill']}, p50 "
                f"{entry.get('submit_deliver_p50_ms')} ms / p99 "
                f"{entry.get('submit_deliver_p99_ms')} ms"
            )
            emit()
        except Exception as e:  # noqa: BLE001 — rung is best-effort
            _mark(f"ladder mempool_e2e FAILED: {e!r}")
    else:
        _mark(f"skipping ladder mempool_e2e (left {left():.0f}s)")

    mpc_secs = float(os.environ.get("DAGRIDER_BENCH_MEMPOOL_CHAOS_S", "1"))
    mpc_n = int(os.environ.get("DAGRIDER_BENCH_MEMPOOL_CHAOS_N", "64"))
    if mpc_secs > 0 and left() > 50:
        from dag_rider_tpu.config import Config as _MpCfg
        from dag_rider_tpu.config import MempoolConfig as _MpMCfg
        from dag_rider_tpu.consensus.simulator import Simulation as _MpSim
        from dag_rider_tpu.mempool.loadgen import (
            ClusterLoadDriver,
            LoadGenerator,
        )
        from dag_rider_tpu.transport.faults import FaultPlan, FaultyTransport

        _mark(
            f"ladder mempool_chaos: n={mpc_n}, 8x bursts over tight pool, "
            f"delay/duplicate faults, {mpc_secs:.0f}s virtual"
        )
        try:
            sim = _MpSim(
                _MpCfg(
                    n=mpc_n,
                    coin="round_robin",
                    propose_empty=True,
                    gc_depth=24,
                ),
                transport=FaultyTransport(
                    FaultPlan(delay=0.05, duplicate=0.02, seed=10)
                ),
            )
            gen = LoadGenerator(
                clients=2 * mpc_n,
                rate=40_000.0,
                tx_bytes=32,
                seed=10,
                profile="burst",
            )
            # pool sized to saturate: the burst peaks MUST overflow the
            # watermarks or the rung proves nothing about shedding
            drv = ClusterLoadDriver(
                sim,
                gen,
                mcfg=_MpMCfg(
                    cap=512, batch_bytes=512, max_batch_txs=64
                ),
                dt=0.02,
            )
            entry = drv.run(mpc_secs, drain_s=20.0)
            sim.check_agreement()
            audit = entry["audit"]
            entry["verifier"] = "none"
            entry["agreement"] = True
            entry["transport_faults"] = dict(sim.transport.stats)
            result["ladder"]["mempool_chaos"] = entry
            if audit["lost"] or audit["duplicates"]:
                raise AssertionError(f"chaos audit failed: {audit}")
            if not entry["shed_tx"]:
                raise AssertionError(
                    f"chaos rung never shed — not an overload run: {entry}"
                )
            _mark(
                f"ladder mempool_chaos: {entry['offered_tx']} offered, "
                f"{entry['accepted_tx']} accepted, {entry['shed_tx']} shed, "
                f"lost {audit['lost']}, dups {audit['duplicates']}, "
                f"agreement ok"
            )
            emit()
        except Exception as e:  # noqa: BLE001 — rung is best-effort
            _mark(f"ladder mempool_chaos FAILED: {e!r}")
    else:
        _mark(f"skipping ladder mempool_chaos (left {left():.0f}s)")

    # -- ladder rung (ISSUE 16): submit→deliver finality — pipelined
    # waves + eager optimistic delivery. Half 1 is the byte-identity
    # gate over the seeded n × adversary matrix (the rung RAISES on any
    # divergence, unbalanced eager books, or a nonzero expected-zero
    # rollback counter); half 2 is the wall-clock knobs-on/off latency
    # A/B at n=64 with the per-transaction attribution split (batcher
    # queueing vs wave lag, components summing to the measured total).
    fin_s = float(os.environ.get("DAGRIDER_BENCH_FINALITY_S", "15"))
    fin_n = int(os.environ.get("DAGRIDER_BENCH_FINALITY_N", "64"))
    fin_rate = float(os.environ.get("DAGRIDER_BENCH_FINALITY_RATE", "2000"))
    if fin_s > 0 and left() > 2 * fin_s + 80:
        _mark(f"ladder finality: n={fin_n}, {fin_s:.0f}s wall per side")
        try:
            t_rung = time.monotonic()
            entry = _finality_rung(
                n=fin_n, wall_s=fin_s, rate=fin_rate, drain_s=30.0
            )
            entry["rung_seconds"] = round(time.monotonic() - t_rung, 1)
            result["ladder"]["finality"] = entry
            _mark(
                f"ladder finality: identity gate held over "
                f"{len(entry['identity'])} matrix cases, p50 "
                f"{entry['on'].get('submit_deliver_p50_ms')} ms on / "
                f"{entry['off'].get('submit_deliver_p50_ms')} ms off, "
                f"eager p50 {entry.get('submit_eager_p50_ms')} ms, "
                f"sub-second gate "
                f"{'OK' if entry['p50_under_1s'] else 'MISSED'}"
            )
            emit()
            import datetime as _dt

            from dag_rider_tpu import config as _cfg

            out_path = os.path.join(
                _REPO, _cfg.env_str("DAGRIDER_FINALITY_OUT")
            )
            with open(out_path, "w") as fh:
                json.dump(
                    {
                        "schema": "dag-rider-tpu/bench-finality/v1",
                        "captured": _dt.datetime.now().isoformat(
                            timespec="seconds"
                        ),
                        "backend": result.get("backend", "cpu"),
                        "finality": entry,
                    },
                    fh,
                    indent=1,
                )
                fh.write("\n")
            _mark(f"ladder finality: wrote {out_path}")
        except Exception as e:  # noqa: BLE001 — rung is best-effort
            _mark(f"ladder finality FAILED: {e!r}")
    else:
        _mark(f"skipping ladder finality (left {left():.0f}s)")

    # -- ladder rung (ISSUE 17): sharded dissemination lanes. Half 1 is
    # the byte-identity gate (commit order AND delivered payload bytes,
    # lanes vs inline, over a seeded n × adversary × pump matrix — the
    # rung RAISES on divergence); half 2 is the committed-bytes-per-
    # pump-second A/B at n=64 with Ed25519-signed vertices as block
    # weight grows 16x, plus a lane-worker sweep at the top size.
    lanes_s = float(os.environ.get("DAGRIDER_BENCH_LANES_S", "15"))
    lanes_n = int(os.environ.get("DAGRIDER_BENCH_LANES_N", "64"))
    if lanes_s > 0 and left() > 150:
        _mark(f"ladder lanes: n={lanes_n}, identity matrix + A/B sweep")
        try:
            t_rung = time.monotonic()
            entry = _lanes_ab_rung(n=lanes_n)
            entry["rung_seconds"] = round(time.monotonic() - t_rung, 1)
            result["ladder"]["lanes"] = entry
            _mark(
                f"ladder lanes: identity gate held over "
                f"{len(entry['identity'])} matrix cases, "
                f"committed-bytes ratio "
                f"{entry['committed_bytes_ratio_top']}x at top size "
                f"({'OK' if entry['throughput_2x'] else 'MISSED'}), "
                f"lane pump flatness {entry['lane_pump_flatness']}x "
                f"({'OK' if entry['pump_flat_1p3x'] else 'MISSED'})"
            )
            emit()
            import datetime as _dt

            from dag_rider_tpu import config as _cfg

            out_path = os.path.join(
                _REPO, _cfg.env_str("DAGRIDER_LANES_OUT")
            )
            with open(out_path, "w") as fh:
                json.dump(
                    {
                        "schema": "dag-rider-tpu/bench-lanes/v1",
                        "captured": _dt.datetime.now().isoformat(
                            timespec="seconds"
                        ),
                        "backend": result.get("backend", "cpu"),
                        "lanes": entry,
                    },
                    fh,
                    indent=1,
                )
                fh.write("\n")
            _mark(f"ladder lanes: wrote {out_path}")
        except Exception as e:  # noqa: BLE001 — rung is best-effort
            _mark(f"ladder lanes FAILED: {e!r}")
    else:
        _mark(f"skipping ladder lanes (left {left():.0f}s)")

    # -- ladder rung (ISSUE 19): real multi-process cluster over sockets
    # with a kill -9 + rejoin-from-checkpoint cell. Gates: clean audits
    # (agreement incl. rejoin embedding, zero loss, uniqueness,
    # liveness, empty flight recorders) and byte-identical steady commit
    # prefixes — the rung RAISES otherwise.
    clu_s = float(os.environ.get("DAGRIDER_BENCH_CLUSTER_S", "6"))
    clu_n = int(os.environ.get("DAGRIDER_BENCH_CLUSTER_N", "4"))
    clu_rate = float(os.environ.get("DAGRIDER_BENCH_CLUSTER_RATE", "300"))
    if clu_s > 0 and left() > 2 * clu_s + 60:
        _mark(
            f"ladder cluster_e2e: n={clu_n} OS processes over uds, "
            f"{clu_s:.0f}s load per cell + one SIGKILL/rejoin"
        )
        try:
            t_rung = time.monotonic()
            entry = _cluster_e2e_rung(n=clu_n, load_s=clu_s, rate=clu_rate)
            entry["rung_seconds"] = round(time.monotonic() - t_rung, 1)
            result["ladder"]["cluster_e2e"] = entry
            ck = entry["kill_rejoin"]
            _mark(
                f"ladder cluster_e2e: clean "
                f"{entry['clean']['committed_tx_per_sec']} committed tx/s "
                f"(p50 {entry['clean'].get('submit_deliver_p50_ms')} ms / "
                f"p99 {entry['clean'].get('submit_deliver_p99_ms')} ms); "
                f"kill-and-rejoin kills={ck['kills']} lost={ck['lost_tx']} "
                f"prefix_identical={ck['prefix_identical']} "
                f"flight_dumps={ck['flight_dump_files']}"
            )
            emit()
            import datetime as _dt

            from dag_rider_tpu import config as _cfg

            out_path = os.path.join(
                _REPO, _cfg.env_str("DAGRIDER_CLUSTER_OUT")
            )
            with open(out_path, "w") as fh:
                json.dump(
                    {
                        "schema": "dag-rider-tpu/bench-cluster/v1",
                        "captured": _dt.datetime.now().isoformat(
                            timespec="seconds"
                        ),
                        "backend": result.get("backend", "cpu"),
                        "cluster_e2e": entry,
                    },
                    fh,
                    indent=1,
                )
                fh.write("\n")
            _mark(f"ladder cluster_e2e: wrote {out_path}")
        except Exception as e:  # noqa: BLE001 — rung is best-effort
            _mark(f"ladder cluster_e2e FAILED: {e!r}")
    else:
        _mark(f"skipping ladder cluster_e2e (left {left():.0f}s)")

    # -- ladder rung (ISSUE 20): epoch reconfiguration + span-attested
    # snapshot sync. Three gated cells — a real OS-process cluster where
    # a late node joins mid-load from a span-attested snapshot (pairing
    # budget + embedding + unanimous epoch), a threshold-coin rotation
    # A/B (byte-identical pre-boundary prefix, zero lost acked txs) and
    # a 3-epoch GC flatness check — the rung RAISES on any missed gate.
    ep_s = float(os.environ.get("DAGRIDER_BENCH_EPOCH_S", "180"))
    ep_rate = float(os.environ.get("DAGRIDER_BENCH_EPOCH_RATE", "250"))
    if ep_s > 0 and left() > ep_s + 30:
        _mark(
            "ladder epoch: mid-load join from span-attested snapshot "
            "+ key-rotation A/B + GC flatness across 3 epochs"
        )
        try:
            t_rung = time.monotonic()
            entry = _epoch_rung(
                rate=ep_rate, catchup_s=max(60.0, ep_s - 60)
            )
            entry["rung_seconds"] = round(time.monotonic() - t_rung, 1)
            result["ladder"]["epoch"] = entry
            j = entry["join"]
            _mark(
                f"ladder epoch: joiner verified "
                f"{j['snapshot_spans_verified']} spans in "
                f"{j['snapshot_pairing_checks']} pairings "
                f"(budget {j['pairing_budget']}), epochs "
                f"{sorted(set(j['epochs'].values()))}, "
                f"lost={j['lost_tx']}; rotate_ab boundary wave "
                f"{entry['rotate_ab']['boundary_wave']} prefix_identical="
                f"{entry['rotate_ab']['prefix_identical']}; flatness "
                f"{entry['flatness']['vertices_live_max_per_epoch']}"
            )
            emit()
            import datetime as _dt

            from dag_rider_tpu import config as _cfg

            out_path = os.path.join(
                _REPO, _cfg.env_str("DAGRIDER_EPOCH_OUT")
            )
            with open(out_path, "w") as fh:
                json.dump(
                    {
                        "schema": "dag-rider-tpu/bench-epoch/v1",
                        "captured": _dt.datetime.now().isoformat(
                            timespec="seconds"
                        ),
                        "backend": result.get("backend", "cpu"),
                        "epoch": entry,
                    },
                    fh,
                    indent=1,
                )
                fh.write("\n")
            _mark(f"ladder epoch: wrote {out_path}")
        except Exception as e:  # noqa: BLE001 — rung is best-effort
            _mark(f"ladder epoch FAILED: {e!r}")
    else:
        _mark(f"skipping ladder epoch (left {left():.0f}s)")

    # -- ladder rung: Byzantine adversary x WAN suite at committee scale.
    # Every adversary class from consensus/adversary.py drives f=10 of
    # n=32 nodes (f < n/3) through consensus/scenarios.py, plus a
    # partition-then-heal WAN run — run_scenario RAISES unless agreement,
    # commit-uniqueness, zero-loss, and the liveness floor all hold, so a
    # recorded entry IS a passed invariant audit. The detection counters
    # (equivocations_detected, edge_rejects, coin_filtered, sync_served)
    # land in the entry so the record also proves each attack genuinely
    # ran. garbage_coin is the expensive one (pure-Python pairings per
    # filtered wave) and gets its own cycle cap.
    byz_s = float(os.environ.get("DAGRIDER_BENCH_BYZ_S", "150"))
    byz_n = int(os.environ.get("DAGRIDER_BENCH_BYZ_N", "32"))
    byz_seed = int(os.environ.get("DAGRIDER_BENCH_BYZ_SEED", "0"))
    if byz_s > 0 and left() > byz_s + 20:
        from dag_rider_tpu.consensus.scenarios import Scenario, run_scenario

        t_rung = time.monotonic()
        byz_plan = [
            # (scenario kwargs, per-scenario wall cap fraction)
            dict(),
            dict(wan="partition", min_waves=1, min_each=1),
            dict(adversary="equivocate", min_waves=1, min_each=0),
            dict(
                adversary="equivocate_split",
                cycles=12,
                min_waves=1,
                min_each=0,
            ),
            dict(adversary="withhold", min_waves=1, min_each=0),
            dict(adversary="invalid_edges", min_waves=1, min_each=0),
            dict(
                adversary="garbage_coin",
                cycles=4,
                min_waves=1,
                min_each=0,
            ),
        ]
        rung: dict = {"n": byz_n, "seed": byz_seed, "scenarios": {}}
        result["ladder"]["byzantine"] = rung
        for kw in byz_plan:
            if time.monotonic() - t_rung > byz_s or left() < 20:
                _mark(
                    f"ladder byzantine: budget spent, skipping "
                    f"{kw.get('adversary') or 'clean'}/{kw.get('wan', 'lan')}"
                )
                continue
            sc = Scenario(n=byz_n, seed=byz_seed, **kw)
            _mark(f"ladder byzantine: {sc.name} (n={byz_n})")
            t0 = time.monotonic()
            try:
                r = run_scenario(sc)
                rung["scenarios"][sc.name] = {
                    "adversary": r["adversary"],
                    "wan": r["wan"],
                    "rbc": r["rbc"],
                    "coin": r["coin"],
                    "byzantine": len(r["byzantine"]),
                    "f": r["f"],
                    "rounds": r["rounds"],
                    "decided_waves": r["decided_waves"],
                    "audit": r["audit"],
                    "equivocations_detected": r["equivocations_detected"],
                    "edge_rejects": r["edge_rejects"],
                    "coin_filtered": r["coin_filtered"],
                    "sync_requested": r["sync_requested"],
                    "sync_served": r["sync_served"],
                    "behavior": r["behavior"],
                    "invariants": r["invariants"],
                    "wall_s": round(time.monotonic() - t0, 2),
                }
                _mark(
                    f"ladder byzantine: {sc.name} OK in "
                    f"{time.monotonic() - t0:.1f}s — waves "
                    f"{r['decided_waves']['min']}..{r['decided_waves']['max']}, "
                    f"eq {r['equivocations_detected']}, edges "
                    f"{r['edge_rejects']}, coin {r['coin_filtered']}"
                )
            except Exception as e:  # noqa: BLE001 — rung is best-effort
                rung["scenarios"][sc.name] = {
                    "failed": repr(e)[:300],
                    "wall_s": round(time.monotonic() - t0, 2),
                }
                _mark(f"ladder byzantine: {sc.name} FAILED: {e!r}")
        rung["wall_s"] = round(time.monotonic() - t_rung, 1)
        rung["passed"] = sum(
            1 for v in rung["scenarios"].values() if "failed" not in v
        )
        emit()
    else:
        _mark(f"skipping ladder byzantine (left {left():.0f}s)")

    # -- ladder rung #4: 256-node threshold coin with one Byzantine share
    if left() > 30:
        _mark("ladder coin256: keygen")
        from dag_rider_tpu.crypto import threshold as th

        n, f = 256, 85
        keys = th.ThresholdKeys.generate(n, f + 1)
        wave = 1
        shares = {
            i: th.sign_share(keys.share_sks[i], wave) for i in range(f + 2)
        }
        shares[0] = th.sign_share(keys.share_sks[0], wave + 13)  # Byzantine
        _mark("ladder coin256: poisoned aggregate + batched recovery")
        t0 = time.monotonic()
        sigma = th.aggregate(shares, keys.threshold)
        first_ok = sigma is not None and th.verify_group(
            keys.group_pk, wave, sigma
        )
        good = th.batch_verify_shares(keys.share_pks, wave, shares)
        sigma = th.aggregate(good, keys.threshold)
        ok = sigma is not None and th.verify_group(keys.group_pk, wave, sigma)
        dt = time.monotonic() - t0
        result["ladder"]["coin256"] = {
            "nodes": n,
            "threshold": f + 1,
            "byzantine_shares": 1,
            "first_aggregate_rejected": not first_ok,
            "recovered": ok,
            "good_shares": len(good),
            "recovery_s": round(dt, 2),
        }
        _mark(f"ladder coin256: recovered={ok} in {dt:.1f}s")
        emit()
        # coin aggregation on-device (VERDICT r3 #6): the lambda-weighted
        # share combination is a G1 MSM — time host vs device at the
        # n=256 share count (87 points pads to one 128-lane dispatch).
        if backend != "cpu" and left() > 45:
            try:
                from dag_rider_tpu.parallel.msm import ShardedMSM

                t0 = time.monotonic()
                host_sigma = th.aggregate(good, keys.threshold)
                host_s = time.monotonic() - t0
                sm = ShardedMSM()
                dev_sigma = th.aggregate(good, keys.threshold, msm=sm)
                t0 = time.monotonic()
                dev_sigma = th.aggregate(good, keys.threshold, msm=sm)
                dev_s = time.monotonic() - t0
                result["ladder"]["coin256"]["aggregate_host_s"] = round(
                    host_s, 3
                )
                result["ladder"]["coin256"]["aggregate_device_s"] = round(
                    dev_s, 3
                )
                result["ladder"]["coin256"]["aggregate_match"] = (
                    host_sigma == dev_sigma
                )
                _mark(
                    f"ladder coin256: aggregate host {host_s:.3f}s vs "
                    f"device {dev_s:.3f}s (match={host_sigma == dev_sigma})"
                )
                emit()
            except Exception as e:  # noqa: BLE001 — evidence, not headline
                _mark(f"ladder coin256: device aggregate FAILED: {e!r}")
    else:
        _mark(f"skipping ladder coin256 (only {left():.0f}s left)")

    # -- ladder rung #5 (Ed25519 half): committee n=1024 — comb tables at
    # 4x the north-star registry (536 MB device HBM) and a merged 4-round
    # verify. The MSM half of the rung is the msm phase below.
    if os.environ.get("DAGRIDER_BENCH_N1024", "1") == "1" and left() > 110:
        _mark("ladder verify1024: keygen + signing 4 rounds")
        n = 1024
        t0 = time.monotonic()
        verifier, batches, _ = _build_batches(n, 4)
        build_s = time.monotonic() - t0
        _mark(f"ladder verify1024: built in {build_s:.0f}s; compiling")
        # One compile only (the merged-bucket program): its warm masks are
        # the validity check — a separate single-round warm would compile
        # a second ~23 s program just to verify what the merged path
        # re-checks anyway.
        t0 = time.monotonic()
        masks = verifier.verify_rounds(batches)
        compile_s = time.monotonic() - t0
        if all(all(m) for m in masks):
            t0 = time.monotonic()
            masks = verifier.verify_rounds(batches)
            dt = time.monotonic() - t0
            total = sum(len(m) for m in masks)
            if all(all(m) for m in masks):
                result["ladder"]["verify1024"] = {
                    "nodes": n,
                    "sigs": total,
                    "build_s": round(build_s, 1),
                    "compile_s": round(compile_s, 1),
                    "sigs_per_sec": round(total / dt, 1),
                    "dispatch_ms": round(1e3 * dt, 2),
                }
                _mark(
                    f"ladder verify1024: {total / dt:,.0f} sigs/s "
                    f"({total} sigs/dispatch)"
                )
                emit()
            else:
                _mark("ladder verify1024: merged masks failed, discarding")
        else:
            _mark("ladder verify1024: warm batch failed, discarding")
    else:
        _mark(f"skipping ladder verify1024 (left {left():.0f}s)")

    # -- ladder rung #6 (round 7): mesh-sharded comb verify at the
    # flagship n=256, driven through the FULL async seam (warmup +
    # dispatch/resolve via VerifierPipeline) — sigs/s at 1 device vs the
    # mesh, same signatures, masks checked identical. When a real
    # multi-device mesh exists the record also refreshes
    # MULTICHIP_r06.json so the smoke file becomes a scaling curve.
    if os.environ.get("DAGRIDER_BENCH_SHARDED", "1") == "1" and left() > 120:
        try:
            from dag_rider_tpu.parallel.mesh import mesh_from_env
            from dag_rider_tpu.parallel.sharded_verifier import (
                ShardedTPUVerifier,
            )
            from dag_rider_tpu.verifier.pipeline import VerifierPipeline

            mesh = mesh_from_env()
            n_dev = int(np.prod(mesh.devices.shape))
            n = 256
            if n in built:
                single, sbatches, _ = built[n]
                sbatches = sbatches[:4]
            else:
                _mark("ladder verify_n256_sharded: signing 4 rounds")
                single, sbatches, _ = _build_batches(n, 4)
            s_total = sum(len(b) for b in sbatches)
            s_bucket = 256
            _mark(
                f"ladder verify_n256_sharded: {n_dev}-device mesh, "
                f"{s_total} sigs, bucket {s_bucket}"
            )

            def _timed_pipe(v):
                # `single` is built[256]'s verifier, reused by the prep
                # and chaos rungs after this one: borrow the bucket
                # under try/finally (driderlint:release)
                prev = getattr(v, "fixed_bucket", None)
                try:
                    v.fixed_bucket = s_bucket
                    pipe = VerifierPipeline(v, depth=2, warmup=True)
                    masks = pipe.verify_rounds(sbatches)  # compile + warm
                    times = []
                    for _ in range(3):
                        t0 = time.monotonic()
                        masks = pipe.verify_rounds(sbatches)
                        times.append(time.monotonic() - t0)
                    return masks, min(times)
                finally:
                    v.fixed_bucket = prev

            one_masks, one_dt = _timed_pipe(single)
            sharded = ShardedTPUVerifier(single.registry, mesh)
            mesh_masks, mesh_dt = _timed_pipe(sharded)
            match = mesh_masks == one_masks and all(
                all(m) for m in mesh_masks
            )
            entry = {
                "nodes": n,
                "sigs": s_total,
                "devices": n_dev,
                "bucket": s_bucket,
                "pipeline_depth": 2,
                "single_device_sigs_per_sec": round(s_total / one_dt, 1),
                "sharded_sigs_per_sec": round(s_total / mesh_dt, 1),
                "speedup": round(one_dt / mesh_dt, 2),
                "shard_batch": sharded.last_shard_batch,
                "shard_imbalance": round(sharded.last_shard_imbalance, 3),
                "masks_match": match,
            }
            result["ladder"]["verify_n256_sharded"] = entry
            _mark(
                f"ladder verify_n256_sharded: 1-dev "
                f"{s_total / one_dt:,.0f} sigs/s vs {n_dev}-dev "
                f"{s_total / mesh_dt:,.0f} sigs/s "
                f"(x{one_dt / mesh_dt:.2f}, match={match})"
            )
            emit()
            if match and n_dev > 1:
                rec = dict(entry)
                rec.update(
                    backend=backend,
                    device_kind=device_kind,
                    ok=True,
                    skipped=False,
                )
                from dag_rider_tpu import config as _cfg

                out_path = os.path.join(
                    _REPO, _cfg.env_str("DAGRIDER_MULTICHIP_OUT")
                )
                with open(out_path, "w") as fh:
                    json.dump(rec, fh, indent=1)
                    fh.write("\n")
                _mark(f"ladder verify_n256_sharded: wrote {out_path}")
        except Exception as e:  # noqa: BLE001 — rung is best-effort
            _mark(f"ladder verify_n256_sharded FAILED: {e!r}")
    else:
        _mark(f"skipping ladder verify_n256_sharded (left {left():.0f}s)")

    # -- ladder rung #7 (round 8): parallel host-prep 1-vs-N A/B at the
    # flagship n=256, through the FULL async seam (VerifierPipeline,
    # depth 2 — prep runs on the engine's seam thread, row-blocked
    # across the pool). On the CPU backend the device program dominates
    # wall clock, so the rung's headline is host_prep_ms_per_round on
    # both sides — a wall-clock tie with a prep-ms drop is the expected
    # CPU shape; on a real chip the prep drop surfaces in sigs/s.
    if (
        os.environ.get("DAGRIDER_BENCH_PREP", "1") == "1"
        and left() > 90
        and 256 in built
    ):
        try:
            from dag_rider_tpu.verifier.pipeline import VerifierPipeline
            from dag_rider_tpu.verifier.prep import default_prep_workers

            verifier, pbatches, _ = built[256]
            pbatches = pbatches[:4]
            p_total = sum(len(b) for b in pbatches)
            p_workers = int(
                os.environ.get("DAGRIDER_BENCH_PREP_WORKERS", "0")
            ) or min(4, os.cpu_count() or 1)
            _mark(
                f"ladder verify_n256_prep: {p_total} sigs, bucket 256, "
                f"workers 1 vs {p_workers}"
            )
            prev_bucket = verifier.fixed_bucket
            prev_workers = verifier.prep_workers
            try:
                verifier.fixed_bucket = 256  # same program shape as the
                # sharded rung's single-device side (already compiled
                # when that rung ran; persistent cache otherwise)
                pipe = VerifierPipeline(verifier, depth=2, warmup=True)
                sides = {}
                masks_by_side = {}
                for w in dict.fromkeys((1, p_workers)):
                    verifier.prep_workers = w
                    pipe.verify_rounds(pbatches)  # warm: pool + program
                    ps0 = verifier.prep_stats()
                    prep0 = verifier.total_prepare_s
                    times = []
                    for _ in range(3):
                        t0 = time.monotonic()
                        masks_by_side[w] = pipe.verify_rounds(pbatches)
                        times.append(time.monotonic() - t0)
                    ps1 = verifier.prep_stats()
                    d_prep = verifier.total_prepare_s - prep0
                    d_rows = ps1["rows_total"] - ps0["rows_total"]
                    d_par = ps1["rows_parallel"] - ps0["rows_parallel"]
                    sides[w] = {
                        "prep_workers": w,
                        "host_prep_ms_per_round": round(
                            1e3 * d_prep / (3 * len(pbatches)), 3
                        ),
                        "sigs_per_sec": round(3 * p_total / sum(times), 1),
                        "wall_s": round(min(times), 3),
                        "parallel_fraction": (
                            round(d_par / d_rows, 3) if d_rows else 0.0
                        ),
                    }
                serial, par = sides[1], sides[p_workers]
                match = all(
                    m == masks_by_side[1] for m in masks_by_side.values()
                ) and all(all(r) for r in masks_by_side[1])
                entry = {
                    "nodes": 256,
                    "sigs": p_total,
                    "bucket": 256,
                    "pipeline_depth": 2,
                    "serial": serial,
                    "parallel": par,
                    "prep_speedup": (
                        round(
                            serial["host_prep_ms_per_round"]
                            / par["host_prep_ms_per_round"],
                            2,
                        )
                        if par["host_prep_ms_per_round"]
                        else None
                    ),
                    "masks_match": match,
                }
                result["ladder"]["verify_n256_prep"] = entry
                _mark(
                    f"ladder verify_n256_prep: prep "
                    f"{serial['host_prep_ms_per_round']} ms/round @1w vs "
                    f"{par['host_prep_ms_per_round']} ms/round "
                    f"@{p_workers}w (x{entry['prep_speedup']}, "
                    f"match={match})"
                )
                emit()
            finally:
                # restore the shared verifier for the deferred merged
                # headline phase: bucket back, engine back to the env
                # default (leaving prep_workers None would pin the LAST
                # A/B side's pool)
                verifier.prep_workers = (
                    prev_workers
                    if prev_workers is not None
                    else default_prep_workers()
                )
                verifier.fixed_bucket = prev_bucket
        except Exception as e:  # noqa: BLE001 — rung is best-effort
            _mark(f"ladder verify_n256_prep FAILED: {e!r}")
    else:
        _mark(f"skipping ladder verify_n256_prep (left {left():.0f}s)")

    # -- ladder rung #8 (round 9): verify under injected chaos at the
    # flagship n=256, through the FULL async seam. Budgeted faults at
    # the dispatch/resolve seams poison depth-2 windows mid-stream; the
    # containment machinery must salvage, re-arm the ring and quarantine
    # with masks IDENTICAL to the clean run — the rung's headline is the
    # latency cost of containment (slowdown vs clean), never
    # correctness. Quarantined chunks re-verify on a clean DEVICE
    # verifier tier (the CPU reference would dominate the rung's wall
    # clock on a 1-core host and measure the oracle, not containment).
    if (
        os.environ.get("DAGRIDER_BENCH_CHAOS", "1") == "1"
        and left() > 60
        and 256 in built
    ):
        try:
            from dag_rider_tpu.verifier.faults import (
                VerifierFaultInjector,
                VerifierFaultPlan,
            )
            from dag_rider_tpu.verifier.pipeline import VerifierPipeline
            from dag_rider_tpu.verifier.tpu import TPUVerifier as _ChaosTPUV

            verifier, cbatches, _ = built[256]
            cbatches = cbatches[:4]
            c_total = sum(len(b) for b in cbatches)
            _mark(
                f"ladder verify_n256_chaos: {c_total} sigs, bucket 256, "
                f"budgeted dispatch/resolve faults"
            )
            prev_bucket = verifier.fixed_bucket
            inj = None
            try:
                verifier.fixed_bucket = 256
                pipe = VerifierPipeline(verifier, depth=2, warmup=True)
                pipe.verify_rounds(cbatches)  # warm program + ring
                t0 = time.monotonic()
                clean_masks = pipe.verify_rounds(cbatches)
                clean_dt = time.monotonic() - t0

                quarantine = _ChaosTPUV(verifier.registry)
                quarantine.fixed_bucket = 256
                quarantine.warmup()  # persistent-cache hit: same shape
                pipe.quarantine_verifier = quarantine
                inj = VerifierFaultInjector(
                    VerifierFaultPlan(
                        dispatch_raise=0.5,
                        resolve_raise=0.5,
                        max_faults=4,
                        seed=9,
                    )
                )
                inj.arm(verifier)
                t0 = time.monotonic()
                chaos_masks = pipe.verify_rounds(cbatches)
                chaos_dt = time.monotonic() - t0
            finally:
                if inj is not None:
                    inj.disarm()
                verifier.fixed_bucket = prev_bucket
            match = chaos_masks == clean_masks and all(
                all(m) for m in clean_masks
            )
            rs = pipe.resilience_stats()
            entry = {
                "nodes": 256,
                "sigs": c_total,
                "bucket": 256,
                "pipeline_depth": 2,
                "clean_sigs_per_sec": round(c_total / clean_dt, 1),
                "chaos_sigs_per_sec": round(c_total / chaos_dt, 1),
                "containment_slowdown": round(chaos_dt / clean_dt, 2),
                "faults_injected": inj.faults_injected,
                "fault_stats": dict(inj.stats),
                "poisoned_windows": rs["poisoned_windows"],
                "verify_quarantined": rs["quarantined"],
                "quarantine_rejected": rs["quarantine_rejected"],
                "masks_match": match,
            }
            result["ladder"]["verify_n256_chaos"] = entry
            _mark(
                f"ladder verify_n256_chaos: clean "
                f"{c_total / clean_dt:,.0f} sigs/s vs chaos "
                f"{c_total / chaos_dt:,.0f} sigs/s "
                f"(x{chaos_dt / clean_dt:.2f} slowdown, "
                f"{inj.faults_injected} faults, match={match})"
            )
            emit()
        except Exception as e:  # noqa: BLE001 — rung is best-effort
            _mark(f"ladder verify_n256_chaos FAILED: {e!r}")
    else:
        _mark(f"skipping ladder verify_n256_chaos (left {left():.0f}s)")

    # -- ladder rung #5 (single-host half): T-point G1 MSM on the device
    msm_t = int(os.environ.get("DAGRIDER_BENCH_MSM_T", "1024"))
    if msm_t > 0 and left() > 90:
        _mark(f"ladder msm{msm_t}: building points")
        import random

        from dag_rider_tpu.crypto import bls12381 as bls
        from dag_rider_tpu.parallel.msm import ShardedMSM

        rng = random.Random(3)
        base = bls.g1_mul(rng.randrange(1, bls.R))
        pts, acc = [], base
        for _ in range(msm_t):  # cheap distinct points: repeated doubling
            pts.append(acc)
            acc = bls.g1_double(acc)
        ks = [rng.randrange(0, bls.R) for _ in range(msm_t)]
        # auto impl picks the pallas tree engine on a real chip; a Mosaic
        # failure on the unproven-on-hardware kernel must not cost the
        # rung — fall back to the bit-identical jnp tree once (skipped
        # when auto already resolves to jnp: identical config).
        from dag_rider_tpu.ops.bls_msm import msm_impl as _msm_impl

        _shards = ShardedMSM().n_shards
        auto_impl = _msm_impl(max(4, msm_t) // _shards)
        impls = (auto_impl,) if auto_impl == "jnp" else (auto_impl, "jnp")
        for impl in impls:
            sm = ShardedMSM(impl=impl)
            try:
                _mark(
                    f"ladder msm{msm_t}: compiling + first run (impl={impl})"
                )
                t0 = time.monotonic()
                first = sm(ks, pts)
                compile_s = time.monotonic() - t0
                _mark(
                    f"ladder msm{msm_t}: first run {compile_s:.1f}s; timing warm run"
                )
                t0 = time.monotonic()
                warm = sm(ks, pts)
                dt = time.monotonic() - t0
            except Exception as e:  # noqa: BLE001 — rung is best-effort
                _mark(f"ladder msm{msm_t}: impl={impl} FAILED: {e!r}")
                continue
            ok = first == warm and first is not None
            result["ladder"][f"msm{msm_t}"] = {
                "points": msm_t,
                "devices": sm.n_shards,
                "impl": impl,
                "compile_plus_first_s": round(compile_s, 1),
                "warm_s": round(dt, 2),
                "points_per_sec": round(msm_t / dt, 1),
                "deterministic": ok,
            }
            _mark(
                f"ladder msm{msm_t}: warm {dt:.2f}s ({msm_t / dt:,.0f} points/s)"
            )
            emit()
            break
    elif msm_t > 0:
        _mark(f"skipping ladder msm{msm_t} (only {left():.0f}s left)")

    # -- Pallas-vs-XLA field-mul microbench (SURVEY §2a evidence; guarded:
    # a Mosaic lowering failure must never cost the headline number)
    if os.environ.get("DAGRIDER_BENCH_PALLAS", "1") == "1" and left() > 60:
        try:
            _mark("pallas probe: compiling field-mul chains (xla + pallas)")
            from dag_rider_tpu.ops import pallas_field

            xla_ms, pallas_ms, same = pallas_field.benchmark_vs_xla()
            result["phases"]["pallas_field_mul"] = {
                "batch": 8192,
                "chain": 64,
                "xla_ms": round(xla_ms, 2),
                "pallas_ms": round(pallas_ms, 2),
                "bit_identical": same,
                "speedup": round(xla_ms / pallas_ms, 2) if pallas_ms else None,
            }
            _mark(
                f"pallas probe: xla {xla_ms:.1f}ms vs pallas {pallas_ms:.1f}ms"
                f" (identical={same})"
            )
            emit()
        except Exception as e:  # noqa: BLE001 — evidence phase is best-effort
            result["phases"]["pallas_field_mul"] = {"error": repr(e)[:200]}
            _mark(f"pallas probe FAILED (non-fatal): {e!r}")
            emit()
    if not headline_first:
        # deferred CPU merged phase: only with whatever window remains
        # after every rung has had its chance (guarded inside)
        merged_phase(cpu_merged_n)
    _mark("measure: done")
    emit()


def main() -> int:
    """Measure on the chip, in this one process; refuse anything else."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(
            f"bench: jax is on {platform!r}, not a TPU — refusing to write "
            "a CPU timing under vertex_sigs_per_sec",
            file=sys.stderr,
        )
        return 2
    _measure()
    return 0


if __name__ == "__main__":
    sys.exit(main())
