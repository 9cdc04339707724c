"""Driver ``inloop``: a whole committee in one interpreter over the
in-memory transport, a mempool in front of every validator, the device
verifier in the loop — ``chip_smoke.py`` phase A's shape on the wall
clock.

``build`` makes the simulation the configuration states and hands it to
``assemble``; ``run_window`` drives an assembled stack (a test assembles
one over the host verifier). The cycle: inject what is due ->
``build_blocks`` -> ``sim.run(max_messages=n*n)``.

The benchmark keeps its own books: a transaction is timed from when it
was DUE to its ``a_deliver`` at the validator it was submitted to (that
validator's own vertex, at its own view). A few forged vertices (the
traffic's ``forged_vertices_per_s``, by the clock, the five wrong kinds
in turn) go out beside the honest ones, each claiming a round a few
ahead of the committee so that it never meets its honest twin; every
receiver must reject each, which gives ``correct`` a verdict to hold the
accept masks to. A forged vertex is n-1 messages more than the round's:
the cycle that sends it pumps that many more, so the honest stream keeps
its place whatever the forged rate is.

The masks compared are the window's own: :class:`MaskRecorder` keeps the
vertices and the mask of every call the views' shared verifier answers.
"""

from __future__ import annotations

import random
import time
from typing import Dict, List, Optional

import jax

from benchmarks.harness import reference, roundpool
from benchmarks.harness.loadgen import LoadGenerator

#: containment / retry counters that stay zero on a sound run
QUIET = ("poisoned_windows", "quarantined", "quarantine_rejected", "retries", "fallbacks")
#: how long past the window's close a transaction is waited for
DRAIN_BOUND_S = 60.0
#: the committee runs this far before the window opens (one wave and its coin)
WARM_ROUNDS = 5


class MaskRecorder:
    """What the views' shared verifier was asked and what it answered,
    call by call: ``(vertices, mask, seam seconds, dispatches)``. Sits on
    the dispatch window's ``run_coalesced`` where ``Simulation.run`` has
    built one (the device verifier), else on the verifier's
    ``verify_rounds`` (a host verifier or a control in a test)."""

    def __init__(self, sim):
        self.calls: List[tuple] = []
        pipe, shared = sim._verify_pipe, sim.processes[0].verifier
        if pipe is not None:
            inner = pipe.run_coalesced

            def run_coalesced(vertices, **kw):
                d0 = pipe.dispatches
                mask = inner(vertices, **kw)
                self.calls.append(
                    (list(vertices), list(mask), pipe.last_seam_s, pipe.dispatches - d0)
                )
                return mask

            pipe.run_coalesced = run_coalesced
        else:
            inner_rounds = shared.verify_rounds

            def verify_rounds(rounds):
                t0 = time.perf_counter()
                masks = inner_rounds(rounds)
                dt = time.perf_counter() - t0
                for r, m in zip(rounds, masks):
                    self.calls.append((list(r), list(m), dt / len(rounds), 1))
                return masks

            shared.verify_rounds = verify_rounds


class Stack:
    def __init__(self, sim, config: dict, traffic: dict, seed: int):
        self.sim = sim
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.n = sim.cfg.n
        self.keys = reference.Keys(self.n)
        self.gen = LoadGenerator.from_traffic(traffic, seed)
        self.rng = random.Random(seed ^ 0x5EED)
        #: per validator: (stamp, vertex) of its OWN vertices delivered at
        #: its own view
        self.own: List[list] = [[] for _ in range(self.n)]
        self.mempools: list = []
        self.forged_ids: set = set()
        self.recorder: Optional[MaskRecorder] = None
        self.first_kind = self.rng.randrange(len(roundpool.KINDS))
        self.setup_parts: Dict[str, float] = {}


def sim_config(config: dict):
    from dag_rider_tpu.config import Config

    return Config(
        n=config["n"],
        coin=config["coin"],
        propose_empty=config["propose_empty"],
        gc_depth=config["gc_depth"],
        wave_length=config["wave_length"],
    )


def build(config: dict, traffic: dict, seed: int) -> Stack:
    from dag_rider_tpu.consensus.scenarios import coin_factory
    from dag_rider_tpu.consensus.simulator import Simulation

    t0 = time.monotonic()
    cfg = sim_config(config)
    if cfg.f != config["f"]:
        raise ValueError(f"configuration states f={config['f']}, program derives {cfg.f}")
    sim = Simulation(
        cfg, verifier="device", coin_factory=coin_factory(config["coin"], cfg.n, cfg.f)
    )
    built = time.monotonic() - t0
    stack = assemble(sim, config, traffic, seed)
    stack.setup_parts["build_s"] = built
    vs = sim.processes[0].verifier.stats()
    stack.setup_parts["tables_s"] = vs["table_build_s"]
    stack.setup_parts["compile_or_cache_load_s"] = sum(vs["compile_s"].values())
    return stack


def assemble(sim, config: dict, traffic: dict, seed: int) -> Stack:
    """Mempools and the benchmark's books on a built simulation, then the
    committee's first wave so that every program and path is warm."""
    from dag_rider_tpu.config import MempoolConfig

    stack = Stack(sim, config, traffic, seed)
    registry = sim.processes[0].verifier.registry
    if list(registry.public_keys) != stack.keys.public:
        raise AssertionError("the program's committee keys are not the configuration's")
    stack.mempools = sim.attach_mempools(MempoolConfig(), clock=time.monotonic)
    clock = time.monotonic
    for i, p in enumerate(sim.processes):
        p.on_deliver = _own_deliveries(i, p.on_deliver, stack.own[i], clock)
    t0 = time.monotonic()
    n = stack.n
    while max(p.round for p in sim.processes) < WARM_ROUNDS:
        sim.run(max_messages=n * n)
    stack.setup_parts["warm_rounds_s"] = time.monotonic() - t0
    stack.recorder = MaskRecorder(sim)
    return stack


def control_stack(control, config: dict, traffic: dict, seed: int) -> Stack:
    """This driver's stack with ``control`` in the device verifier's
    place, shared by every view as the device verifier is."""
    from dag_rider_tpu.consensus.scenarios import coin_factory
    from dag_rider_tpu.consensus.simulator import Simulation
    from dag_rider_tpu.verifier.base import KeyRegistry, VertexSigner

    cfg = sim_config(config)
    registry, seeds = KeyRegistry.generate(cfg.n)
    shared = control(registry)
    signers = [VertexSigner(s) for s in seeds]
    sim = Simulation(
        cfg,
        verifier_factory=lambda i: shared,
        signer_factory=lambda i: signers[i],
        coin_factory=coin_factory(config["coin"], cfg.n, cfg.f),
    )
    return assemble(sim, config, traffic, seed)


def _own_deliveries(i: int, prev, sink: list, clock):
    def deliver(v):
        prev(v)
        if v.id.source == i:
            sink.append((clock(), v))

    return deliver


def _forge(stack: Stack, rnd: int):
    """One wrong vertex for round ``rnd`` and the message that carries
    it; the kind and the source come from the seed. Where every source
    of ``rnd`` is already forged (a small committee whose view 0 is
    slow to advance), under the first round after it that has one free."""
    from dag_rider_tpu.core.types import BroadcastMessage

    rng, n = stack.rng, stack.n
    while all((rnd, s) in stack.forged_ids for s in range(n)):
        rnd += 1
    q = roundpool.quorum(n)
    strong = tuple((rnd - 1, s) for s in range(q))
    kind = roundpool.KINDS[(stack.first_kind + len(stack.forged_ids)) % len(roundpool.KINDS)]
    while True:
        honest = roundpool.sign(
            stack.keys, rnd, rng.randrange(n), (b"forged".ljust(32, b"."),), strong
        )
        wrong = roundpool.corrupt(honest, kind, n, rng)
        # a second vertex under one (round, source) would be dropped as a
        # duplicate before any verifier saw it
        if (rnd, wrong.source) not in stack.forged_ids:
            break
    stack.forged_ids.add((rnd, wrong.source))
    (vertex,) = roundpool.to_vertices([wrong])
    return BroadcastMessage(vertex=vertex, round=rnd, sender=wrong.source)


def _counter_sum(sim, name: str) -> int:
    return sum(p.metrics.counters.get(name, 0) for p in sim.processes)


def _pipe_stats(sim) -> dict:
    """The shared verifier's window, once ``run`` has built it (a host
    verifier has none)."""
    return sim._verify_pipe.stats() if sim._verify_pipe is not None else {}


def _inject(stack: Stack, books: Dict[bytes, list], until: float, at: float) -> None:
    """Submit every arrival due by ``until`` at its validator, at window
    time ``at``; books[tx] = [due, injected, validator, accepted,
    delivered stamp]."""
    n = stack.n
    for due, c, tx in stack.gen.events_until(until):
        res = stack.mempools[c % n].submit((tx,), client=f"c{c}")
        books[tx] = [due, at, c % n, res.accepted == 1, None]


def _feed(stack: Stack, force: bool = False) -> int:
    """Built blocks from every mempool to its process; returns the
    deepest proposal queue met."""
    deepest = 0
    for p, mp in zip(stack.sim.processes, stack.mempools):
        staged = len(p.blocks_to_propose)
        deepest = max(deepest, staged)
        for b in mp.build_blocks(force=force, staged=staged):
            p.submit(b)
    return deepest


def run_window(stack: Stack, seconds: float, tracer=None) -> dict:
    """The measured window and the bounded drain after it. Returns what
    was observed; nothing is compared here."""
    sim, n = stack.sim, stack.n
    procs = sim.processes
    traffic = stack.traffic
    lead = traffic["forged_round_lead"]
    forge_every = 1.0 / traffic["forged_vertices_per_s"]
    annotate = jax.profiler.TraceAnnotation

    books: Dict[bytes, list] = {}
    settled = [len(o) for o in stack.own]  # warm-up deliveries are not ours
    staged_max = 0
    forged = 0
    cycles = 0
    #: (seconds into the window, view 0's round, view 0's decided wave) per cycle
    timeline: List[tuple] = []
    calls0 = len(stack.recorder.calls)
    rejects0 = _counter_sum(sim, "msgs_rejected_signature")
    round0 = max(p.round for p in procs)

    t0 = time.monotonic()
    while True:
        t = time.monotonic() - t0
        if t >= seconds:
            break
        if tracer is not None:
            tracer.tick(t)
        with annotate("bench.inject"):
            _inject(stack, books, min(t, seconds), t)
            # forged vertex k is due at (k + 1/2) / rate seconds
            send = int(t / forge_every + 0.5) - forged
            for _ in range(send):
                sim.transport.broadcast(_forge(stack, max(p.round for p in procs) + lead))
            forged += send
        with annotate("bench.build_blocks"):
            staged_max = max(staged_max, _feed(stack))
        with annotate("bench.sim_run"):
            sim.run(max_messages=n * n + send * (n - 1))
        cycles += 1
        timeline.append(
            (round(time.monotonic() - t0, 3), procs[0].round, procs[0].decided_wave)
        )
    t_close = time.monotonic()
    if tracer is not None:
        tracer.stop()
    window_s = t_close - t0
    rounds = max(p.round for p in procs) - round0
    calls_in_window = len(stack.recorder.calls)

    # what was due in the window's last cycle is still owed its injection
    _inject(stack, books, seconds, window_s)
    owed = sum(1 for b in books.values() if b[3])
    done = _settle(stack, books, settled)
    _feed(stack, force=True)
    while done < owed and time.monotonic() - t_close < DRAIN_BOUND_S:
        _feed(stack)
        sim.run(max_messages=n * n)
        done += _settle(stack, books, settled)
    # the forged vertices still queued behind the last round's messages
    for _ in range(2):
        sim.run(max_messages=n * n)
    t_end = time.monotonic()

    latencies, lags, in_window = [], [], 0
    for due, injected, _, accepted, stamp in books.values():
        lags.append(injected - due)
        if stamp is None:
            latencies.append(t_end - t0 - due)  # it has waited this long
        else:
            latencies.append(stamp - t0 - due)
            if stamp <= t0 + seconds:
                in_window += 1
    shed = sum(1 for b in books.values() if not b[3])
    calls = stack.recorder.calls[calls0:calls_in_window]

    def carries_a_round(call) -> bool:
        """One chunk, more than half full."""
        return call[3] == 1 and n < 2 * len(call[0]) <= 2 * n

    return {
        "t_open": t0,
        "seconds": seconds,
        "attempted": len(books),
        "failed": shed + (owed - done),
        "samples": {
            "commit_latency_s": latencies,
            "inject_lag_s": lags,
            "seam_full_s": [c[2] for c in calls if carries_a_round(c)],
            "seam_other_s": [c[2] for c in calls if not carries_a_round(c)],
        },
        "counters": {
            "tx_delivered_in_window": in_window,
            "tx_shed": shed,
            "tx_undelivered": owed - done,
            "staged_blocks_max": staged_max,
            "rounds_advanced": rounds,
            "window_s": window_s,
            "drain_s": t_end - t_close,
            "cycles": cycles,
            "seam_s": sum(c[2] for c in calls),
            "dispatches": sum(c[3] for c in calls),
            "forged_sent": forged,
            "sig_rejects": _counter_sum(sim, "msgs_rejected_signature") - rejects0,
        },
        "books": books,
        "timeline": timeline,
        "verify_calls": stack.recorder.calls[calls0:],
    }


def _settle(stack: Stack, books: Dict[bytes, list], settled: List[int]) -> int:
    """Close the books of transactions newly delivered at their own
    validator; returns how many."""
    done = 0
    for i, own in enumerate(stack.own):
        for stamp, v in own[settled[i]:]:
            for tx in v.block.transactions:
                b = books.get(tx)
                if b is not None and b[2] == i and b[4] is None:
                    b[4] = stamp
                    done += 1
        settled[i] = len(own)
    return done


def check(stack: Stack, observed: dict) -> dict:
    """Each number compared, beside its limit. The reference gives its
    own verdict on every vertex the shared verifier was asked about from
    the window's opening on and holds each mask to it, verifies every
    vertex the longest view delivered, explains that view's order by
    DAG-Rider's rule, holds every other view to it, and audits the
    acknowledged transactions."""
    sim, n, keys = stack.sim, stack.n, stack.keys
    c = observed["counters"]
    records: Dict[int, tuple] = {}
    verdicts: Dict[int, bool] = {}

    def record(v):
        r = records.get(id(v))
        if r is None:
            r = records[id(v)] = (v.id.round, v.id.source, v.signature, v.block.transactions)
        return r

    def verdict(v) -> bool:
        ok = verdicts.get(id(v))
        if ok is None:
            msg = reference.signing_bytes(
                v.id.round,
                v.id.source,
                v.block.transactions,
                v.strong_edges,
                v.weak_edges,
                v.coin_share or b"",
            )
            ok = verdicts[id(v)] = keys.verify(v.id.source, msg, v.signature or b"")
        return ok

    mismatches = 0
    for vertices, mask, _, _ in observed["verify_calls"]:
        mismatches += abs(len(vertices) - len(mask))
        mismatches += sum(1 for v, bit in zip(vertices, mask) if bool(bit) != verdict(v))
    logs = [[record(v) for v in sim.deliveries[i]] for i in range(n)]
    longest = max(sim.deliveries, key=len)
    bad = sum(1 for v in longest if not verdict(v))
    order = reference.delivered_order_faults(logs)
    unexplained = reference.order_unexplained(
        [(v.id.round, v.id.source, v.strong_edges + v.weak_edges) for v in longest],
        gc_depth=stack.config["gc_depth"],
        wave_length=stack.config["wave_length"],
    )
    books = observed["books"]
    seen: Dict[bytes, int] = {}
    for v in longest:
        for tx in v.block.transactions:
            if tx in books:
                seen[tx] = seen.get(tx, 0) + 1
    pipe = _pipe_stats(sim)
    vstats = getattr(sim.processes[0].verifier, "stats", None)
    programs = vstats()["compile_s"] if callable(vstats) else {"host": 0}
    return {
        "mask_mismatches": {"value": mismatches, "limit": 0},
        "sig_rejects_off_expected": {
            "value": abs(c["sig_rejects"] - c["forged_sent"] * (n - 1)),
            "limit": 0,
        },
        "delivered_bad_signatures": {"value": bad, "limit": 0},
        "order_unexplained": {"value": unexplained, "limit": 0},
        "views_diverged": {"value": order["views_diverged"], "limit": 0},
        "vertices_delivered_twice": {"value": order["records_twice"], "limit": 0},
        "tx_lost": {"value": c["tx_undelivered"], "limit": 0},
        "tx_delivered_twice": {
            "value": sum(1 for k in seen.values() if k > 1),
            "limit": 0,
        },
        "contained_or_retried": {
            "value": sum(pipe.get(k, 0) for k in QUIET),
            "limit": 0,
        },
        "programs_beyond_one": {"value": len(programs) - 1, "limit": 0},
    }


def close(stack: Stack) -> None:
    """Nothing outlives the process: no child, no socket, no file."""
