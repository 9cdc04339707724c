"""The span primitive (``obs/spans.py``) and the sites that open it.

The book is process-wide, so every test reads what it added to it: the
difference of two snapshots.
"""

import ast
import gc
import glob
import os
import subprocess
import sys
import threading

import pytest

from dag_rider_tpu import obs
from dag_rider_tpu.analysis import events as events_checker
from dag_rider_tpu.config import Config, MempoolConfig
from dag_rider_tpu.consensus.scenarios import coin_factory
from dag_rider_tpu.consensus.simulator import Simulation
from dag_rider_tpu.core.types import Block
from dag_rider_tpu.mempool import Mempool
from dag_rider_tpu.obs import spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEYS = ("count", "total_ns", "max_ns", "child_ns")


def added(before: dict, after: dict) -> dict:
    """What the book gained between two snapshots (``max_ns`` is the
    later one's: a maximum has no difference)."""
    out = {"spans": {}, "counts": {}}
    for name, s in after["spans"].items():
        b = before["spans"].get(name, dict.fromkeys(KEYS, 0))
        d = {k: s[k] - b[k] for k in KEYS}
        d["max_ns"] = s["max_ns"]
        if d["count"]:
            out["spans"][name] = d
    for name, n in after["counts"].items():
        if n - before["counts"].get(name, 0):
            out["counts"][name] = n - before["counts"].get(name, 0)
    return out


def self_ns(stat: dict) -> int:
    return stat["total_ns"] - stat["child_ns"]


# -- the primitive -----------------------------------------------------------


def test_child_ns_of_a_parent_is_the_sum_of_its_childrens_totals():
    before = spans.snapshot()
    with obs.span("pump.run") as run:
        with obs.span("pump.deliver") as deliver:
            with obs.span("pump.inbox"):
                pass
        with obs.span("pump.step") as step:
            pass
        with obs.span("pump.step"):
            pass
    got = added(before, spans.snapshot())["spans"]
    assert got["pump.run"]["count"] == 1 and got["pump.step"]["count"] == 2
    assert got["pump.run"]["total_ns"] == run.ns
    assert (
        got["pump.run"]["child_ns"]
        == got["pump.deliver"]["total_ns"] + got["pump.step"]["total_ns"]
    )
    # a grandchild is its parent's child, not its grandparent's
    assert got["pump.deliver"]["child_ns"] == got["pump.inbox"]["total_ns"]
    assert got["pump.step"]["max_ns"] >= step.ns > 0
    assert deliver.seconds == deliver.ns * 1e-9
    assert self_ns(got["pump.run"]) >= 0


def test_the_same_name_nested_in_itself_still_adds_up():
    before = spans.snapshot()
    with obs.span("pump.wave") as outer:
        with obs.span("pump.wave"):
            with obs.span("coin.combine") as leaf:
                pass
    got = added(before, spans.snapshot())["spans"]
    tree_self = self_ns(got["pump.wave"]) + self_ns(got["coin.combine"])
    assert got["pump.wave"]["count"] == 2
    assert tree_self == outer.ns and leaf.ns <= outer.ns


def test_a_span_closed_by_an_exception_still_closes():
    before = spans.snapshot()
    with pytest.raises(ValueError):
        with obs.span("pump.run"):
            with obs.span("pump.step"):
                raise ValueError("boom")
    with obs.span("pump.collect"):  # the stack is empty again: a root
        pass
    got = added(before, spans.snapshot())["spans"]
    assert got["pump.run"]["count"] == got["pump.step"]["count"] == 1
    assert got["pump.run"]["child_ns"] == got["pump.step"]["total_ns"]
    assert got["pump.collect"]["child_ns"] == 0


def test_record_books_a_closed_span_under_nothing():
    before = spans.snapshot()
    with obs.span("sidecar.rpc"):
        spans.record("sidecar.between_rpcs", 5_000)
        spans.record("sidecar.between_rpcs", 7_000)
    got = added(before, spans.snapshot())["spans"]
    wait = got["sidecar.between_rpcs"]
    assert (wait["count"], wait["total_ns"], wait["child_ns"]) == (2, 12_000, 0)
    assert wait["max_ns"] >= 7_000
    assert got["sidecar.rpc"]["child_ns"] == 0


def test_two_threads_closing_the_same_name_lose_no_count():
    laps = 10_000
    before = spans.snapshot()
    start = threading.Barrier(2)

    def work():
        start.wait(timeout=30)
        for _ in range(laps):
            with obs.span("verify_batch.prepare"):
                obs.count("pump.round_advance")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    got = added(before, spans.snapshot())
    assert got["spans"]["verify_batch.prepare"]["count"] == 2 * laps
    assert got["counts"]["pump.round_advance"] == 2 * laps
    # each thread's spans were roots of its own stack
    assert got["spans"]["verify_batch.prepare"]["child_ns"] == 0


def test_watch_gc_times_full_collections_only_and_installs_once():
    spans.watch_gc()
    spans.watch_gc()
    assert gc.callbacks.count(spans._on_gc) == 1
    before = spans.snapshot()
    gc.collect(0)
    gc.collect(1)
    assert "host.gc" not in added(before, spans.snapshot())["spans"]
    with obs.span("pump.run"):
        gc.collect(2)
    got = added(before, spans.snapshot())["spans"]
    assert got["host.gc"]["count"] == 1 and got["host.gc"]["total_ns"] > 0
    # it nests under the span it interrupted
    assert got["pump.run"]["child_ns"] == got["host.gc"]["total_ns"]


def test_a_span_never_loads_jax():
    code = (
        "import sys\n"
        "from dag_rider_tpu import obs\n"
        "from dag_rider_tpu.obs import spans\n"
        "spans.watch_gc()\n"
        "with obs.span('pump.run'):\n"
        "    obs.count('pump.round_advance')\n"
        "assert spans.snapshot()['spans']['pump.run']['count'] == 1\n"
        "print('jax' in sys.modules)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


def _host_span_names(trace_dir: str) -> set:
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    return names


def test_the_timeline_gets_a_span_only_while_a_profiler_session_is_open(tmp_path):
    import jax

    before = spans.snapshot()
    with obs.span("pump.collect"):  # no session: the book alone
        pass
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert jax.profiler.TraceAnnotation.is_enabled()
        with obs.span("pump.run"):
            with obs.span("pump.step"):
                pass
    finally:
        jax.profiler.stop_trace()
    with obs.span("pump.apply"):
        pass
    names = _host_span_names(str(tmp_path))
    assert {"pump.run", "pump.step"} <= names
    assert not {"pump.collect", "pump.apply"} & names
    got = added(before, spans.snapshot())["spans"]
    assert all(
        got[n]["count"] == 1
        for n in ("pump.collect", "pump.run", "pump.step", "pump.apply")
    )


# -- driderlint: names are registered, the primitive is the only one ---------


def _synthetic(src):
    return [("dag_rider_tpu/fake.py", ast.parse(src), src)]


@pytest.mark.parametrize(
    "src, needle",
    [
        ('with obs.span("pump.typo"):\n    pass\n', "pump.typo"),
        ('spans.record("sidecar.nah", 3)\n', "sidecar.nah"),
        ('obs.spans.record("mempool.wiat", 3)\n', "mempool.wiat"),
        ('obs.count("pump.round_advanced")\n', "pump.round_advanced"),
        (
            'with jax.profiler.TraceAnnotation("pump.run"):\n    pass\n',
            "TraceAnnotation",
        ),
    ],
)
def test_events_checker_catches_planted_span_violation(src, needle):
    findings = events_checker.run(_synthetic(src), "/nonexistent")
    assert len(findings) == 1 and needle in findings[0].message
    assert findings[0].checker == "events"


def test_events_checker_accepts_registered_spans_and_other_receivers():
    src = (
        'with obs.span("pump.run"):\n    pass\n'
        'obs.count("pump.round_advance")\n'
        'spans.record("mempool.wait", 1)\n'
        "with obs.span(name):\n    pass\n"  # non-literal: out of scope
        '"abc".count("a")\n'
        'rows.count("pump.nope")\n'
    )
    assert events_checker.run(_synthetic(src), "/nonexistent") == []


def test_every_literal_span_name_is_registered_and_registered_names_are_used():
    from dag_rider_tpu.analysis.core import discover

    files = discover(ROOT)
    assert events_checker.run(files, ROOT) == []
    src = "\n".join(s for rel, _, s in files if not rel.endswith("obs/spans.py"))
    unused = [n for n in spans.KNOWN_SPANS | spans.KNOWN_COUNTS if f'"{n}"' not in src]
    assert unused == ["host.gc"]  # opened by spans.py's own collector hook


# -- the sites: host pump ----------------------------------------------------

PUMP_TREE = (
    "pump.run", "pump.deliver", "pump.collect", "pump.verify", "pump.apply",
    "pump.step", "pump.inbox", "pump.insert", "pump.propose", "pump.wave",
    "pump.chain", "pump.order", "pump.prune", "pump.sync", "coin.share",
    "coin.combine", "sign.vertex", "host.gc",
)


class _NoSpan:
    """``obs.span`` replaced by a no-op: the oracle run."""

    ns = 0
    seconds = 0.0

    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


def _committee(pump: str):
    cfg = Config(
        n=4, coin="threshold_bls", propose_empty=True, pump=pump,
        sync_window=2, gc_depth=10,  # the fourth wave's decision prunes
    )
    sim = Simulation(
        cfg, verifier="cpu", coin_factory=coin_factory("threshold_bls", 4, cfg.f)
    )
    now = [0.0]
    mempools = sim.attach_mempools(MempoolConfig(), clock=lambda: now[0])
    blocks = 0
    for cycle in range(60):
        now[0] += 0.5
        for i, (p, mp) in enumerate(zip(sim.processes, mempools)):
            if cycle % 2 == 0:
                mp.submit((b"tx-%d-%d" % (i, cycle),), client="c", now=now[0] - 0.25)
                built = mp.build_blocks(force=True)
                blocks += len(built)
                for b in built:
                    p.submit(b)
        sim.run(max_messages=16)
        if min(p.decided_wave for p in sim.processes) >= 4:
            break
    return sim, blocks


@pytest.mark.parametrize("pump", ["scalar", "vector"])
def test_simulation_fills_the_book_and_spans_change_nothing(pump, monkeypatch):
    before = spans.snapshot()
    sim, blocks = _committee(pump)
    got = added(before, spans.snapshot())
    assert min(p.decided_wave for p in sim.processes) >= 2
    sim.check_agreement()

    # a committee that loses nothing never asks for a catch-up sync
    want = set(PUMP_TREE) - {"host.gc", "pump.sync"}
    assert "pump.sync" not in got["spans"]
    if pump == "scalar":
        want.discard("pump.inbox")  # the scalar pump admits in on_message
    else:
        # the vector pump admits in step(), so each process verifies its
        # own batch there (pump.verify under pump.insert): no fan-out
        want.discard("pump.apply")
    assert want <= set(got["spans"])
    rounds = max(p.round for p in sim.processes)
    assert all(p.round == rounds for p in sim.processes)
    assert got["counts"]["pump.round_advance"] == 4 * rounds
    # one closed wait per block that carried a transaction, each 0.25 s
    # on the mempools' clock or longer
    assert got["spans"]["mempool.wait"]["count"] == blocks > 0
    assert got["spans"]["mempool.wait"]["total_ns"] >= blocks * 0.25e9 - blocks

    # self times of pump.run's tree add up to its total: the blocks fed
    # between run() calls step their process outside any pump.run, so
    # the tree may read a little over, never under
    total = got["spans"]["pump.run"]["total_ns"]
    tree = sum(self_ns(got["spans"][n]) for n in PUMP_TREE if n in got["spans"])
    assert total <= tree <= 1.01 * total

    # byte-identity is the oracle: the same run with the primitive gone
    monkeypatch.setattr(obs, "span", _NoSpan)
    monkeypatch.setattr(obs, "count", lambda name, by=1: None)
    monkeypatch.setattr(spans, "record", lambda name, ns: None)
    quiet = spans.snapshot()
    oracle, _ = _committee(pump)
    assert not added(quiet, spans.snapshot())["spans"]
    log = lambda s: [[(v.id, v.digest()) for v in d] for d in s.deliveries]  # noqa: E731
    assert log(sim) == log(oracle)
    assert sim.transport.delivered_count == oracle.transport.delivered_count


def test_phase_events_carry_the_spans_durations():
    from dag_rider_tpu.utils import slog

    log, records = slog.capture()
    before = spans.snapshot()
    sim = Simulation(Config(n=4, propose_empty=True), verifier="cpu", log=log)
    sim.run(max_messages=64)
    got = added(before, spans.snapshot())["spans"]
    pumps = [r["dur_s"] for r in records if r["event"] == "phase_pump"]
    verifies = [r["dur_s"] for r in records if r["event"] == "phase_verify"]
    assert len(pumps) == got["pump.deliver"]["count"] == got["pump.step"]["count"]
    # the lockstep driver runs the views' inbox checks between the two
    # (no step finds anything in its inbox: every pump.inbox is the driver's)
    assert got["pump.inbox"]["count"] == len(pumps)
    in_spans = sum(got[f"pump.{p}"]["total_ns"] for p in ("deliver", "inbox", "step")) * 1e-9
    assert sum(pumps) == pytest.approx(in_spans, rel=1e-9)
    assert len(verifies) == got["pump.verify"]["count"] > 0
    assert sum(verifies) == pytest.approx(got["pump.verify"]["total_ns"] * 1e-9, rel=1e-9)


def test_pump_sync_spans_a_catch_up_request_and_nothing_else():
    from dag_rider_tpu.consensus.process import Process
    from dag_rider_tpu.core.types import BroadcastMessage, Vertex, VertexID
    from dag_rider_tpu.transport.memory import InMemoryTransport

    cfg = Config(n=4, sync_patience=2, sync_request_cooldown_s=0.0)
    p = Process(cfg, 0, InMemoryTransport())
    p.defer_steps = True
    p.start()
    # a round-2 vertex whose round-1 predecessors never arrived: stuck
    orphan = Vertex(
        id=VertexID(2, 1), block=Block((b"x",)),
        strong_edges=tuple(VertexID(1, j) for j in range(3)),
    )
    p.on_message(BroadcastMessage(vertex=orphan, round=2, sender=1))
    before = spans.snapshot()
    p.step()  # patience not yet out: no request, no span
    assert "pump.sync" not in added(before, spans.snapshot())["spans"]
    for _ in range(4):
        p.step()
    got = added(before, spans.snapshot())["spans"]
    assert p.metrics.counters["sync_requested"] >= 1
    assert got["pump.sync"]["count"] == p.metrics.counters["sync_requested"]


# -- the sites: mempool ------------------------------------------------------


def test_mempool_wait_runs_from_the_earliest_submit_to_the_proposal():
    now = [10.0]
    mp = Mempool(MempoolConfig(), clock=lambda: now[0])
    mp.submit((b"a" * 32,), client="c", now=10.0)
    mp.submit((b"b" * 32,), client="c", now=11.5)
    (block,) = mp.build_blocks(now=12.0, force=True)
    before = spans.snapshot()
    now[0] = 13.0
    mp.observe_proposed(block)
    mp.observe_proposed(Block())  # an empty block waited for nothing
    mp.observe_proposed(Block((b"z" * 32,)))  # a peer's: not ours to time
    got = added(before, spans.snapshot())["spans"]
    assert got["mempool.wait"]["count"] == 1
    assert got["mempool.wait"]["total_ns"] == 3_000_000_000


# -- the sites: sidecar handler ----------------------------------------------


def test_sidecar_handler_spans_every_rpc_and_the_wait_between_them():
    from dag_rider_tpu.core.types import Vertex, VertexID
    from dag_rider_tpu.verifier.base import KeyRegistry, VertexSigner
    from dag_rider_tpu.verifier.cpu import CPUVerifier
    from dag_rider_tpu.verifier.sidecar import RemoteVerifier, VerifierSidecarServer

    registry, seeds = KeyRegistry.generate(4)
    strong = tuple(VertexID(0, j) for j in range(3))
    batch = [
        VertexSigner(seeds[i]).sign_vertex(
            Vertex(id=VertexID(1, i), block=Block((b"tx%d" % i,)), strong_edges=strong)
        )
        for i in range(4)
    ]
    # one forged: signed by its neighbour's key
    batch[2] = VertexSigner(seeds[3]).sign_vertex(
        Vertex(id=VertexID(1, 2), block=Block((b"forged",)), strong_edges=strong)
    )
    want = CPUVerifier(registry).verify_batch(batch)
    assert want == [True, True, False, True]
    before = spans.snapshot()
    server = VerifierSidecarServer(CPUVerifier(registry))
    remote = RemoteVerifier(server.address, timeout=30.0)
    k = 5
    try:
        for _ in range(k):
            assert remote.verify_batch(batch) == want
    finally:
        remote.close()
        server.stop()
    got = added(before, spans.snapshot())["spans"]
    assert got["sidecar.rpc"]["count"] == k
    assert got["sidecar.between_rpcs"]["count"] == k - 1
    assert got["sidecar.decode"]["count"] == k
    assert got["sidecar.decode"]["total_ns"] <= got["sidecar.rpc"]["child_ns"]
