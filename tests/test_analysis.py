"""driderlint non-vacuity suite (round 14).

Every checker is proven by a PLANTED violation, mirroring the
consensus/invariants.py pattern: a checker that cannot fail is not a
checker. Synthetic files are fed through the same ``run(files, root)``
entry the production runner uses, so these tests exercise the real
code path, not a parallel one. The clean-tree test at the bottom is
the other half of the acceptance criterion: the suite must pass on
today's repo with zero unexplained allowlist entries.
"""

import ast
import os
import threading
import time

import pytest

from dag_rider_tpu.analysis import (
    determinism,
    jitpure,
    knobs,
    metricsreg,
    oracle,
    races,
)
from dag_rider_tpu.analysis.core import (
    Allow,
    Finding,
    apply_allowlist,
    run_static,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def F(path, src):
    """One synthetic (relpath, tree, source) triple."""
    return (path, ast.parse(src), src)


def _msgs(findings):
    return [f.message for f in findings]


# -- knob discipline --------------------------------------------------------


def test_knobs_flags_direct_env_read_outside_config():
    got = knobs.run(
        [
            F(
                "dag_rider_tpu/evil.py",
                "import os\nx = os.environ.get('DAGRIDER_EVIL')\n",
            )
        ],
        REPO,
    )
    assert any("DAGRIDER_EVIL" in m for m in _msgs(got))


def test_knobs_flags_subscript_and_getenv_spellings():
    got = knobs.run(
        [
            F(
                "dag_rider_tpu/evil.py",
                "import os\n"
                "a = os.environ['DAGRIDER_A']\n"
                "b = os.getenv('DAGRIDER_B')\n",
            )
        ],
        REPO,
    )
    assert sum("DAGRIDER_A" in m for m in _msgs(got)) == 1
    assert sum("DAGRIDER_B" in m for m in _msgs(got)) == 1


def test_knobs_allows_direct_reads_in_config_only():
    """config.py is the one reader; no file or namespace is carved out
    beside it (a root script reading a name of its own is flagged)."""
    src = "import os\nx = os.environ.get('DAGRIDER_PUMP')\n"
    assert knobs.run([F("dag_rider_tpu/config.py", src)], REPO) == []
    for name in ("DAGRIDER_PUMP", "DAGRIDER_SMOKE_OWN"):
        got = knobs.run(
            [F("chip_smoke.py", src.replace("DAGRIDER_PUMP", name))], REPO
        )
        assert any(name in m for m in _msgs(got)), name


def _sources(*extra):
    """The files driderlint walks (the package, chip_smoke.py) plus
    the graft entry and ``extra``."""
    from dag_rider_tpu.analysis.core import discover

    files = discover(REPO)
    for rel in ("__graft_entry__.py", *extra):
        with open(os.path.join(REPO, rel), encoding="utf-8") as fh:
            files.append(F(rel, fh.read()))
    return files


def test_no_file_outside_config_reads_a_dagrider_variable():
    got = [
        f
        for f in knobs.run(_sources(), REPO)
        if "direct environment read" in f.message
    ]
    assert got == [], _msgs(got)


def test_registry_holds_exactly_the_knobs_that_are_read():
    """Every registered knob has a reader (an ``env_*`` accessor call
    naming it: in the package, a root script, ``scripts/`` or the
    suite's conftest), every name read is registered, and the README's
    table documents the registered names and no other."""
    import re

    from dag_rider_tpu import config

    scripts = sorted(
        os.path.join("scripts", f)
        for f in os.listdir(os.path.join(REPO, "scripts"))
        if f.endswith(".py")
    )
    read = set()
    for _rel, tree, _src in _sources(*scripts, "tests/conftest.py"):
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and node.args):
                continue
            f = node.func
            name = getattr(f, "attr", None) or getattr(f, "id", "")
            arg = node.args[0]
            if name.lstrip("_") in knobs._ACCESSORS and isinstance(
                arg, ast.Constant
            ):
                read.add(arg.value)
    assert read == set(config.KNOBS)
    assert len(config.KNOBS) == 40
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as fh:
        rows = [ln for ln in fh if ln.startswith("|")]
    documented = {
        name
        for ln in rows
        for name in re.findall(r"DAGRIDER_[A-Z0-9_]+", ln.split("|")[1])
    }
    assert documented == set(config.KNOBS)


def test_knobs_flags_unregistered_accessor_name():
    got = knobs.run(
        [
            F(
                "dag_rider_tpu/evil.py",
                "from dag_rider_tpu import config\n"
                "x = config.env_int('DAGRIDER_NOT_A_KNOB')\n",
            )
        ],
        REPO,
    )
    assert any("DAGRIDER_NOT_A_KNOB" in m for m in _msgs(got))


def test_knob_accessors_reject_unregistered_at_runtime():
    from dag_rider_tpu import config

    with pytest.raises(KeyError):
        config.env_flag("DAGRIDER_NOT_A_KNOB")


# -- determinism ------------------------------------------------------------


def test_determinism_flags_wall_clock_call():
    got = determinism.run(
        [F("dag_rider_tpu/evil.py", "import time\nt = time.time()\n")],
        REPO,
    )
    assert any("time.time()" in m for m in _msgs(got))


def test_determinism_allows_monotonic_and_clock_reference():
    got = determinism.run(
        [
            F(
                "dag_rider_tpu/ok.py",
                "import time\n"
                "t = time.monotonic()\n"
                "def f(clock=time.time):\n"
                "    return clock()\n",
            )
        ],
        REPO,
    )
    assert got == []


def test_determinism_flags_unseeded_random():
    got = determinism.run(
        [
            F(
                "dag_rider_tpu/evil.py",
                "import random\n"
                "a = random.random()\n"
                "r = random.Random()\n"
                "import numpy as np\n"
                "b = np.random.rand(3)\n",
            )
        ],
        REPO,
    )
    msgs = _msgs(got)
    assert any("random.random" in m for m in msgs)
    assert any("without a seed" in m for m in msgs)
    assert any("np.random.rand" in m for m in msgs)


def test_determinism_allows_seeded_rng():
    got = determinism.run(
        [
            F(
                "dag_rider_tpu/ok.py",
                "import random\nimport numpy as np\n"
                "r = random.Random(7)\n"
                "g = np.random.default_rng(7)\n",
            )
        ],
        REPO,
    )
    assert got == []


def test_determinism_flags_set_iteration_on_consensus_path():
    src = (
        "class P:\n"
        "    def __init__(self):\n"
        "        self._pending = set()\n"
        "    def step(self):\n"
        "        for v in self._pending:\n"
        "            pass\n"
        "        for w in {1, 2}:\n"
        "            pass\n"
    )
    got = determinism.run([F("dag_rider_tpu/consensus/evil.py", src)], REPO)
    assert sum("set" in m for m in _msgs(got)) == 2
    # identical code OUTSIDE consensus/ is not in scope for this rule
    assert determinism.run([F("dag_rider_tpu/utils/x.py", src)], REPO) == []


def test_determinism_allows_sorted_set_iteration():
    src = (
        "class P:\n"
        "    def __init__(self):\n"
        "        self._pending = set()\n"
        "    def step(self):\n"
        "        for v in sorted(self._pending):\n"
        "            pass\n"
    )
    assert (
        determinism.run([F("dag_rider_tpu/consensus/ok.py", src)], REPO)
        == []
    )


# -- oracle purity ----------------------------------------------------------


def test_oracle_flags_scalar_state_write_in_vector_branch():
    src = (
        "class P:\n"
        "    def step(self):\n"
        "        if self._vector:\n"
        "            self._buffer[1] = 2\n"
    )
    got = oracle.run([F("dag_rider_tpu/consensus/evil.py", src)], REPO)
    assert any("_buffer" in m for m in _msgs(got))


def test_oracle_flags_vector_state_write_in_scalar_branch():
    src = (
        "class P:\n"
        "    def step(self):\n"
        "        if self._vector:\n"
        "            pass\n"
        "        else:\n"
        "            self._inbox.append(1)\n"
        "    def other(self):\n"
        "        if not self._vector:\n"
        "            self._buffer_rounds = {}\n"
    )
    got = oracle.run([F("dag_rider_tpu/consensus/evil.py", src)], REPO)
    msgs = _msgs(got)
    assert any("_inbox" in m for m in msgs)
    assert any("_buffer_rounds" in m for m in msgs)


def test_oracle_flags_vector_only_method_and_cert_branch():
    src = (
        "class P:\n"
        "    def _drain_buffer_vector(self):\n"
        "        self._blocked_on.pop(3)\n"
        "    def go(self):\n"
        "        if self._cert:\n"
        "            self._buffered_ids.add(7)\n"
    )
    got = oracle.run([F("dag_rider_tpu/consensus/evil.py", src)], REPO)
    msgs = _msgs(got)
    assert any("_blocked_on" in m for m in msgs)
    assert any("_buffered_ids" in m for m in msgs)


def test_oracle_allows_legal_mutations():
    # cert path pushing into per-vertex re-verify is the degradation
    # seam, and vector code touching its OWN state is fine
    src = (
        "class P:\n"
        "    def _apply_certificate(self, c):\n"
        "        self._pending_verify.append(c)\n"
        "    def _process_inbox(self):\n"
        "        self._inbox.clear()\n"
    )
    assert oracle.run([F("dag_rider_tpu/consensus/ok.py", src)], REPO) == []


# -- jit purity -------------------------------------------------------------


def test_jitpure_flags_side_effects_in_jitted_fns():
    src = (
        "import functools, os, jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    print(x)\n"
        "    return x\n"
        "@functools.partial(jax.jit, static_argnames=('n',))\n"
        "def g(x, n):\n"
        "    y = os.environ.get('HOME')\n"
        "    return x\n"
        "def h(x):\n"
        "    import time\n"
        "    time.time()\n"
        "    return x\n"
        "h = jax.jit(h)\n"
    )
    got = jitpure.run([F("dag_rider_tpu/ops/evil.py", src)], REPO)
    msgs = _msgs(got)
    assert any("print" in m and "f()" in m for m in msgs)
    assert any("os.environ.get" in m and "g()" in m for m in msgs)
    assert any("time.time" in m and "h()" in m for m in msgs)


def test_jitpure_ignores_unjitted_fns_and_other_dirs():
    src = "def f(x):\n    print(x)\n    return x\n"
    assert jitpure.run([F("dag_rider_tpu/ops/ok.py", src)], REPO) == []
    jitted = "import jax\n@jax.jit\ndef f(x):\n    print(x)\n    return x\n"
    # consensus/ is host code — out of jitpure's scope by design
    assert (
        jitpure.run([F("dag_rider_tpu/consensus/x.py", jitted)], REPO) == []
    )


# -- metrics discipline -----------------------------------------------------


def test_metrics_flags_unregistered_counter():
    src = (
        "def f(m):\n"
        "    m.inc('totally_new_counter')\n"
        "    m.counters['another_rogue'] += 1\n"
        "    m.inc('msgs_received')\n"
    )
    got = metricsreg.run([F("dag_rider_tpu/evil.py", src)], REPO)
    msgs = _msgs(got)
    assert any("totally_new_counter" in m for m in msgs)
    assert any("another_rogue" in m for m in msgs)
    assert not any("msgs_received" in m for m in msgs)


# -- allowlist semantics ----------------------------------------------------


def test_allowlist_suppresses_and_reports_stale_entries():
    f1 = Finding("determinism", "a.py", 3, "wall-clock time.time() call")
    allows = [
        Allow("determinism", "a.py", "time.time()", "justified"),
        Allow("determinism", "b.py", "never matches", "stale"),
    ]
    kept, suppressed, unused = apply_allowlist([f1], allows)
    assert kept == [] and suppressed == [f1]
    assert len(unused) == 1 and unused[0].path == "b.py"


# -- race harness -----------------------------------------------------------


@pytest.fixture
def harness():
    installed_here = not races.active()
    if installed_here:
        races.install(auto_guard=False)
    yield races
    races.drain_violations()  # consume what this test planted
    if installed_here:
        races.uninstall()


def test_lock_order_cycle_detected(harness):
    g = races.LockGraph()
    a = races.TrackedLock(g, "siteA")
    b = races.TrackedLock(g, "siteB")
    with a:
        with b:
            pass
    with pytest.raises(races.RaceViolation, match="cycle"):
        with b:
            with a:
                pass


def test_three_lock_cycle_detected(harness):
    g = races.LockGraph()
    a = races.TrackedLock(g, "sA")
    b = races.TrackedLock(g, "sB")
    c = races.TrackedLock(g, "sC")
    with a:
        with b:
            pass
    with b:
        with c:
            pass
    with pytest.raises(races.RaceViolation, match="cycle"):
        with c:
            with a:
                pass


def test_nonreentrant_reacquire_raises_reentrant_allowed(harness):
    g = races.LockGraph()
    lk = races.TrackedLock(g, "siteL")
    with pytest.raises(races.RaceViolation, match="re-acquire"):
        with lk:
            lk.acquire()
    rl = races.TrackedRLock(g, "siteR")
    with rl:
        with rl:
            assert rl.held_by_current()
    assert not rl.held_by_current()


def test_unguarded_shared_field_write_raises(harness):
    from dag_rider_tpu.transport.memory import InMemoryTransport

    t = InMemoryTransport()
    races.guard(t)
    t.subscribe(0, lambda m: None)  # mutates under the lock: legal
    with pytest.raises(races.RaceViolation, match="unguarded write"):
        t._handlers[9] = lambda m: None
    with pytest.raises(races.RaceViolation, match="unguarded write"):
        t._queue.append((0, None))
    with pytest.raises(races.RaceViolation, match="unguarded write"):
        t._fanout = []
    # and with the lock held, all of those are legal
    with t._lock:
        t._handlers[9] = lambda m: None
        t._queue.append((0, None))
        t._fanout = [0, 9]


def test_guarded_transport_still_works_end_to_end(harness):
    from dag_rider_tpu.core.types import BroadcastMessage
    from dag_rider_tpu.transport.memory import InMemoryTransport

    t = InMemoryTransport()
    races.guard(t)
    got = []
    t.subscribe(0, got.append)
    t.subscribe(1, got.append)
    t.broadcast(BroadcastMessage(vertex=None, round=0, sender=0))
    t.pump()
    assert len(got) >= 1
    assert races.VIOLATIONS == []


def test_prep_gauges_are_lock_guarded(harness):
    from dag_rider_tpu.verifier.prep import PrepEngine

    eng = PrepEngine(workers=1)
    races.guard(eng)
    with pytest.raises(races.RaceViolation, match="unguarded write"):
        eng.dispatches += 1
    # the engine's own path takes the gauge lock
    eng.run_blocks(lambda lo, hi: None, eng.plan(64))
    assert eng.dispatches == 1
    eng.close()


def test_serialized_method_overlap_raises(harness):
    class SingleOwner:
        def work(self, dwell):
            time.sleep(dwell)
            return "ok"

    obj = SingleOwner()
    races.guard_serial(obj, ("work",))
    assert obj.work(0.0) == "ok"  # plain reuse by one thread

    errs = []
    started = threading.Event()

    def first():
        started.set()
        obj.work(0.3)

    def second():
        started.wait()
        time.sleep(0.05)
        try:
            obj.work(0.0)
        except races.RaceViolation as e:
            errs.append(e)

    t1 = threading.Thread(target=first)
    t2 = threading.Thread(target=second)
    t1.start()
    t2.start()
    t1.join()
    t2.join()
    assert len(errs) == 1 and "overlap" in str(errs[0])


def test_violations_recorded_for_session_hook(harness):
    g = races.LockGraph()
    lk = races.TrackedLock(g, "siteV")
    with pytest.raises(races.RaceViolation):
        with lk:
            lk.acquire()
    assert any("re-acquire" in v for v in races.drain_violations())
    assert races.drain_violations() == []


# -- the tree itself is clean ----------------------------------------------


def test_driderlint_clean_on_this_repo():
    kept, suppressed, unused = run_static(REPO)
    assert kept == [], "\n".join(str(f) for f in kept)
    assert unused == [], f"stale allowlist entries: {unused}"
    # every suppressed finding is explained
    from dag_rider_tpu.analysis.allowlist import ALLOWS

    assert all(a.reason.strip() for a in ALLOWS)


def test_runner_main_exits_zero_on_this_repo(capsys):
    from dag_rider_tpu.analysis.__main__ import main

    assert main(["--root", REPO]) == 0
    out = capsys.readouterr().out
    assert "clean" in out
