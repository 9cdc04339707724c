"""driderlint v2 non-vacuity + cross-validation suite (round 17).

Same contract as tests/test_analysis.py: every interprocedural checker
is proven by a PLANTED violation fed through the production
``run(files, root)`` entry, the clean-tree gate proves today's repo
passes with zero unexplained allows, and the static/dynamic lock-site
cross-validation ties the two lock views together — every site the
dynamic race harness registers must be known to the static graph (the
reverse gap is coverage intel, printed, not a failure).

The release-checker fixtures reproduce a defect class a measurement
script once had (the file went in PR 30): fixed_bucket set, restore at
the bottom, nothing covering the middle — kept here as the permanent
regression fixture.
"""

import ast
import os

import pytest

from dag_rider_tpu.analysis import flow, ladder, locks, races, release, shapes
from dag_rider_tpu.analysis.core import discover, run_static

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def F(path, src):
    """One synthetic (relpath, tree, source) triple."""
    return (path, ast.parse(src), src)


def _msgs(findings):
    return [f.message for f in findings]


@pytest.fixture(scope="module")
def tree_files():
    return discover(REPO)


@pytest.fixture(scope="module")
def tree_graph(tree_files):
    return flow.build(tree_files)


# -- flow: the interprocedural core ----------------------------------------


def test_flow_resolves_method_and_module_calls():
    files = [
        F(
            "dag_rider_tpu/alpha.py",
            "def helper():\n    return 1\n"
            "class A:\n"
            "    def top(self):\n        return self.mid()\n"
            "    def mid(self):\n        return helper()\n",
        ),
        F(
            "dag_rider_tpu/beta.py",
            "from dag_rider_tpu import alpha\n"
            "def entry():\n    a = alpha.A()\n    return a.top()\n",
        ),
    ]
    g = flow.build(files)
    reach = g.reachable("dag_rider_tpu.beta.entry")
    assert "dag_rider_tpu.alpha.A.top" in reach
    assert "dag_rider_tpu.alpha.A.mid" in reach
    assert "dag_rider_tpu.alpha.helper" in reach


def test_flow_function_local_imports_resolve():
    files = [
        F("dag_rider_tpu/gamma.py", "def target():\n    return 7\n"),
        F(
            "dag_rider_tpu/delta.py",
            "def entry():\n"
            "    from dag_rider_tpu.gamma import target\n"
            "    return target()\n",
        ),
    ]
    g = flow.build(files)
    assert "dag_rider_tpu.gamma.target" in g.reachable(
        "dag_rider_tpu.delta.entry"
    )


def test_flow_covers_real_degradation_seams(tree_graph):
    p = "dag_rider_tpu.consensus.process.Process."
    assert p + "_drain_buffer_vector" in tree_graph.reachable(
        p + "_drain_buffer"
    )
    assert p + "_degrade_cert_round" in tree_graph.reachable(
        p + "_apply_certificate"
    )


# -- locks: static lock-order proofs ---------------------------------------

_CYCLE_SRC = """
import threading

_A = threading.Lock()
_B = threading.Lock()

def f():
    with _A:
        g()

def g():
    with _B:
        pass

def h():
    with _B:
        f()
"""


def test_locks_planted_cycle_detected():
    got = locks.run([F("dag_rider_tpu/evil_locks.py", _CYCLE_SRC)], REPO)
    assert any("lock-order cycle" in m for m in _msgs(got)), _msgs(got)


def test_locks_one_direction_is_clean():
    src = _CYCLE_SRC.replace("def h():\n    with _B:\n        f()", "")
    got = locks.run([F("dag_rider_tpu/ok_locks.py", src)], REPO)
    assert got == []


def test_locks_self_deadlock_detected():
    src = (
        "import threading\n"
        "_L = threading.Lock()\n"
        "def f():\n"
        "    with _L:\n"
        "        with _L:\n"
        "            pass\n"
    )
    got = locks.run([F("dag_rider_tpu/evil_self.py", src)], REPO)
    assert any("self-deadlock" in m for m in _msgs(got))
    # the same shape on an RLock is legal
    rsrc = src.replace("threading.Lock", "threading.RLock")
    assert locks.run([F("dag_rider_tpu/ok_rlock.py", rsrc)], REPO) == []


def test_locks_interprocedural_edge_through_helper():
    src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "    def outer(self):\n"
        "        with self._a:\n"
        "            self.helper()\n"
        "    def helper(self):\n"
        "        with self._b:\n"
        "            self.outer2()\n"
        "    def outer2(self):\n"
        "        with self._b:\n"
        "            with self._a:\n"
        "                pass\n"
    )
    got = locks.run([F("dag_rider_tpu/evil_helper.py", src)], REPO)
    assert any("lock-order cycle" in m for m in _msgs(got)), _msgs(got)


def test_static_lock_graph_covers_tree_sites(tree_files):
    sites = locks.lock_sites(tree_files)
    # the dynamic harness's own registry modules are excluded; every
    # other package Lock()/RLock() creation must be indexed
    assert len(sites) >= 10
    assert all(":" in s for s in sites)


# -- release: exception-safe borrow/restore --------------------------------

#: the shape that once leaked a sim-sized bucket into the next phase,
#: kept verbatim as the checker's permanent regression fixture
_SIM256_LEAK_SRC = """
def sim256_rung(verifier, batches, bucket):
    prev = verifier.fixed_bucket
    verifier.fixed_bucket = bucket
    verifier.warmup()
    masks = verifier.verify_batch(batches)
    verifier.fixed_bucket = prev
    return masks
"""


def test_release_flags_unwrapped_sim256_shape():
    got = release.run(
        [F("dag_rider_tpu/evil_rel.py", _SIM256_LEAK_SRC)], REPO
    )
    assert any(
        "fixed_bucket" in m and "leak" in m for m in _msgs(got)
    ) or any("finally-restore" in m for m in _msgs(got)), _msgs(got)


def test_release_fixed_shape_is_clean():
    src = (
        "def sim256_rung(verifier, batches, bucket):\n"
        "    prev = verifier.fixed_bucket\n"
        "    try:\n"
        "        verifier.fixed_bucket = bucket\n"
        "        verifier.warmup()\n"
        "        masks = verifier.verify_batch(batches)\n"
        "    finally:\n"
        "        verifier.fixed_bucket = prev\n"
        "    return masks\n"
    )
    assert release.run([F("dag_rider_tpu/ok_rel.py", src)], REPO) == []


def test_release_registry_attr_on_shared_receiver():
    src = (
        "def rung(verifier):\n"
        "    verifier.prep_workers = 4\n"
        "    verifier.run()\n"
    )
    got = release.run([F("dag_rider_tpu/evil_rel2.py", src)], REPO)
    assert any("prep_workers" in m for m in _msgs(got))


def test_release_exempts_init_and_local_constructor():
    src = (
        "class V:\n"
        "    def __init__(self, backend):\n"
        "        backend.prep_workers = 1\n"
        "        self.fixed_bucket = 256\n"
        "def make():\n"
        "    v = V(None)\n"
        "    v.fixed_bucket = 128\n"
        "    return v\n"
    )
    assert release.run([F("dag_rider_tpu/ok_rel2.py", src)], REPO) == []


def test_release_arm_without_finally():
    src = (
        "def chaos(inj, verifier):\n"
        "    inj.arm(verifier)\n"
        "    verifier.run()\n"
        "    inj.disarm()\n"
    )
    got = release.run([F("dag_rider_tpu/evil_rel3.py", src)], REPO)
    assert any("arm" in m and "finally" in m for m in _msgs(got))


def test_release_arm_with_finally_is_clean():
    src = (
        "def chaos(inj, verifier):\n"
        "    try:\n"
        "        inj.arm(verifier)\n"
        "        verifier.run()\n"
        "    finally:\n"
        "        inj.disarm()\n"
    )
    assert release.run([F("dag_rider_tpu/ok_rel3.py", src)], REPO) == []


# -- shapes: jit recompile hazards -----------------------------------------

_SHAPES_EVIL_SRC = """
import functools
import jax
import jax.numpy as jnp

@jax.jit
def bad(x, y):
    if x > 0:
        y = y + 1
    n = x.shape[0]
    while n > 2:
        n //= 2
    v = float(x)
    z = x.item()
    for e in x:
        y = y + e
    return y

@functools.partial(jax.jit, static_argnames=("impl",))
def kern(a, impl="jnp"):
    return a

def caller(a):
    return kern(a, impl=["not", "hashable"])
"""


def test_shapes_flags_each_hazard_class():
    got = _msgs(
        shapes.run([F("dag_rider_tpu/ops/evil_shapes.py", _SHAPES_EVIL_SRC)], REPO)
    )
    assert any("Python if on a traced value" in m for m in got)
    assert any("while on a shape-derived bound" in m for m in got)
    assert any("float() on a traced value" in m for m in got)
    assert any(".item() on a traced value" in m for m in got)
    assert any("for over a traced value" in m for m in got)
    assert any("unhashable static arg" in m for m in got)


def test_shapes_clean_idioms_not_flagged():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from jax import lax\n"
        "@jax.jit\n"
        "def good(x, mask=None):\n"
        "    if mask is not None:\n"  # trace-time identity: fine
        "        x = jnp.where(mask, x, 0)\n"
        "    n = x.shape[0]\n"
        "    if n > 4:\n"  # shape-derived if: the bucketing idiom
        "        x = x[:4]\n"
        "    return lax.fori_loop(0, 4, lambda i, a: a + x[i], 0.0)\n"
    )
    assert shapes.run([F("dag_rider_tpu/ops/ok_shapes.py", src)], REPO) == []


def test_shapes_ignores_files_outside_ops_parallel():
    got = shapes.run(
        [F("dag_rider_tpu/consensus/evil_shapes.py", _SHAPES_EVIL_SRC)],
        REPO,
    )
    assert got == []


# -- ladder: degradation totality ------------------------------------------

_LADDER_SRC = """
def entry(x):
    if x:
        return fast(x)
    return oracle(x)

def fast(x):
    return x

def oracle(x):
    return x

def unrelated():
    return 0
"""


def _ladder_files():
    return [F("dag_rider_tpu/lad.py", _LADDER_SRC)]


def test_ladder_intact_rung_is_clean():
    lad = ladder.Ladder(
        "DAGRIDER_PUMP",  # any registered knob
        "dag_rider_tpu.lad.entry",
        "dag_rider_tpu.lad.fast",
        "dag_rider_tpu.lad.oracle",
    )
    assert ladder.run(_ladder_files(), REPO, ladders=[lad]) == []


def test_ladder_flags_unregistered_knob():
    lad = ladder.Ladder(
        "DAGRIDER_NO_SUCH_KNOB",
        "dag_rider_tpu.lad.entry",
        "dag_rider_tpu.lad.fast",
        "dag_rider_tpu.lad.oracle",
    )
    got = _msgs(ladder.run(_ladder_files(), REPO, ladders=[lad]))
    assert any("not registered" in m for m in got)


def test_ladder_flags_missing_and_severed():
    missing = ladder.Ladder(
        "DAGRIDER_PUMP",
        "dag_rider_tpu.lad.entry",
        "dag_rider_tpu.lad.gone",
        "dag_rider_tpu.lad.oracle",
    )
    got = _msgs(ladder.run(_ladder_files(), REPO, ladders=[missing]))
    assert any("missing function" in m for m in got)
    severed = ladder.Ladder(
        "DAGRIDER_PUMP",
        "dag_rider_tpu.lad.entry",
        "dag_rider_tpu.lad.fast",
        "dag_rider_tpu.lad.unrelated",  # exists, not reachable
    )
    got = _msgs(ladder.run(_ladder_files(), REPO, ladders=[severed]))
    assert any("degradation edge severed" in m for m in got)


def test_ladder_shipped_table_holds_on_tree(tree_files, tree_graph):
    assert ladder.run(tree_files, REPO, graph=tree_graph) == []


# -- static/dynamic lock-site cross-validation -----------------------------


def test_dynamic_lock_sites_subset_of_static(tree_files):
    """Every site the dynamic harness hands a tracked lock for must be
    known to the static lock graph; statically-known sites the dynamic
    suites never exercised are printed as coverage intel."""
    installed_here = not races.active()
    if installed_here:
        races.install(auto_guard=False)
    try:
        # exercise a couple of lock-creating constructors so the test
        # is meaningful even outside the DAGRIDER_RACE=1 CI lane (under
        # that lane, SITES also carries every suite that ran before us)
        from dag_rider_tpu.obs.flight import FlightRecorder
        from dag_rider_tpu.transport.memory import InMemoryTransport

        FlightRecorder(out_dir=None)
        InMemoryTransport()
        dynamic = set(races.SITES)
    finally:
        races.drain_violations()
        if installed_here:
            races.uninstall()

    static = set(locks.lock_sites(tree_files))
    assert dynamic, "harness registered no lock sites at all"
    missing = dynamic - static
    assert not missing, (
        "dynamically-registered lock sites invisible to the static "
        f"graph (static extraction has a hole): {sorted(missing)}"
    )
    unexercised = static - dynamic
    print(
        f"\nlock-site coverage: {len(dynamic)} exercised dynamically, "
        f"{len(unexercised)} statically known but not exercised here: "
        f"{sorted(unexercised)}"
    )


# -- clean tree + runner ----------------------------------------------------


def test_driderlint_v2_clean_on_this_repo():
    kept, _suppressed, unused = run_static(REPO)
    assert kept == [], [str(f) for f in kept]
    assert unused == []


def test_runner_budget_flag(capsys):
    from dag_rider_tpu.analysis.__main__ import main

    assert main(["--budget-s", "120"]) == 0
    # an absurdly tight budget must fail even on a clean tree
    assert main(["--budget-s", "0.000001"]) == 1
    out = capsys.readouterr().out
    assert "BUDGET" in out
