"""The heap that building a served stack leaves is collected once and
frozen where its one program was compiled (``TPUVerifier.warmup``), so
that no later full collection walks it — and the benchmark's metric that
says the mechanism ran (``heap_frozen_objects.verify`` / ``.commit``).

On the CPU backend, with a stub in the lowering's place: a warm-up that
"compiles" is then a few milliseconds.
"""

import gc
import importlib.util
import os
import sys
import weakref

import pytest

from dag_rider_tpu.obs import spans
from dag_rider_tpu.verifier import CPUVerifier
from dag_rider_tpu.verifier.base import KeyRegistry
from dag_rider_tpu.verifier.sidecar import VerifierSidecarServer
from dag_rider_tpu.verifier.tpu import TPUVerifier

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import cells  # noqa: E402

COUNTER = "heap.frozen_objects"


def booked() -> int:
    return spans.snapshot()["counts"].get(COUNTER, 0)


def frozen_so_far() -> int:
    """The frozen count, after a full collection: one parks the
    interpreter's immortal objects (375 here) among the frozen, and the
    site's own collection would otherwise show in a difference."""
    gc.collect()
    return gc.get_freeze_count()


def agree(booked_count: int, frozen_now: int) -> bool:
    """The site books what it froze; a frozen object that has died since
    (reference counts free it as ever) has left the interpreter's count."""
    return 0 <= booked_count - frozen_now < 100


def tracked(obj) -> bool:
    """Whether a collection would visit ``obj``: ``gc.get_objects()``
    lists the three generations and leaves the frozen ones out."""
    return any(o is obj for o in gc.get_objects())


@pytest.fixture(scope="module")
def registry():
    return KeyRegistry.generate(4)[0]


@pytest.fixture
def stub_program(monkeypatch):
    """The comb program's lowering replaced by a stub, as
    test_device_rules.py does: ``warmup`` takes the branch that compiled
    without the seconds a real trace costs."""
    monkeypatch.setattr(TPUVerifier, "_comb_tables_dev", lambda self: (None, None))
    monkeypatch.setattr(
        TPUVerifier, "_aot_lower", lambda self, size, impl, tables, b_tab: object()
    )


# -- the mechanism -----------------------------------------------------------


def test_a_warmup_that_compiled_freezes_the_heap_and_books_the_count(
    registry, stub_program
):
    v = TPUVerifier(registry)
    # construction alone freezes nothing
    held, before = frozen_so_far(), booked()
    v.warmup()
    frozen = gc.get_freeze_count() - held
    # the interpreter with jax and the package imported, at the least
    assert frozen > 50_000
    assert agree(booked() - before, frozen)


def test_the_suite_hands_back_what_a_test_froze():
    # conftest.py's autouse fixture, after the test above
    assert gc.get_freeze_count() == 0


def first_use(v):
    v._program(16, v._select_impl(16))


@pytest.mark.parametrize("compiled_by", (TPUVerifier.warmup, first_use))
def test_a_warmup_that_finds_its_program_there_freezes_nothing(
    registry, stub_program, compiled_by
):
    v = TPUVerifier(registry)
    compiled_by(v)
    frozen, before = gc.get_freeze_count(), booked()
    made_since = [[] for _ in range(100)]
    assert v.warmup() == 0.0
    assert gc.get_freeze_count() <= frozen and booked() == before
    assert tracked(made_since)


def test_a_second_program_freezes_what_was_made_since_and_books_only_that(
    registry, stub_program
):
    v = TPUVerifier(registry)
    v.warmup()
    first, before = gc.get_freeze_count(), booked()
    made_since = [[] for _ in range(1_000)]
    v.warmup(bucket=64)
    assert gc.get_freeze_count() - first >= 1_001
    assert agree(booked() - before, gc.get_freeze_count() - first)
    assert not tracked(made_since)


@pytest.mark.parametrize("backend, freezes", ((CPUVerifier, False), (TPUVerifier, True)))
def test_a_sidecar_freezes_only_over_a_backend_that_compiles(
    registry, stub_program, backend, freezes
):
    held, before = frozen_so_far(), booked()
    server = VerifierSidecarServer(backend(registry), "127.0.0.1:0")
    try:
        assert (gc.get_freeze_count() > held) == freezes
        assert (booked() > before) == freezes
    finally:
        server.stop()


def test_a_full_collection_after_the_freeze_leaves_the_frozen_alone(
    registry, stub_program
):
    class Node:
        pass

    held = [[] for _ in range(100)]
    plain = Node()
    ring = Node()
    ring.me = ring  # a cycle, alive at the freeze
    plain_alive, ring_alive = weakref.ref(plain), weakref.ref(ring)
    assert tracked(held) and tracked(ring)
    TPUVerifier(registry).warmup()
    assert not tracked(held) and not tracked(ring)
    assert not any(tracked(x) for x in held)

    # reference counts free a frozen object as ever
    del plain
    assert plain_alive() is None
    # the price, and why the collection comes before the freeze and the
    # site runs once: a cycle frozen alive is not reclaimed when it dies
    del ring
    gc.collect()
    assert ring_alive() is not None
    gc.unfreeze()
    gc.collect()
    assert ring_alive() is None


def test_garbage_made_after_the_freeze_is_collected_as_before(registry, stub_program):
    class Node:
        pass

    TPUVerifier(registry).warmup()
    ring = Node()
    ring.me = ring
    alive = weakref.ref(ring)
    del ring
    gc.collect()
    assert alive() is None
    assert gc.isenabled() and gc.get_threshold() == (700, 10, 10)


def test_a_simulation_freezes_its_views_with_the_program(stub_program):
    """``Simulation._pipeline_for`` builds the window, and with it the
    program, on the first ``run()`` — after the views exist: their
    construction-time state is frozen with the rest, and the lookup a
    later ``run()`` makes freezes nothing more."""
    from dag_rider_tpu.config import Config
    from dag_rider_tpu.consensus.simulator import Simulation

    sim = Simulation(Config(n=4, propose_empty=True), verifier="device")
    view = sim.processes[0]
    held, before = frozen_so_far(), booked()
    assert tracked(view)
    pipe = sim._pipeline_for(view.verifier)
    frozen = gc.get_freeze_count() - held
    assert frozen > 50_000 and agree(booked() - before, frozen)
    assert not tracked(view) and not tracked(view.dag)
    booked_once = booked()
    assert sim._pipeline_for(view.verifier) is pipe
    pipe._warm()  # what every window does before it opens: a lookup
    assert booked() == booked_once and gc.get_freeze_count() - held <= frozen


# -- the metric --------------------------------------------------------------

MANIFEST = cells.load_manifest(ROOT)
METRICS = [m for m in MANIFEST["per_layer"] if m["name"].startswith("heap_frozen_objects.")]
NAMES = sorted(m["name"] for m in METRICS)
TRACED = {"programs": {}, "busy_s": 0.1, "window_s": 4.0}


def obs_with(trace) -> dict:
    return {"samples": {}, "counters": {}, "seconds": 40.0, "trace": trace,
            "device_kind": "TPU v5 lite", "config": {"n": 4}}


@pytest.fixture(scope="module")
def readers():
    return cells.load_readers(ROOT, METRICS)


def test_the_manifest_has_the_count_once_a_cell_under_the_layers_name():
    assert NAMES == ["heap_frozen_objects.commit", "heap_frozen_objects.verify"]
    by_name = {m["name"]: m for m in METRICS}
    for name, moves, cell in (
        ("heap_frozen_objects.commit", "commit_p95_ms", "committee256.poisson1k"),
        ("heap_frozen_objects.verify", "verified_sigs_per_s", "sidecar256.colocated4"),
    ):
        m = by_name[name]
        assert (m["moves"], m["workloads"]) == (moves, [cell])
        assert (m["unit"], m["better"], m["source"]) == ("count", "higher", "program_counter")
        # the layer's name letter for letter, as the collector's share has it
        assert m["layer"] == "host runtime"
        assert cells.reader_path(ROOT, name).endswith("heap_frozen_objects.py")
    # they follow the 27 entries the manifest had before them, in this
    # order: nothing it had was moved (later PRs add after them)
    assert [m["name"] for m in MANIFEST["per_layer"][27:29]] == [
        "heap_frozen_objects.verify", "heap_frozen_objects.commit",
    ]


@pytest.mark.parametrize("name", NAMES)
def test_reader_gives_the_counter_of_a_hand_filled_book(name, readers, monkeypatch):
    book = {"spans": {}, "counts": {COUNTER: 1_312_345, "pump.round_advance": 40}}
    monkeypatch.setattr(spans, "snapshot", lambda: book)
    assert readers[name](obs_with(TRACED)) == 1_312_345


@pytest.mark.parametrize("name", NAMES)
def test_reader_returns_nothing_in_a_run_that_takes_no_trace(name, readers, monkeypatch):
    monkeypatch.setattr(spans, "snapshot", lambda: {"spans": {}, "counts": {COUNTER: 7}})
    assert readers[name](obs_with(None)) is None
    no_trace_key = obs_with(None)
    del no_trace_key["trace"]
    assert readers[name](no_trace_key) is None


@pytest.mark.parametrize("name", NAMES)
def test_reader_returns_nothing_from_a_program_without_the_span_module(
    name, readers, monkeypatch
):
    import dag_rider_tpu.obs as obs_pkg

    monkeypatch.delattr(obs_pkg, "spans")
    monkeypatch.setitem(sys.modules, "dag_rider_tpu.obs.spans", None)
    assert readers[name](obs_with(TRACED)) is None


@pytest.mark.parametrize("name", NAMES)
def test_reader_returns_nothing_from_a_program_that_never_froze(name, readers, monkeypatch):
    # the parent commit under this PR's benchmark files: a book, no such counter
    monkeypatch.setattr(
        spans, "snapshot", lambda: {"spans": {}, "counts": {"pump.round_advance": 40}}
    )
    assert readers[name](obs_with(TRACED)) is None


_spec = importlib.util.spec_from_file_location(
    "benchmarks_run", os.path.join(ROOT, "benchmarks", "run.py")
)
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


@pytest.mark.parametrize(
    "cell, name",
    (
        ("committee256.poisson1k", "heap_frozen_objects.commit"),
        ("sidecar256.colocated4", "heap_frozen_objects.verify"),
    ),
)
def test_a_traced_line_carries_what_a_device_verifier_froze(
    cell, name, registry, stub_program
):
    """``run.py``'s own reading of a cell's per-layer group: the value
    grows by exactly what the warm-up froze (the book is the process's,
    so an earlier test's share is in it), under the cell's own name."""

    def line():
        observed = {"samples": {}, "counters": {}, "seconds": 1.5}
        return bench.read_metrics(
            cells.load_cell(ROOT, cell), "per_layer", observed,
            trace=TRACED, device_kind="TPU v5 lite",
        )

    earlier = line().get(name, {"value": 0})["value"]
    held = frozen_so_far()
    TPUVerifier(registry).warmup()
    frozen = gc.get_freeze_count() - held
    got = line()
    assert frozen > 50_000 and got[name]["unit"] == "count"
    assert agree(got[name]["value"] - earlier, frozen)
    assert not any(k.startswith("heap_frozen_objects.") for k in got if k != name)
