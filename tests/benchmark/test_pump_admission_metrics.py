"""``pump_batched_admission_pct`` and ``verify_dispatches_per_round``: the
stated arithmetic on a hand-filled book, nothing where the program
counts nothing or the run left no book, and after a committee window at
n=4: every admission the round-batched drain's.
"""

import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import cells  # noqa: E402


def _sibling(stem):
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{stem}", os.path.join(os.path.dirname(__file__), f"{stem}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


base = _sibling("test_cells")
rule = _sibling("manifest_rule")

ADMISSION = "pump_batched_admission_pct"
DISPATCHES = "verify_dispatches_per_round"
COMMITTEE = "committee256.poisson1k"
ENTRIES = {name: rule.entry(name) for name in (ADMISSION, DISPATCHES)}
READERS = cells.load_readers(ROOT, list(ENTRIES.values()))
TRACED = {"programs": {}, "busy_s": 0.1, "window_s": 4.0}
DISPATCH_STAT = {"total_ns": 1, "max_ns": 1, "child_ns": 0}


def obs_with(trace) -> dict:
    return {"samples": {}, "counters": {}, "seconds": 51.0, "trace": trace,
            "device_kind": "TPU v5 lite", "config": {"n": 4}}


@pytest.fixture
def book(monkeypatch):
    """This process's book, hand-filled: a committee of 4 that advanced
    10 rounds, unless ``rounds`` says otherwise."""
    from dag_rider_tpu.obs import spans

    def fill(counts=None, dispatches=None, rounds=10):
        stats = {}
        if dispatches is not None:
            stats["verify_batch.dispatch"] = {"count": dispatches, **DISPATCH_STAT}
        filled = {"spans": stats, "counts": {"pump.round_advance": 4 * rounds, **(counts or {})}}
        monkeypatch.setattr(spans, "snapshot", lambda: filled)

    fill()
    return fill


def test_the_manifest_lists_both_for_the_committee_cell_alone():
    """Each entry's own fields exactly, and the committee cell among its
    cells; where the entries stand, and which cells a later PR appends to
    them, is not this test's (``manifest_rule.py``)."""
    rule.assert_fields(
        ADMISSION, cells_=[COMMITTEE], unit="%", better="higher", source="program_counter",
        layer="host pump", moves="commit_p95_ms",
    )
    rule.assert_fields(
        DISPATCHES, cells_=[COMMITTEE], unit="count", better="lower", source="program_counter",
        layer="verify seam", moves="commit_p95_ms",
    )
    rule.check_cell(COMMITTEE, per_layer=ENTRIES)
    for name in ENTRIES:
        assert cells.reader_path(ROOT, name).endswith(name + ".py")


@pytest.mark.parametrize(
    "counts, want",
    [
        ({"pump.admit_batched": 400}, 100.0),
        ({"pump.admit_scalar": 400}, 0.0),
        ({"pump.admit_batched": 300, "pump.admit_scalar": 100}, 75.0),
    ],
)
def test_admission_share_out_of_a_hand_filled_book(counts, want, book):
    book(counts)
    assert READERS[ADMISSION](obs_with(TRACED)) == pytest.approx(want)


@pytest.mark.parametrize("dispatches, want", [(20, 2.0), (2570, 257.0)])
def test_dispatches_a_round_out_of_a_hand_filled_book(dispatches, want, book):
    book(dispatches=dispatches)
    assert READERS[DISPATCHES](obs_with(TRACED)) == pytest.approx(want)


@pytest.mark.parametrize("name", [ADMISSION, DISPATCHES])
def test_reader_returns_nothing_where_there_is_nothing_to_read(name, book, monkeypatch):
    # the parent's book: its rounds, neither counter; a host verifier's:
    # no dispatch span
    assert READERS[name](obs_with(TRACED)) is None
    book({"pump.admit_batched": 0, "pump.admit_scalar": 0}, dispatches=20, rounds=0)
    assert READERS[name](obs_with(TRACED)) is None  # nothing admitted; no round to divide by
    # an end-to-end run: the process's book is not the run's
    book({"pump.admit_batched": 400}, dispatches=20)
    assert READERS[name](obs_with(None)) is None
    # a program from before the span primitive
    import dag_rider_tpu.obs as obs_pkg

    monkeypatch.delattr(obs_pkg, "spans")
    monkeypatch.setitem(sys.modules, "dag_rider_tpu.obs.spans", None)
    assert READERS[name](obs_with(TRACED)) is None


def test_both_counters_are_registered_and_the_reader_names_them():
    from dag_rider_tpu.obs import spans

    text = open(cells.reader_path(ROOT, ADMISSION)).read()
    for counter in ("pump.admit_batched", "pump.admit_scalar"):
        assert counter in spans.KNOWN_COUNTS and f'"{counter}"' in text
    assert "verify_batch.dispatch" in spans.KNOWN_SPANS
    assert '"verify_batch.dispatch"' in open(cells.reader_path(ROOT, DISPATCHES)).read()


def test_a_committee_window_at_n4_admits_every_vertex_in_batches(monkeypatch):
    """The window's own share of the book (the book is the process's,
    and another test's scalar simulation may have written to it)."""
    from dag_rider_tpu.obs import spans

    before = spans.snapshot()
    cell = base.small_cell(COMMITTEE)
    line = base.bench.drive(
        cell, base.SEED, 1.5, 0, base.cpu_devices(), build=base.inloop_over("cpu")
    )
    assert line["correct"], line["compared"]
    after = spans.snapshot()
    mine = {
        "spans": {},
        "counts": {k: v - before["counts"].get(k, 0) for k, v in after["counts"].items()},
    }
    monkeypatch.setattr(spans, "snapshot", lambda: mine)
    assert mine["counts"]["pump.admit_batched"] >= 4 * 3 * 5  # the warm rounds' at least
    assert mine["counts"].get("pump.admit_scalar", 0) == 0
    obs = {**obs_with(TRACED), "config": cell["config"]}
    assert READERS[ADMISSION](obs) == 100.0
    # the host verifier has no device seam: no dispatch was spanned
    assert READERS[DISPATCHES](obs) is None
