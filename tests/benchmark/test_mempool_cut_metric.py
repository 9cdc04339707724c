"""``mempool_cut_at_propose_pct`` (the committee's) and
``mempool_cut_at_propose_pct.wan`` (the WAN cell's: a name for each,
since the two cells report different end-to-end metrics; one reader
file): the stated arithmetic on a hand-filled book, nothing where the
program counts neither kind of cut or the run left no book, and after a
window at n=4 of each cell: 100 where the validators are ``Node``s
(their proposers cut their own blocks), 0 where the driver feeds
``Process.submit`` itself.
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
NAME = "mempool_cut_at_propose_pct"
WAN = "narwhal20-wan.poisson512"
COMMITTEE = "committee256.poisson1k"
WAN_NAME = NAME + ".wan"
ENTRY = rule.entry(NAME)
WAN_ENTRY = rule.entry(WAN_NAME)
READERS = cells.load_readers(ROOT, [ENTRY, WAN_ENTRY])
READ = READERS[NAME]
TRACED = {"programs": {}, "busy_s": 0.1, "window_s": 4.0}


def book(**counts):
    return {"spans": {}, "counts": {"pump.round_advance": 10, **counts}}


def cluster_obs(book0):
    """What the ``cluster`` driver observes: validator 0's book beside
    the counters; this process's own book holds no mempool."""
    counters = {} if book0 is None else {"validator0_book": book0}
    return {"samples": {}, "counters": counters, "seconds": 51.0, "trace": TRACED,
            "device_kind": "TPU v5 lite", "config": {"n": 4}}


@pytest.fixture
def own_book(monkeypatch):
    """This process's book, hand-filled: the ``inloop`` driver's views
    and their mempools live in the run's own process."""
    from dag_rider_tpu.obs import spans

    def fill(**counts):
        monkeypatch.setattr(spans, "snapshot", lambda: book(**counts))

    fill()
    return fill


@pytest.mark.parametrize(
    "entry, cell, moves",
    [(ENTRY, COMMITTEE, "commit_p95_ms"), (WAN_ENTRY, WAN, "commit_p50_ms.wan")],
)
def test_the_manifest_lists_the_metric_once_for_each_cell_with_a_mempool(entry, cell, moves):
    """Split by what the two cells report end to end, read by one file:
    each name is listed for its own cell and not for the other's, and
    every cell that lists it reports the end-to-end metric it moves."""
    rule.assert_fields(
        entry["name"], cells_=[cell], unit="%", better="higher", source="program_counter",
        layer="mempool", moves=moves,
    )
    assert cells.reader_path(ROOT, entry["name"]).endswith(os.sep + NAME + ".py")
    rule.check_cell(cell, per_layer=[entry["name"]], end_to_end=[moves])
    for other in rule.cell_names():
        listed = entry["name"] in {m["name"] for m in cells.load_cell(ROOT, other)["per_layer"]}
        assert listed == rule.owns(other, entry), other
        assert not listed or moves in rule.owned_names(other, "end_to_end"), other
    assert not rule.owns(WAN if cell == COMMITTEE else COMMITTEE, entry)


@pytest.mark.parametrize(
    "counts, want",
    [
        ({"mempool.cut_at_propose": 60}, 100.0),
        ({"mempool.cut_ahead": 60}, 0.0),
        ({"mempool.cut_at_propose": 45, "mempool.cut_ahead": 15}, 75.0),
    ],
)
def test_reader_works_the_share_out_of_validator_0s_book(counts, want, own_book):
    own_book(**{"mempool.cut_ahead": 1_000})  # not this book: validator 0's
    assert READERS[WAN_NAME](cluster_obs(book(**counts))) == pytest.approx(want)
    assert READ(cluster_obs(book(**counts))) == pytest.approx(want)  # one file for both


@pytest.mark.parametrize(
    "counts, want",
    [({"mempool.cut_ahead": 900}, 0.0), ({"mempool.cut_at_propose": 1, "mempool.cut_ahead": 3}, 25.0)],
)
def test_reader_takes_this_processs_book_where_no_validator_left_one(counts, want, own_book):
    own_book(**counts)
    assert READ(cluster_obs(None)) == pytest.approx(want)


def test_reader_returns_nothing_from_a_program_that_counts_neither(own_book):
    # the parent: a validator's book with its spans and other counters
    assert READ(cluster_obs(book(**{"net.messages": 3_840}))) is None
    assert READ(cluster_obs(None)) is None
    assert READ(cluster_obs(book(**{"mempool.cut_at_propose": 0, "mempool.cut_ahead": 0}))) is None


def test_reader_returns_nothing_where_there_is_no_book_to_read(own_book, monkeypatch):
    own_book(**{"mempool.cut_ahead": 5})
    untraced = cluster_obs(None)
    untraced["trace"] = None  # an end-to-end run: the process's book is not the run's
    assert READ(untraced) is None
    import dag_rider_tpu.obs as obs_pkg

    monkeypatch.delattr(obs_pkg, "spans")
    monkeypatch.setitem(sys.modules, "dag_rider_tpu.obs.spans", None)
    assert READ(cluster_obs(None)) is None  # a program from before the span primitive


def test_both_counters_are_registered_and_the_reader_names_them():
    from dag_rider_tpu.obs import spans

    text = open(cells.reader_path(ROOT, NAME)).read()
    for counter in ("mempool.cut_at_propose", "mempool.cut_ahead"):
        assert counter in spans.KNOWN_COUNTS and f'"{counter}"' in text


def test_the_committees_driver_cuts_every_block_ahead_at_n4():
    span_metrics = _sibling("test_span_metrics")
    metrics = span_metrics._metrics_after_a_window(COMMITTEE, base.inloop_over("cpu"))
    assert metrics[NAME] == {"value": 0.0, "unit": "%"}
    assert metrics["mempool_wait_ms_per_block"]["value"] > 0


def test_the_clusters_validators_cut_every_block_at_the_proposal_at_n4():
    cluster_cell = _sibling("test_cluster_cell")
    seen = cluster_cell.window_over(base.host_backend, trace_on=1)
    line = seen["line"]
    assert line["correct"], line["compared"]
    assert line["metrics"][WAN_NAME] == {"value": 100.0, "unit": "%"}
    assert NAME not in line["metrics"]
    book0 = seen["observed"]["counters"]["validator0_book"]
    assert book0["counts"]["mempool.cut_at_propose"] == book0["spans"]["mempool.wait"]["count"]
    assert "mempool.cut_ahead" not in book0["counts"]
    # a block waits for the validator's next vertex, not behind a queue
    # of staged ones: its mean wait is under two of the run's rounds
    wait_ms = book0["spans"]["mempool.wait"]["total_ns"] / book0["spans"]["mempool.wait"]["count"] / 1e6
    assert wait_ms < 2 * line["metrics"]["round_ms.wan"]["value"]
