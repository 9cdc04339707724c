"""``narwhal20-wan.poisson512`` is judged on the median of its books
(``commit_p50_ms.wan``) and carries their tail as a per-layer number
(``commit_p95_ms.wan``): why, on synthetic books shaped like the cell's
(a window of nine to twelve waves, one of them without a commit moves
the tail by a fifth and the median by a thirtieth); and which end-to-end
metrics the three commit cells name, the WAN cell's under the bound its
sixteen chip runs gave (PERF.md section 2). That a per-layer metric's
``moves`` names an end-to-end metric each of its cells reports is
``test_cells.py``'s ``test_every_layer_metric_moves_a_metric_its_cells_report``.
"""

import importlib.util
import os
import random
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import cells  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "benchmark_manifest_rule", os.path.join(os.path.dirname(__file__), "manifest_rule.py")
)
rule = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(rule)

WAN = "narwhal20-wan.poisson512"
COMMITTEE = "committee256.poisson1k"
CRASH = "narwhal10-wan.poisson512-crash3"
MEDIAN, TAIL = "commit_p50_ms.wan", "commit_p95_ms.wan"
READERS = cells.load_readers(ROOT, [{"name": MEDIAN}, {"name": TAIL}])


# -- (a) what one skipped wave does to the tail and to the median -----------


def books(seed: int) -> tuple:
    """A window's commit latencies with every wave committed, and the
    same transactions with one wave skipped. The cell's shape (PERF.md
    section 5): a round ~1.5 s, a wave four rounds, 9-12 waves a window,
    50 tx/s; a transaction waits a base (its vertex's three hops, the
    leader's wait) and then for its wave's end, so latencies are uniform
    over a wave on top of the base; a wave without a commit leaves its
    transactions to the next wave's leader chain: one wave more."""
    rng = random.Random(seed)
    waves = rng.randint(9, 12)
    wave_s = 4 * rng.uniform(1.45, 1.55)
    base_s = rng.uniform(8.0, 8.4)
    skipped = rng.randrange(waves)
    whole, one_skipped = [], []
    for _ in range(int(50 * waves * wave_s)):
        due = rng.uniform(0.0, waves * wave_s)
        wave = int(due // wave_s)
        latency = base_s + (wave + 1) * wave_s - due
        whole.append(latency)
        one_skipped.append(latency + wave_s if wave == skipped else latency)
    return waves, whole, one_skipped


def read(name: str, latencies) -> float:
    return READERS[name]({"samples": {"commit_latency_s": latencies}})


@pytest.mark.parametrize("seed", (1, 2, 3, 2_147_483_659, 4_294_967_311))
def test_one_skipped_wave_moves_the_tail_by_a_tenth_and_more_and_the_median_under_4pct(seed):
    waves, whole, one_skipped = books(seed)
    assert 9 <= waves <= 12 and len(whole) > 2_000
    # the cell's own level: a median of ~11 s, a tail of ~14 s
    assert 10_500 < read(MEDIAN, whole) < 12_000 and 13_000 < read(TAIL, whole) < 15_000
    tail = read(TAIL, one_skipped) / read(TAIL, whole) - 1
    median = read(MEDIAN, one_skipped) / read(MEDIAN, whole) - 1
    # a wave is 8-11% of the window's transactions and the tail leaves 5%
    # above it, so it lands inside the skipped wave: +19-26% by arithmetic;
    # the median moves by wave_s / 2 / (waves - 1): +2.4-3.4%
    assert 0.10 < tail < 0.30, (waves, tail)
    assert 0.0 < median < 0.04, (waves, median)


def test_median_and_tail_of_the_wan_cell_are_read_by_the_quantities_own_files():
    assert cells.reader_path(ROOT, MEDIAN).endswith(os.sep + "commit_p50_ms.py")
    assert cells.reader_path(ROOT, TAIL).endswith(os.sep + "commit_p95_ms.py")
    assert read(MEDIAN, [1.0, 2.0, 3.0, 4.0]) == 2_000.0 and read(TAIL, [1.0, 2.0, 3.0]) == 3_000.0
    assert read(MEDIAN, []) is None and read(TAIL, []) is None


# -- (b) which end-to-end metrics the commit cells name ---------------------


@pytest.mark.parametrize(
    "cell, gate", [(WAN, MEDIAN), (COMMITTEE, "commit_p95_ms"), (CRASH, "commit_p95_ms")]
)
def test_the_commit_cells_end_to_end_metrics(cell, gate):
    loaded = cells.load_cell(ROOT, cell)
    rule.check_cell(cell, end_to_end=[gate, "setup_s"])
    assert {m["name"] for m in loaded["end_to_end"]} == rule.owned_names(cell, "end_to_end")
    # one gate a cell: the WAN cell's median, the others' tail
    other = "commit_p95_ms" if gate == MEDIAN else MEDIAN
    assert other not in rule.owned_names(cell, "end_to_end")
    entry = rule.assert_fields(
        gate, "end_to_end", cells_=[cell], unit="ms", better="lower", source="host_clock"
    )
    # the tail stays where the runs hold it, under 0.05 since the driver's
    # check read 0.04 at its lower end; the WAN cell's median has a bound of
    # its own: three times the mean of the two spreads that check read,
    # 3 x 0.0466, after it refused 0.09 as too tight (PERF.md section 2)
    if gate == MEDIAN:
        assert entry["bound"] == 0.14
        assert not rule.owns(COMMITTEE, entry) and not rule.owns(CRASH, entry)
    else:
        assert not rule.owns(WAN, entry) and entry["bound"] == 0.05


def test_the_wan_cells_traced_line_names_its_tail_and_its_skipped_waves():
    per_layer = {m["name"]: m for m in cells.load_cell(ROOT, WAN)["per_layer"]}
    assert {TAIL, "waves_without_commit_pct.wan", "mempool_cut_at_propose_pct.wan"} <= set(per_layer)
    assert not {MEDIAN, "commit_p95_ms", "mempool_cut_at_propose_pct",
                "waves_without_commit_pct"} & set(per_layer)
    assert per_layer[TAIL]["layer"] == "client" and per_layer[TAIL]["source"] == "host_clock"
    skipped = per_layer["waves_without_commit_pct.wan"]
    assert skipped["layer"] == "consensus" and skipped["source"] == "program_counter"
    assert cells.reader_path(ROOT, skipped["name"]) == cells.reader_path(
        ROOT, "waves_without_commit_pct"
    )
    # the crash cell's own keep their names and their tail
    crash = {m["name"]: m for m in cells.load_cell(ROOT, CRASH)["per_layer"]}
    assert crash["waves_without_commit_pct"]["moves"] == "commit_p95_ms"
