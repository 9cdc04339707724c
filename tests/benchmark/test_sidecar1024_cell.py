"""The verify service of a 1,024-validator committee as a cell:
``sidecar1024.colocated4`` loads through the harness as data, reports the
sidecar cells' six per-layer metrics that are not read from the span
book and three that are (its server's decode, the gap between RPCs and
the prep), the two counters this cell brings and the share of programs
loaded, each worked out of a hand-filled sample set, book and trace (and
nothing without one), and the ``sidecar`` driver's comparison counts a
single wrong verdict of a 1,024-vertex mask.
"""

import importlib.util
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import bytecount, cells, reference, roundpool  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "benchmark_manifest_rule", os.path.join(os.path.dirname(__file__), "manifest_rule.py")
)
rule = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(rule)

CELL = "sidecar1024.colocated4"
MS = 1_000_000  # ns
ROUND_BYTES = 5_734_375  # one round of the pool on the wire
TABLE_BYTES = (1024 + 1) * 64 * 16 * 128 * 4


def stat(count, total_ms, child_ms=0.0):
    return {"count": count, "total_ns": int(total_ms * MS), "max_ns": 0,
            "child_ns": int(child_ms * MS)}


#: a sidecar that served 20 RPCs of a whole n=1,024 round each, from a
#: program it loaded
BOOK = {
    "spans": {
        "sidecar.rpc": stat(20, 1_200, child_ms=500),
        "sidecar.decode": stat(20, 400, child_ms=20),
        "sidecar.between_rpcs": stat(19, 418),
        "verify_batch.prepare": stat(20, 300),
    },
    "counts": {
        "sidecar.request_bytes": 20 * ROUND_BYTES,
        "verifier.table_bytes": TABLE_BYTES,
        "verifier.program_loaded": 1,
    },
}
PROGRAM_S = [0.0022, 0.0023]
OBS = {
    "samples": {
        "rpc_latency_s": [0.27, 0.25, 0.26],
        "server_span_s": [0.019, 0.021],
        "server_gap_s": [0.040, 0.042, 0.044],
    },
    "counters": {"bucket": 1024},
    "seconds": 51.0,
    "device_kind": "TPU v5 lite",
    "trace": {"programs": {"jit__device_verify_comb(3)": PROGRAM_S},
              "busy_s": 0.15, "window_s": 5.0},
}
#: the sidecar cells' metrics this cell reports, its name appended to
#: their ``workloads`` (six not read from the book, three of the server's
#: book), and the share of programs loaded, which every cell reports
LISTED = {
    "verify_rpc_p50_ms": 260.0,
    "sidecar_gap_ms_per_rpc": 42.0,
    "verify_batch_ms_per_rpc": 20.0,
    "device_idle_pct.verify": 97.0,
    "comb_program_us": 2250.0,
    "comb_roofline": 100 * (46_366_720 / 819e9) / 0.00225,
    "sidecar_decode_ms_per_rpc": (400 - 20) / 20,
    "sidecar_between_rpcs_ms_per_rpc": 418 / 19,
    "seam_prepare_ms_per_rpc": 300 / 20,
    "program_loaded_pct": 100.0,
}
#: the two counters the cell brings, listed for it alone
COUNTED = {
    "sidecar_request_kib_per_rpc": ROUND_BYTES / 1024,
    "comb_tables_mib": 512.5,
}
EXPECTED = {**LISTED, **COUNTED}


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(ROOT, CELL)


@pytest.fixture
def hand_filled(monkeypatch):
    from dag_rider_tpu.obs import spans

    monkeypatch.setattr(spans, "snapshot", lambda: BOOK)


def test_the_cell_is_data_over_the_sidecar_driver_and_colocated4(cell):
    config = cell["config"]
    assert (config["n"], config["f"], config["strong_edges_per_vertex"]) == (1024, 341, 683)
    assert roundpool.quorum(config["n"]) == 683
    assert config["driver"] == "sidecar" and cell["chips"] == 1
    assert cell["traffic"] == cells.load_cell(ROOT, "sidecar256.colocated4")["traffic"]
    rule.check_cell(
        CELL, per_layer=EXPECTED,
        end_to_end=["verify_rpc_p95_ms", "verified_sigs_per_s", "setup_s"],
    )
    assert {m["name"] for m in cell["per_layer"]} == rule.owned_names(CELL)
    # the sidecar cells' own metrics stay theirs: this cell is appended
    for name in LISTED:
        if name != "program_loaded_pct":
            rule.assert_fields(name, cells_=["sidecar256.colocated4", CELL])
    for name, unit, layer in (("sidecar_request_kib_per_rpc", "KiB", "sidecar server"),
                              ("comb_tables_mib", "MiB", "kernels")):
        rule.assert_fields(name, cells_=[CELL], unit=unit, better="lower",
                           source="program_counter", layer=layer, moves="verified_sigs_per_s")
        # the layer's name letter for letter, as the cell's other metrics have it
        assert layer in {m["layer"] for m in rule.owned("sidecar256.colocated4")}


@pytest.mark.parametrize("name", list(EXPECTED))
def test_each_metric_is_worked_out_of_a_hand_filled_book_and_nothing_without(
    name, cell, hand_filled
):
    entries = [m for m in cell["per_layer"] if m["name"] == name] or [{"name": name}]
    read = cells.load_readers(ROOT, entries)[name]
    assert read(OBS) == pytest.approx(EXPECTED[name])
    # the run's line at --trace 0: no trace, no samples of its own
    assert read({**OBS, "samples": {}, "trace": None}) is None


@pytest.mark.parametrize("name", list(COUNTED))
def test_the_new_counters_read_nothing_from_a_program_that_does_not_count_them(
    name, monkeypatch
):
    from dag_rider_tpu.obs import spans

    monkeypatch.setattr(spans, "snapshot", lambda: {"spans": BOOK["spans"], "counts": {}})
    read = cells.load_readers(ROOT, [{"name": name}])[name]
    assert read(OBS) is None


def test_the_roofline_moves_the_bytes_of_a_bucket_1024_walk():
    assert bytecount.comb_walk_bytes(1024) == 46_366_720


def test_the_check_counts_one_flipped_verdict_as_one_mismatch(cell):
    driver = cells.load_driver(ROOT, "sidecar")
    n = cell["config"]["n"]
    seed = 2**31 + 3601
    # two rounds of the pool are enough for the arithmetic
    traffic = {**cell["traffic"], "pool_rounds": 2}
    keys = reference.Keys(n)
    pool = roundpool.make_pool(keys, n=n, rounds=2, wrong_per_round=8, seed=seed)
    masks = ["".join("1" if ok else "0" for ok in roundpool.expected_mask(keys, r))
             for r in pool]
    backend = types.SimpleNamespace(stats=lambda: {"compile_s": {"1024xpallas": 1.0}})
    stack = types.SimpleNamespace(
        traffic=traffic, keys=keys, n=n, seed=seed,
        timed=types.SimpleNamespace(backend=backend),
    )
    rpcs = [[k % 2, 0.0, 0.1, 0, masks[k % 2]] for k in range(4)]
    observed = {"rpcs": rpcs, "failed": 0, "counters": {"clients_with_libtpu": 0}}
    compared = driver.check(stack, observed)
    assert all(v["value"] == 0 for v in compared.values()), compared
    flipped = masks[1][:500] + ("0" if masks[1][500] == "1" else "1") + masks[1][501:]
    rpcs[3][4] = flipped
    assert driver.check(stack, observed)["mask_mismatches"]["value"] == 1
