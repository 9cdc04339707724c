"""The per-layer metrics that read the program's span book
(``benchmarks/harness/spanbook.py`` and their readers): nothing where
there is nothing to read, the stated arithmetic on a hand-filled book,
and a value for every one of them after each driver's window at n=4.
"""

import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import cells, spanbook  # noqa: E402


def _sibling(stem):
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{stem}", os.path.join(os.path.dirname(__file__), f"{stem}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# the windows at n=4 are built as test_cells.py builds them
base = _sibling("test_cells")
rule = _sibling("manifest_rule")

SPAN_METRICS = [m for m in rule.MANIFEST["per_layer"] if m["source"] == "program_span"]
READERS = cells.load_readers(ROOT, SPAN_METRICS)
COMMITTEE = "committee256.poisson1k"
SIDECAR = "sidecar256.colocated4"
MS = 1_000_000  # ns


def stat(count, total_ms, child_ms=0.0, max_ms=0.0):
    return {
        "count": count,
        "total_ns": int(total_ms * MS),
        "max_ns": int(max_ms * MS),
        "child_ns": int(child_ms * MS),
    }


#: a committee of 4 that advanced 10 rounds, and a sidecar that served
#: 20 RPCs: round numbers, so that each metric can be worked out by hand.
#: The committee's tree is whole — every span's ``child_ms`` is the sum
#: of the totals nested in it — so its self times add up to pump.run.
BOOK = {
    "spans": {
        # run > deliver, collect, verify, apply, step
        "pump.run": stat(10, 10_000, child_ms=4_000 + 50 + 500 + 150 + 5_000),
        "pump.deliver": stat(10, 4_000, child_ms=100),  # a collection of 100
        "pump.collect": stat(10, 50),
        "pump.apply": stat(10, 150),
        # verify > window > prepare, dispatch, resolve, overlap > order, prune
        "pump.verify": stat(10, 500, child_ms=480),
        "seam.window": stat(10, 480, child_ms=100 + 50 + 200 + 100),
        "verify_batch.prepare": stat(20, 100),
        "verify_batch.dispatch": stat(20, 50),
        "verify_batch.resolve": stat(20, 200),
        "seam.overlap": stat(10, 100, child_ms=50 + 40),
        "pump.prune": stat(4, 40),
        # step > insert, propose, wave (a retry), sync
        "pump.step": stat(10, 5_000, child_ms=600 + 2_500 + 200 + 100),
        "pump.insert": stat(80, 600),
        "pump.sync": stat(40, 100),
        # propose > sign, share, wave (at the boundary)
        "pump.propose": stat(80, 2_500, child_ms=300 + 60 + 1_000),
        "sign.vertex": stat(40, 300),
        "coin.share": stat(12, 60),
        # wave > combine, chain, order (inline)
        "pump.wave": stat(12, 1_000 + 200, child_ms=700 + 250 + 100),
        "pump.chain": stat(8, 250),
        "pump.order": stat(8, 50 + 100),
        "coin.combine": stat(3, 700, child_ms=150),  # a collection of 150
        "host.gc": stat(2, 100 + 150),
        "mempool.wait": stat(25, 50_000, max_ms=4_000),
        "sidecar.rpc": stat(20, 1_000, child_ms=700),
        "sidecar.decode": stat(20, 400, child_ms=20),
        "sidecar.between_rpcs": stat(19, 1_500, max_ms=420),
    },
    "counts": {"pump.round_advance": 40},
}

EXPECTED = {
    "pump_deliver_ms_per_round": (4_000 - 100) / 10,
    # no pump.inbox (the scalar pump) and no pump.cert (cert off)
    "pump_insert_ms_per_round": (600 + 50 + 150 + 100) / 10,
    "pump_propose_ms_per_round": (2_500 - 1_360 + 300) / 10,
    "pump_wave_ms_per_round": (1_200 - 1_050 + 250) / 10,
    "pump_order_ms_per_round": (150 + 40) / 10,
    "coin_ms_per_round": (60 + 700 - 150) / 10,
    "pump_unspanned_pct": 100 * (300 + 1_600) / 10_000,
    "mempool_wait_ms_per_block": 50_000 / 25,
    "host_gc_pct.commit": 100 * 250 / 10_000,
    "sidecar_decode_ms_per_rpc": (400 - 20) / 20,
    "sidecar_between_rpcs_ms_per_rpc": 1_500 / 19,
    "sidecar_between_rpcs_max_ms": 420.0,
    "seam_prepare_ms_per_rpc": 100 / 20,
    "host_gc_pct.verify": 100 * 250 / (1_000 + 1_500),
}
#: the committee's span metrics and the sidecar's: each of the fourteen
#: is one cell's or the other's
SIDECAR_SPANS = {"sidecar_decode_ms_per_rpc", "sidecar_between_rpcs_ms_per_rpc",
                 "sidecar_between_rpcs_max_ms", "seam_prepare_ms_per_rpc", "host_gc_pct.verify"}
COMMITTEE_SPANS = set(EXPECTED) - SIDECAR_SPANS
#: the verify seam of the committee's tree, which no span metric of that
#: cell reads (``seam_ms_per_dispatch`` times it from outside)
SEAM = ("pump.verify", "seam.window", "seam.overlap", "verify_batch.prepare",
        "verify_batch.dispatch", "verify_batch.resolve")


def obs_with(trace) -> dict:
    return {"samples": {}, "counters": {}, "seconds": 40.0, "trace": trace,
            "device_kind": "TPU v5 lite", "config": {"n": 4}}


TRACED = {"programs": {}, "busy_s": 0.1, "window_s": 4.0}


@pytest.fixture
def hand_filled(monkeypatch):
    from dag_rider_tpu.obs import spans

    monkeypatch.setattr(spans, "snapshot", lambda: BOOK)


def test_the_manifest_has_the_fourteen_span_metrics_each_with_a_reader_of_its_own():
    """The fourteen are a floor: a later PR appends a span metric, or a
    cell to one's ``workloads`` (``sidecar1024.colocated4`` reads three of
    the sidecar's from its own server's book)."""
    rule.assert_floor(EXPECTED, {m["name"] for m in SPAN_METRICS}, "program_span")
    for name in EXPECTED:
        rule.assert_fields(name, better="lower", source="program_span")
    for m in SPAN_METRICS:
        assert cells.reader_path(ROOT, m["name"]).endswith(m["name"] + ".py")
    for cell, names in ((COMMITTEE, COMMITTEE_SPANS), (SIDECAR, SIDECAR_SPANS)):
        rule.check_cell(cell, per_layer=names)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_works_its_number_out_of_a_hand_filled_book(name, hand_filled):
    assert READERS[name](obs_with(TRACED)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_returns_nothing_in_a_run_that_takes_no_trace(name, hand_filled):
    # the book is full (it is process-wide, an earlier test's simulation
    # may have filled it), but the run's line is the end-to-end one
    assert READERS[name](obs_with(None)) is None
    no_trace_key = obs_with(None)
    del no_trace_key["trace"]
    assert READERS[name](no_trace_key) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_returns_nothing_from_a_program_without_the_span_module(name, monkeypatch):
    import dag_rider_tpu.obs as obs_pkg

    monkeypatch.delattr(obs_pkg, "spans")
    monkeypatch.setitem(sys.modules, "dag_rider_tpu.obs.spans", None)
    assert READERS[name](obs_with(TRACED)) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_returns_nothing_for_names_never_recorded(name, monkeypatch):
    from dag_rider_tpu.obs import spans

    monkeypatch.setattr(spans, "snapshot", lambda: {"spans": {}, "counts": {}})
    assert READERS[name](obs_with(TRACED)) is None


def test_the_collectors_share_reads_zero_where_no_full_collection_ran(monkeypatch):
    from dag_rider_tpu.obs import spans

    quiet = {"spans": {k: v for k, v in BOOK["spans"].items() if k != "host.gc"},
             "counts": BOOK["counts"]}
    monkeypatch.setattr(spans, "snapshot", lambda: quiet)
    assert READERS["host_gc_pct.commit"](obs_with(TRACED)) == 0.0
    assert READERS["host_gc_pct.verify"](obs_with(TRACED)) == 0.0


def test_the_committees_metrics_leave_no_span_of_the_pump_out(hand_filled):
    """What PERF.md shows of a chip run, held on the hand-filled book:
    the six phase metrics, the seam, the collector and the unspanned
    part are the whole of ``pump.run`` — no span falls between them."""
    obs = obs_with(TRACED)
    book = spanbook.open_book(obs)
    run_ms = book.total_ns("pump.run") / 10 / MS
    phases = sum(
        READERS[n](obs)
        for n in ("pump_deliver_ms_per_round", "pump_insert_ms_per_round",
                  "pump_propose_ms_per_round", "pump_wave_ms_per_round",
                  "pump_order_ms_per_round", "coin_ms_per_round")
    )
    unspanned = READERS["pump_unspanned_pct"](obs) / 100 * run_ms
    collector = READERS["host_gc_pct.commit"](obs) / 100 * run_ms
    seam = book.self_ns(*SEAM) / 10 / MS
    assert phases + seam + collector + unspanned == pytest.approx(run_ms)


#: the registered names no reader file names, and who reads each instead
READ_ELSEWHERE = {
    "pump.verify": "Process.apply_verify_mask and phase_verify take its seconds",
    "seam.window": "VerifierPipeline.last_seam_s (seam_ms_per_dispatch)",
    "seam.overlap": "taken off seam.window for last_seam_s",
    "verify_batch.resolve": "last_dispatch_s / wait_s; the trace's idle gaps",
}


def test_every_registered_span_and_counter_has_a_reader():
    """A span that nothing reads is cost for nothing: each registered
    name is in a reader file of the benchmark, or on the short list of
    those whose seconds the program or the device trace reads."""
    import glob

    from benchmarks.harness import trace
    from dag_rider_tpu.obs import spans

    files = glob.glob(os.path.join(ROOT, "benchmarks", "layer_metrics", "*.py"))
    files.append(os.path.join(ROOT, "benchmarks", "harness", "spanbook.py"))
    text = "".join(open(f).read() for f in files)
    unread = {n for n in spans.KNOWN_SPANS | spans.KNOWN_COUNTS if f'"{n}"' not in text}
    assert unread == set(READ_ELSEWHERE)
    assert "verify_batch." in trace.HOST_PREFIXES
    program = "".join(
        open(os.path.join(ROOT, "dag_rider_tpu", *rel)).read()
        for rel in (("consensus", "process.py"), ("consensus", "simulator.py"),
                    ("verifier", "pipeline.py"), ("verifier", "tpu.py"))
    )
    for name in READ_ELSEWHERE:
        assert f'obs.span("{name}") as ' in program, name  # its seconds are taken


def _metrics_after_a_window(name, build) -> dict:
    cell = base.small_cell(name)
    line = base.bench.drive(cell, base.SEED, 1.5, 0, base.cpu_devices(), build=build)
    assert line["correct"], line["compared"]
    observed = {"samples": {}, "counters": {}, "seconds": 1.5}
    return base.bench.read_metrics(
        cell, "per_layer", observed, trace=TRACED, device_kind="TPU v5 lite"
    )


def test_every_committee_span_metric_has_a_value_after_a_window_at_n4():
    metrics = _metrics_after_a_window(COMMITTEE, base.inloop_over("cpu"))
    want = {m["name"] for m in SPAN_METRICS if rule.owns(COMMITTEE, m)}
    assert want <= set(metrics)
    assert all(metrics[n]["value"] >= 0 for n in want)
    assert 0 <= metrics["pump_unspanned_pct"]["value"] < 50
    assert metrics["mempool_wait_ms_per_block"]["value"] > 0


def test_every_sidecar_span_metric_but_the_device_seams_has_a_value_after_a_window_at_n4():
    metrics = _metrics_after_a_window(SIDECAR, base.sidecar_over(base.host_backend))
    want = {m["name"] for m in SPAN_METRICS if rule.owns(SIDECAR, m)}
    # verify_batch.prepare is the device verifier's; the host verifier has none
    assert want - {"seam_prepare_ms_per_rpc"} <= set(metrics)
    assert metrics["sidecar_between_rpcs_max_ms"]["value"] >= (
        metrics["sidecar_between_rpcs_ms_per_rpc"]["value"]
    )
    assert metrics["sidecar_decode_ms_per_rpc"]["value"] > 0
