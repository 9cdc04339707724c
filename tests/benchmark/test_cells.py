"""The benchmark's own tests, on the CPU: the manifest and the files it
names, the yardstick's arithmetic, and each driver's window at n=4 on a
stack the test builds over the host verifier — with the control and the
planted faults that `correct` has to refuse.

The same windows over the device verifier on the CPU backend carry
``@pytest.mark.slow``: run them by hand before chip time is spent.
"""

import copy
import importlib.util
import json
import os
import random
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import (  # noqa: E402
    bytecount,
    cells,
    controls,
    loadgen,
    peaks,
    reference,
    roundpool,
    stats,
    trace,
)

_spec = importlib.util.spec_from_file_location(
    "benchmarks_run", os.path.join(ROOT, "benchmarks", "run.py")
)
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)

_spec = importlib.util.spec_from_file_location(
    "benchmark_manifest_rule", os.path.join(os.path.dirname(__file__), "manifest_rule.py")
)
rule = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(rule)

MANIFEST = rule.MANIFEST
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device"]
SEED = 2**31 + 11


def cpu_devices():
    import jax

    return jax.devices()[:1]


def small_cell(name: str) -> dict:
    """The cell at n=4, a size a test run can hold."""
    cell = copy.deepcopy(cells.load_cell(ROOT, name))
    cell["config"].update(n=4, f=1)
    if cell["config"]["driver"] == "inloop":
        cell["traffic"].update(clients=4, rate_tx_per_s=300.0, forged_vertices_per_s=5.0)
    else:
        cell["traffic"].update(clients=2, pool_rounds=4, wrong_per_round=2)
    return cell


# -- the manifest ----------------------------------------------------------


def test_manifest_names_files_that_exist():
    for cfg in MANIFEST["configs"]:
        path = os.path.join(ROOT, cfg["file"])
        assert os.path.exists(path), cfg["file"]
        with open(path) as fh:
            body = json.load(fh)
        assert os.path.exists(cells.driver_path(ROOT, body["driver"]))
        for key in cfg["reduced"]:
            assert key in body and key in body["reduced"], (cfg["name"], key)
        assert body["source"] == cfg["source"]
        assert body["guarantees"] and "assumed" in body
    for w in MANIFEST["workloads"]:
        assert any(c["name"] == w["config"] for c in MANIFEST["configs"])
        cells.traffic_path(ROOT, w["traffic"])
        cells.load_cell(ROOT, w["name"])
    for m in MANIFEST["per_layer"] + MANIFEST["end_to_end"]:
        assert os.path.exists(cells.reader_path(ROOT, m["name"])), m["name"]


def test_a_split_quantity_is_read_by_the_quantitys_reader(tmp_path):
    readers = tmp_path / "benchmarks" / "layer_metrics"
    readers.mkdir(parents=True)
    (readers / "dispatch_ms.py").write_text("def read(obs):\n    return 1\n")
    (readers / "dispatch_ms.serve.py").write_text("def read(obs):\n    return 2\n")
    got = cells.load_readers(
        str(tmp_path), [{"name": "dispatch_ms.train"}, {"name": "dispatch_ms.serve"}]
    )
    assert got["dispatch_ms.train"]({}) == 1 and got["dispatch_ms.serve"]({}) == 2
    assert cells.reader_path(ROOT, "device_idle_pct.commit") == cells.reader_path(
        ROOT, "device_idle_pct.verify"
    )


def test_every_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    all_cells = [w["name"] for w in MANIFEST["workloads"]]
    for m in MANIFEST["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", all_cells):
            assert cell in moved.get("workloads", all_cells), (m["name"], cell)
    for cell in all_cells:
        reported = [m["name"] for m in e2e.values() if cell in m.get("workloads", [cell])]
        assert "setup_s" in reported and len(reported) >= 2
        assert any(cell in m.get("workloads", [cell]) for m in MANIFEST["per_layer"])


def test_names_units_and_limits_of_the_manifest():
    assert set(MANIFEST) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names))
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert "roofline" not in m["name"] or m["unit"] == "%"
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200 and NAME.match(w["traffic"])
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and all(NAME.match(k) for k in c["reduced"])
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) < 64 * 1024


def test_a_cell_a_config_and_a_metric_are_added_without_editing_a_file(tmp_path):
    """A later PR's whole change: new files and new manifest entries."""
    root = str(tmp_path / "copy")
    os.makedirs(root)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), os.path.join(root, "benchmarks"))
    before = {}
    for d, _, files in os.walk(os.path.join(root, "benchmarks")):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()
    manifest = copy.deepcopy(MANIFEST)
    cfg = cells.load_cell(ROOT, "committee256.poisson1k")["config"]
    cfg = {**cfg, "name": "committee64", "n": 64, "f": 21}
    with open(os.path.join(root, "benchmarks", "configs", "committee64.json"), "w") as fh:
        json.dump(cfg, fh)
    with open(os.path.join(root, "benchmarks", "traffic", "uniform200.json"), "w") as fh:
        json.dump(
            {"loop": "open", "profile": "uniform", "clients": 64, "rate_tx_per_s": 200.0,
             "tx_bytes": 32, "forged_vertices_per_s": 0.5, "forged_round_lead": 3}, fh,
        )
    with open(os.path.join(root, "benchmarks", "layer_metrics", "cycles_in_window.py"), "w") as fh:
        fh.write("def read(obs):\n    return obs['counters'].get('cycles')\n")
    manifest["configs"].append(
        {"name": "committee64", "source": "a later PR's", "reduced": [], "why": "x",
         "file": "benchmarks/configs/committee64.json"}
    )
    manifest["workloads"].append(
        {"name": "committee64.uniform200", "config": "committee64",
         "traffic": "uniform200", "chips": 1, "why": "x"}
    )
    for m in manifest["end_to_end"]:
        if m["name"] == "commit_p95_ms":
            m["workloads"].append("committee64.uniform200")
    manifest["per_layer"].append(
        {"name": "cycles_in_window", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "client", "moves": "commit_p95_ms",
         "workloads": ["committee64.uniform200"]}
    )
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(manifest, fh)
    cell = cells.load_cell(root, "committee64.uniform200")
    assert cell["config"]["n"] == 64 and cell["traffic"]["profile"] == "uniform"
    rule.check_cell(
        "committee64.uniform200", manifest, per_layer=["cycles_in_window"],
        end_to_end=["commit_p95_ms", "setup_s"],
    )
    assert {m["name"] for m in cell["per_layer"]} == rule.owned_names(
        "committee64.uniform200", manifest=manifest
    )
    readers = cells.load_readers(root, cell["per_layer"])
    assert readers["cycles_in_window"]({"counters": {"cycles": 7}}) == 7
    assert cells.load_driver(root, cell["config"]["driver"]).run_window
    for path, body in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == body, path


def test_run_exits_nonzero_off_the_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "committee256.poisson1k", "--seed", str(SEED),
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "3"},
    )
    assert proc.returncode not in (0, None)
    assert proc.stdout.strip() == ""
    assert "TPU only" in proc.stderr


# -- the yardstick's arithmetic -------------------------------------------


def test_comb_walk_bytes_by_the_formula():
    assert bytecount.comb_walk_bytes(256) == 11_591_680
    assert bytecount.comb_walk_bytes(1) == 64 * 2 * 4 * 22 * 4 + 131 + 23 * 4 + 1


def test_peaks_table_refuses_an_unknown_device():
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9", "hbm_bytes_per_s")


@pytest.mark.parametrize(
    "values,q,want",
    [([], 95, None), ([3.0], 95, 3.0), (list(range(1, 101)), 95, 95),
     (list(range(1, 101)), 50, 50), ([5, 1, 9, 3], 50, 3), (list(range(1, 21)), 95, 19)],
)
def test_percentile_is_nearest_rank(values, q, want):
    assert stats.percentile(values, q) == want


def test_rate_is_all_work_over_all_time():
    assert stats.rate(300, 40.0) == 7.5
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_trace_reduction_on_a_hand_made_trace():
    ms = 1_000_000
    events = {
        "chips": {
            "/device:TPU:0": {
                # two runs of one program: 3 ms busy inside 4 ms, 2 ms inside 2 ms
                "ops": [["fusion.1", 10 * ms, 2 * ms], ["copy.2", 13 * ms, 1 * ms],
                        ["fusion.1", 50 * ms, 2 * ms]],
                "modules": [["jit__device_verify_comb(1)", 10 * ms, 4 * ms],
                            ["jit__device_verify_comb(1)", 50 * ms, 2 * ms]],
            }
        },
        "host": [["bench.sim_run", 0, 40 * ms], ["verify_batch.prepare", 5 * ms, 5 * ms],
                 ["bench.inject", 40 * ms, 10 * ms]],
    }
    r = trace.reduce(events, 0.1)
    assert r["busy_s"] == pytest.approx(0.005) and r["window_s"] == 0.1
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.004)]
    gaps = dict(r["idle_gaps"])
    assert gaps[trace.BETWEEN_OPS] == pytest.approx(0.001)
    assert gaps["verify_batch.prepare"] == pytest.approx(0.005)
    assert gaps["bench.sim_run"] == pytest.approx(0.005 + 0.026)
    assert gaps["bench.inject"] == pytest.approx(0.010)
    assert gaps[trace.UNANNOTATED] == pytest.approx(0.048)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(0.1)
    assert trace.program_seconds(r, "device_verify_comb") == pytest.approx([0.004, 0.002])
    assert trace.reduce({"chips": {}, "host": []}, 1.0) is None


def test_trace_reduction_on_the_recorded_v5e_trace():
    """Six seconds of committee256.poisson1k on a TPU v5e (my chip run,
    PR 25), as :func:`trace.extract` left it (recorded with
    ``json.dump(trace.extract(<the run's .xplane.pb>), fh)``): three pump
    cycles, six dispatches of the comb program."""
    path = os.path.join(
        ROOT, "benchmarks", "harness", "testdata", "committee256_v5e_trace.json"
    )
    with open(path) as fh:
        r = trace.reduce(json.load(fh), 6.0)
    assert r["busy_s"] == pytest.approx(0.003306372, rel=1e-9)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.000805087)]
    assert len(r["device_ops"]) == 10
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.sim_run"] == pytest.approx(5.918373245)
    assert gaps["verify_batch.prepare"] == pytest.approx(0.009601389)
    assert gaps[trace.BETWEEN_OPS] == pytest.approx(2.535e-06)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(6.0)
    runs = trace.program_seconds(r, "device_verify_comb")
    assert len(runs) == 6 and stats.mean(runs) == pytest.approx(0.0005514845)


def test_op_names_are_cut_out_of_the_hlo_text():
    assert trace.short("%fusion.1 = s32[16384,128]{1,0} fusion(s32[2,2] %x), kind=kCustom") == "fusion.1"
    assert trace.short("jit__device_verify_comb(87)") == "jit__device_verify_comb(87)"


def test_readers_return_nothing_where_there_is_nothing_to_read():
    readers = cells.load_readers(ROOT, MANIFEST["per_layer"] + MANIFEST["end_to_end"])
    empty = {"samples": {}, "counters": {}, "trace": None, "seconds": 40.0,
             "device_kind": "TPU v5 lite"}
    for name, read in readers.items():
        assert read(empty) is None, name


def test_roofline_reader_from_shapes_and_the_program_time():
    readers = cells.load_readers(ROOT, MANIFEST["per_layer"])
    obs = {
        "samples": {}, "counters": {"bucket": 256}, "device_kind": "TPU v5 lite",
        "trace": {"programs": {"jit__device_verify_comb": [0.002, 0.003]},
                  "busy_s": 0.5, "window_s": 2.0},
    }
    assert readers["comb_program_us"](obs) == pytest.approx(2500.0)
    assert readers["comb_roofline"](obs) == pytest.approx(100 * (11_591_680 / 819e9) / 0.0025)
    assert readers["device_idle_pct.verify"](obs) == pytest.approx(75.0)


# -- the references, held to the program -----------------------------------


def test_signing_bytes_equal_the_programs():
    from dag_rider_tpu.core.types import Block, Vertex, VertexID

    v = Vertex(
        id=VertexID(9, 2), block=Block((b"a" * 32, b"")),
        strong_edges=(VertexID(8, 3), VertexID(8, 0), VertexID(8, 1)),
        weak_edges=(VertexID(5, 2),), coin_share=b"\x01\x02",
    )
    mine = reference.signing_bytes(
        9, 2, v.block.transactions, v.strong_edges, v.weak_edges, v.coin_share
    )
    assert mine == v.signing_bytes()


def test_reference_keys_are_the_programs_test_pki():
    from dag_rider_tpu.verifier.base import KeyRegistry

    assert list(KeyRegistry.generate(8)[0].public_keys) == reference.Keys(8).public


@pytest.mark.parametrize("kind", ("",) + roundpool.KINDS)
def test_openssl_plain_and_program_oracles_agree_on_every_kind(kind):
    from dag_rider_tpu.verifier.base import KeyRegistry
    from dag_rider_tpu.verifier.cpu import CPUVerifier

    n = 4
    keys = reference.Keys(n)
    v = roundpool.sign(keys, 3, 1, (b"tx" * 16,), tuple((2, s) for s in range(3)))
    if kind:
        v = roundpool.corrupt(v, kind, n, random.Random(5))
    msg = reference.signing_bytes(v.rnd, v.source, v.transactions, v.strong)
    want = kind == ""
    assert keys.verify(v.source, msg, v.signature) is want
    assert reference.verify_plain(keys.public[v.source], msg, v.signature) is want
    assert roundpool.expected_mask(keys, [v]) == [want]
    program = CPUVerifier(KeyRegistry.generate(n)[0])
    assert program.verify_batch(roundpool.to_vertices([v])) == [want]
    lax = controls.LaxVerifier(KeyRegistry.generate(n)[0])
    assert lax.verify_batch(roundpool.to_vertices([v])) == [want or kind == "s_plus_l"]


def test_pool_has_the_same_sizes_whatever_the_seed():
    keys = reference.Keys(8)
    for seed in (1, SEED):
        pool = roundpool.make_pool(keys, n=8, rounds=6, wrong_per_round=5, seed=seed)
        assert [len(r) for r in pool] == [8] * 6
        assert all(sum(1 for v in r if v.wrong) == 5 for r in pool)
        assert all({v.wrong for v in r if v.wrong} == set(roundpool.KINDS) for r in pool)
    again = roundpool.make_pool(keys, n=8, rounds=6, wrong_per_round=5, seed=SEED)
    assert again == pool


def test_loadgen_is_the_programs_schedule_and_repeats():
    from dag_rider_tpu.mempool.loadgen import LoadGenerator

    traffic = cells.load_cell(ROOT, "committee256.poisson1k")["traffic"]
    mine = loadgen.LoadGenerator.from_traffic(traffic, SEED).events_until(0.5)
    again = loadgen.LoadGenerator.from_traffic(traffic, SEED).events_until(0.5)
    theirs = LoadGenerator(
        clients=256, rate=1000.0, tx_bytes=32, seed=SEED, profile="poisson"
    ).events_until(0.5)
    assert mine == again == theirs and 400 < len(mine) < 600


def test_delivered_order_faults_counts_divergence_and_repeats():
    a = [(1, 0), (1, 1), (2, 0)]
    assert reference.delivered_order_faults([a, a[:2], a]) == {
        "views_diverged": 0, "records_twice": 0,
    }
    assert reference.delivered_order_faults([a, [(1, 1)], a + [(1, 0)]]) == {
        "views_diverged": 1, "records_twice": 1,
    }


def _dag(rounds: int, n: int = 4):
    """Every vertex of rounds 1..rounds with strong edges to the whole
    round below, and the log DAG-Rider's rule makes of it when (1, 2) and
    then (5, 0) lead."""
    edges = {
        (r, s): tuple((r - 1, t) for t in range(n))
        for r in range(1, rounds + 1) for s in range(n)
    }
    first = [(1, 2)]
    second = sorted(set(k for k in edges if k[0] < 5 and k != (1, 2)) | {(5, 0)})
    return [(r, s, edges[(r, s)]) for r, s in first + second]


def test_order_unexplained_holds_a_log_to_dag_riders_rule():
    log = _dag(5)
    rule = dict(gc_depth=24, wave_length=4)
    assert reference.order_unexplained(log, **rule) == 0
    assert reference.order_unexplained([], **rule) == 0
    # two records of the second chunk swapped: nothing from there on is explained
    swapped = log[:3] + [log[4], log[3]] + log[5:]
    assert reference.order_unexplained(swapped, **rule) == len(log) - 1
    # a vertex of the leader's history left out
    assert reference.order_unexplained(log[:6] + log[7:], **rule) == len(log) - 2
    # a chunk that ends in no wave's first round
    assert reference.order_unexplained(log[:-1], **rule) == len(log) - 2
    # what lies gc_depth rounds under the leader is left out by rule
    assert reference.order_unexplained(log, gc_depth=4, wave_length=4) > 0
    kept = [log[0]] + [rec for rec in log[1:] if rec[0] > 1]
    assert reference.order_unexplained(kept, gc_depth=4, wave_length=4) == 0


# -- the windows, at n=4 ---------------------------------------------------


def inloop_over(verifier: str):
    def build(config, traffic, seed):
        from dag_rider_tpu.consensus.scenarios import coin_factory
        from dag_rider_tpu.consensus.simulator import Simulation

        driver = cells.load_driver(ROOT, "inloop")
        cfg = driver.sim_config(config)
        sim = Simulation(
            cfg, verifier=verifier, coin_factory=coin_factory(config["coin"], cfg.n, cfg.f)
        )
        return driver.assemble(sim, config, traffic, seed)

    return build


def sidecar_over(make_backend):
    def build(config, traffic, seed):
        driver = cells.load_driver(ROOT, "sidecar")
        return driver.control_stack(make_backend, config, traffic, seed)

    return build


def host_backend(registry):
    from dag_rider_tpu.verifier.cpu import CPUVerifier

    return CPUVerifier(registry)


def device_backend(registry):
    from dag_rider_tpu.verifier.tpu import TPUVerifier

    return TPUVerifier(registry)


def check_line(line: dict, cell: dict, trace_on: int) -> None:
    assert list(line)[: len(LINE_KEYS)] == LINE_KEYS and list(line)[-1] == "compared"
    assert set(line) - {"breakdown", "setup_parts", "compared"} == set(LINE_KEYS)
    assert 0 <= line["failed"] <= line["attempted"] and line["attempted"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    group = "per_layer" if trace_on else "end_to_end"
    known = {m["name"]: m["unit"] for m in cell[group]}
    assert line["metrics"] and set(line["metrics"]) <= set(known)
    for name, m in line["metrics"].items():
        assert m["unit"] == known[name] and m["value"] >= 0
        assert trace_on or m["value"] > 0  # an end-to-end metric is never 0
    if not trace_on:
        assert set(line["metrics"]) == set(known)
    for v in line["compared"].values():
        assert set(v) == {"value", "limit"}
    json.dumps(line)


@pytest.mark.parametrize("trace_on", (0, 1))
def test_committee_window_on_the_host_verifier(trace_on):
    cell = small_cell("committee256.poisson1k")
    line = bench.drive(cell, SEED, 2.0, trace_on, cpu_devices(), build=inloop_over("cpu"))
    check_line(line, cell, trace_on)
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 300


@pytest.mark.parametrize("trace_on", (0, 1))
def test_sidecar_window_on_the_host_verifier(trace_on):
    cell = small_cell("sidecar256.colocated4")
    line = bench.drive(cell, SEED, 1.5, trace_on, cpu_devices(), build=sidecar_over(host_backend))
    check_line(line, cell, trace_on)
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] >= 8


@pytest.mark.parametrize("control", (controls.LaxVerifier, controls.AcceptAll))
@pytest.mark.parametrize("name", ("committee256.poisson1k", "sidecar256.colocated4"))
def test_the_control_comes_out_as_not_correct(name, control):
    cell = small_cell(name)
    driver = cells.load_driver(ROOT, cell["config"]["driver"])

    def build(config, traffic, seed):
        return driver.control_stack(control, config, traffic, seed)

    line = bench.drive(cell, SEED, 2.0, 0, cpu_devices(), build=build)
    assert not line["correct"]
    assert line["compared"]["mask_mismatches"]["value"] > 0, line["compared"]


def test_an_answer_altered_where_it_is_produced_flips_correct_in_the_sidecar():
    """The timed path broken underneath: the backend's mask with one
    verdict flipped, every fourth call."""
    from dag_rider_tpu.verifier.cpu import CPUVerifier

    class Flipping(CPUVerifier):
        calls = 0

        def verify_batch(self, vertices):
            mask = super().verify_batch(vertices)
            self.calls += 1
            if self.calls % 4 == 0 and mask:
                mask[0] = not mask[0]
            return mask

    cell = small_cell("sidecar256.colocated4")
    line = bench.drive(cell, SEED, 1.5, 0, cpu_devices(), build=sidecar_over(Flipping))
    assert not line["correct"] and line["compared"]["mask_mismatches"]["value"] > 0


def test_a_rejected_honest_vertex_flips_correct_in_the_committee():
    """The mask altered where it is produced: every 50th honest vertex
    is refused (the committee recovers it; the count of refusals tells)."""
    from dag_rider_tpu.verifier.cpu import CPUVerifier

    class Refusing(CPUVerifier):
        seen = 0

        def verify_batch(self, vertices):
            mask = super().verify_batch(vertices)
            for i in range(len(mask)):
                self.seen += 1
                if self.seen % 50 == 0:
                    mask[i] = False
            return mask

    cell = small_cell("committee256.poisson1k")
    driver = cells.load_driver(ROOT, "inloop")

    def build(config, traffic, seed):
        return driver.control_stack(Refusing, config, traffic, seed)

    line = bench.drive(cell, SEED, 2.0, 0, cpu_devices(), build=build)
    assert not line["correct"]
    assert line["compared"]["mask_mismatches"]["value"] > 0
    assert line["compared"]["sig_rejects_off_expected"]["value"] > 0


def test_a_wrong_but_agreed_order_flips_correct_in_the_committee():
    """Every view swaps each pair of deliveries alike: the views agree,
    nothing is lost or doubled, and only the ordering rule tells."""
    cell = small_cell("committee256.poisson1k")
    plain = inloop_over("cpu")

    def build(config, traffic, seed):
        stack = plain(config, traffic, seed)
        for p in stack.sim.processes:
            p.on_deliver = swapped(p.on_deliver)
        return stack

    def swapped(deliver):
        held = []

        def on_deliver(v):
            held.append(v)
            if len(held) == 2:
                deliver(held.pop())
                deliver(held.pop())

        return on_deliver

    line = bench.drive(cell, SEED, 2.0, 0, cpu_devices(), build=build)
    failing = {k for k, v in line["compared"].items() if v["value"] > v["limit"]}
    assert not line["correct"] and "order_unexplained" in failing
    assert not failing & {"views_diverged", "vertices_delivered_twice"}, failing


def test_forged_vertices_go_out_by_the_clock_and_carry_their_own_messages():
    cell = small_cell("committee256.poisson1k")
    budgets = []
    plain = inloop_over("cpu")

    def build(config, traffic, seed):
        stack = plain(config, traffic, seed)
        run = stack.sim.run
        stack.sim.run = lambda max_messages: budgets.append(max_messages) or run(max_messages)
        return stack

    line = bench.drive(cell, SEED, 2.0, 1, cpu_devices(), build=build)
    assert line["correct"], line["compared"]
    n, sent = 4, sum((b - 16) // 3 for b in budgets)
    assert all(b >= n * n and (b - n * n) % (n - 1) == 0 for b in budgets)
    assert 8 <= sent <= 10  # 5 a second for 2 s, the last due at 1.9 s


def test_a_dropped_transaction_flips_correct_in_the_committee(monkeypatch):
    """A validator that loses every fifth block it was handed."""
    from dag_rider_tpu.consensus.process import Process

    real = Process.submit
    count = {"n": 0}

    def lossy(self, block):
        count["n"] += 1
        if count["n"] % 5 == 0 and block.transactions:
            return
        real(self, block)

    monkeypatch.setattr(Process, "submit", lossy)
    # what is lost is waited for to the drain's bound: shorten it in the
    # driver module the harness loads
    real_load = cells.load_driver

    def load(root, name):
        mod = real_load(root, name)
        mod.DRAIN_BOUND_S = 3.0
        return mod

    monkeypatch.setattr(cells, "load_driver", load)
    cell = small_cell("committee256.poisson1k")
    build = inloop_over("cpu")
    line = bench.drive(cell, SEED, 1.5, 0, cpu_devices(), build=build)
    assert not line["correct"]
    assert line["compared"]["tx_lost"]["value"] > 0
    assert line["failed"] == line["compared"]["tx_lost"]["value"]


# -- the same windows over the device verifier (CPU backend, jnp tree) -----


@pytest.mark.slow
def test_committee_window_on_the_device_verifier():
    cell = small_cell("committee256.poisson1k")
    line = bench.drive(cell, SEED, 3.0, 0, cpu_devices(), build=inloop_over("device"))
    check_line(line, cell, 0)
    assert line["correct"], line["compared"]


@pytest.mark.slow
def test_sidecar_window_on_the_device_verifier():
    cell = small_cell("sidecar256.colocated4")
    line = bench.drive(cell, SEED, 2.0, 1, cpu_devices(), build=sidecar_over(device_backend))
    check_line(line, cell, 1)
    assert line["correct"], line["compared"]
