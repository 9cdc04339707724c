"""The ``cluster_crash`` driver's cell (``narwhal10-wan.poisson512-crash3``)
on the CPU at n=4, f=1, validator 3 killed, the host verifier behind the
sidecar: a window that comes out ``correct`` with every compared number
0, the control and the planted faults that it has to refuse, why the
driver forges under dead sources only, the crash's reference against
hand-worked cases, and each new reader against a hand-filled book.
"""

import copy
import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import (  # noqa: E402
    cells,
    controls,
    reference_cluster,
    reference_crash,
)


def _sibling(stem):
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{stem}", os.path.join(os.path.dirname(__file__), f"{stem}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


base = _sibling("test_cells")
rule = _sibling("manifest_rule")

CRASH = "narwhal10-wan.poisson512-crash3"
WAN = "narwhal20-wan.poisson512"
MANIFEST = rule.MANIFEST
CRASH_METRICS = rule.owned(CRASH)
READERS = cells.load_readers(ROOT, CRASH_METRICS)
SECONDS = 3.0
MS = 1_000_000  # ns


def small_cell() -> dict:
    """The cell at n=4: validator 3 (stockholm) crashes at round 6 and
    the window opens a wave later; 100 tx/s at the three live doors, a
    wrong vertex every half second."""
    cell = copy.deepcopy(cells.load_cell(ROOT, CRASH))
    cell["config"].update(n=4, f=1, crashed=[3])
    cell["traffic"].update(
        clients=3, client_processes=2, rate_tx_per_s=100.0, forged_vertices_per_s=2.0,
        crashed=[3], settle_rounds=4,
    )
    return cell


def window_over(backend, trace_on: int = 0, forge=None) -> dict:
    """One window of the small cell with ``backend(registry)`` behind the
    sidecar (``forge`` in the place of the driver's own): the result
    line, and the stack and what was observed as ``check`` saw them."""
    cell = small_cell()
    driver = cells.load_driver(ROOT, "cluster_crash")
    if forge is not None:
        driver.cluster._forge = getattr(driver, forge)
    seen = {}
    check = driver.check

    def keeping(stack, observed):
        seen.update(stack=stack, observed=observed)
        return check(stack, observed)

    driver.check = keeping
    load = cells.load_driver
    cells.load_driver = lambda root, name: driver
    try:
        line = base.bench.drive(
            cell, base.SEED, SECONDS, trace_on, base.cpu_devices(),
            build=lambda c, t, s: driver.control_stack(backend, c, t, s),
        )
    finally:
        cells.load_driver = load
    return {"line": line, "cell": cell, "driver": driver, "check": check, **seen}


@pytest.fixture(scope="module")
def good():
    return window_over(base.host_backend, trace_on=1)


def recheck(good, **changed) -> dict:
    return good["check"](good["stack"], {**good["observed"], **changed})


def failing(compared: dict) -> set:
    return {k for k, v in compared.items() if v["value"] > v["limit"]}


# -- the window ------------------------------------------------------------


def test_the_window_is_correct_with_every_compared_number_zero(good):
    line = good["line"]
    base.check_line(line, good["cell"], 1)
    assert line["correct"], line["compared"]
    assert all(v["value"] == 0 and v["limit"] == 0 for v in line["compared"].values())
    assert {"crashed_still_running", "delivered_from_the_dead", "views_diverged", "tx_lost",
            "mask_mismatches", "forged_not_refused_at_validator0",
            "validators_killed_at_stop", "compiles_in_window"} <= set(line["compared"])
    assert len(line["compared"]) == 12 + 2 + 1
    assert line["failed"] == 0 and line["attempted"] > 200
    assert line["setup_parts"]["kill_and_settle_s"] > 0


def test_the_victim_died_by_round_before_the_window_and_nobody_stood_at_its_door(good):
    observed, traffic = good["observed"], good["cell"]["traffic"]
    crash = observed["counters"]["crash"]
    (victim,) = observed["victims"]
    assert victim["validator"] == 3 and not victim["alive"] and not victim["final_report"]
    assert victim["killed_at"] is not None and victim["late_lines"] == 0
    assert traffic["kill_at_round"] <= crash["killed_at_round"] < crash["window_from_round"]
    assert crash["window_from_round"] == traffic["kill_at_round"] + traffic["settle_rounds"]
    # it proposed what validator 0 had reached, give or take a round
    assert abs(victim["last_proposed"] - crash["killed_at_round"]) <= 2
    assert {b[2] for b in observed["books"].values()} == {0, 1, 2}
    # its log is a prefix (views_diverged 0) and stops where it died
    assert crash["victims_delivered"][0] < min(crash["live_delivered"])
    # every wrong vertex claimed its slot, reached all three that live and was refused
    c = observed["counters"]
    assert c["forged_sent"] == 6 and {w.source for w in good["stack"].forged} == {3}
    assert c["sig_rejects"][:3] == [6, 6, 6] and c["validators_killed_at_stop"] == 0


def test_the_traced_line_carries_every_new_metric_that_needs_no_device(good):
    metrics = good["line"]["metrics"]
    # the cell's own (program_loaded_pct needs the device verifier's program)
    want = {n for n in EXPECTED if rule.entry(n)["source"] != "device_trace"}
    assert want <= set(metrics), want - set(metrics)
    # stockholm gone: virginia, california and sydney wait for each other
    assert metrics["wan_floor_ms_per_round.crash"]["value"] == pytest.approx(300.0)
    assert metrics["round_ms.crash"]["value"] > metrics["wan_floor_ms_per_round.crash"]["value"]
    assert 0 <= metrics["waves_without_commit_pct"]["value"] <= 100
    assert metrics["leader_chain_waves_max"]["value"] >= 1
    assert metrics["net_peers_down"]["value"] == 1
    # a down peer costs a probe a second, not a third of all frames
    assert metrics["net_attempts_to_down_peers_pct"]["value"] < 5
    assert metrics["sync_requests_per_round"]["value"] < 1


def test_the_control_behind_the_sidecar_is_refused():
    line = window_over(controls.LaxVerifier)["line"]
    assert not line["correct"]
    assert line["compared"]["mask_mismatches"]["value"] > 0, line["compared"]
    assert line["compared"]["forged_not_refused_at_validator0"]["value"] > 0
    assert line["compared"]["crashed_still_running"]["value"] == 0


def test_a_wrong_vertex_under_a_live_source_is_never_delivered():
    """Why the driver forges under dead sources: relayed to every
    validator but its claimed source, a vertex under a living name
    reaches one fewer than the quorum that is everybody alive."""
    run = window_over(base.host_backend, forge="forge_under_any_source")
    compared, forged = run["line"]["compared"], run["stack"].forged
    living = sum(1 for w in forged if w.source != 3)
    assert living > 0
    assert compared["forged_not_refused_at_validator0"]["value"] == living
    assert compared["validator0_rejects_off_expected"]["value"] == living


# -- planted faults: check again on what the good window left --------------


def test_a_victim_left_running_is_refused(good):
    (victim,) = good["observed"]["victims"]
    for planted in ({"alive": True}, {"killed_at": None}, {"final_report": True},
                    {"late_lines": 2}):
        compared = recheck(good, victims=[{**victim, **planted}])
        assert failing(compared) == {"crashed_still_running"}, planted
        assert compared["crashed_still_running"]["value"] == 1


def test_a_vertex_under_the_victims_name_above_its_last_round_is_refused(good):
    (victim,) = good["observed"]["victims"]
    logs = [list(log) for log in good["observed"]["logs"]]
    ghost = next(rec for rec in reversed(min(logs[:3], key=len)) if rec["s"] == 0)
    for log in logs[:3]:
        at = next(i for i, rec in enumerate(log) if (rec["r"], rec["s"]) == (ghost["r"], 0))
        log[at] = {**log[at], "s": 3, "r": victim["last_proposed"] + 1}
    compared = recheck(good, logs=logs)
    # the spliced record verifies under nobody's key and hangs in no order
    assert "delivered_from_the_dead" in failing(compared)
    assert compared["delivered_from_the_dead"]["value"] == 1
    assert compared["views_diverged"]["value"] == 0


# -- the reference ---------------------------------------------------------


def test_the_crash_reference_reads_the_files_alone(tmp_path):
    events = tmp_path / "events.jsonl"
    events.write_text(
        '{"ts": 10.0, "event": "started"}\n'
        '{"ts": 11.0, "event": "round_advance", "round": 4}\n'
        '{"ts": 12.5, "event": "round_advance", "round": 5}\n'
        '{"ts": 13.0, "event": "round_adv'
    )
    assert reference_crash.last_proposed_round(str(events)) == 5
    assert reference_crash.last_proposed_round(str(tmp_path / "none")) == 0
    assert reference_crash.stamps_after(str(events), 12.0) == 1
    assert reference_crash.stamps_after(str(events), 12.5) == 0
    log = [{"r": 5, "s": 3}, {"r": 6, "s": 3}, {"r": 9, "s": 1}, {"r": 7, "s": 2}]
    assert reference_crash.delivered_from_the_dead(log, {3: 5, 2: 7}) == 1
    assert reference_crash.delivered_from_the_dead(log, {3: 4, 2: 6}) == 3


def test_the_floor_over_a_live_set_by_hand():
    # three alive of four, every link alike: 2f+1 = 3 = all of them, so
    # VAL at d, the third ECHO at 2d, the third READY at 3d
    uniform = {"a": {"a": 40.0}}
    assert reference_crash.wan_round_floor_ms(
        4, 1, ["a"] * 4, uniform, live=[0, 1, 2]
    ) == pytest.approx(120.0)
    # one of the three far away: nobody advances without it, three hops of 500
    far = {"a": {"a": 10.0, "b": 500.0}, "b": {"b": 10.0}}
    assert reference_crash.wan_round_floor_ms(
        4, 1, ["a", "a", "b", "a"], far, live=[0, 1, 2]
    ) == pytest.approx(1500.0)
    # with the far one dead and the fourth alive the near three never wait
    assert reference_crash.wan_round_floor_ms(
        4, 1, ["a", "a", "b", "a"], far, live=[0, 1, 3]
    ) == pytest.approx(30.0)
    with pytest.raises(ValueError):
        reference_crash.wan_round_floor_ms(4, 1, ["a"] * 4, uniform, live=[0, 1])


def test_the_floor_is_the_clusters_when_nobody_is_down():
    for name, n, f in ((WAN, 20, 6), (CRASH, 10, 3)):
        config = cells.load_cell(ROOT, name)["config"]
        names = config["regions"]
        regions = [names[i % 5] for i in range(n)]
        assert reference_crash.wan_round_floor_ms(
            n, f, regions, config["one_way_delay_ms"]
        ) == pytest.approx(
            reference_cluster.wan_round_floor_ms(n, f, regions, config["one_way_delay_ms"])
        )
    # the cell's own: sydney, stockholm and tokyo keep one validator each
    assert reference_crash.wan_round_floor_ms(
        10, 3, regions, config["one_way_delay_ms"], live=range(7)
    ) == pytest.approx(450.0)


# -- the manifest and the files --------------------------------------------


def test_the_configuration_and_the_traffic_state_the_fault():
    cell = cells.load_cell(ROOT, CRASH)
    config, traffic = cell["config"], cell["traffic"]
    wan = cells.load_cell(ROOT, WAN)["config"]
    assert (config["n"], config["f"], config["driver"]) == (10, 3, "cluster_crash")
    for key in ("regions", "one_way_delay_ms", "delay_jitter", "rbc", "coin", "cert",
                "gc_depth", "wave_length", "durability", "transport"):
        assert config[key] == wan[key], key
    assert config["committee_seed"] != wan["committee_seed"]
    assert config["crashed"] == traffic["crashed"] == [7, 8, 9] and traffic["kill"] == "SIGKILL"
    assert len(config["crashed"]) == config["f"]
    live_regions = {config["regions"][i % 5] for i in range(10) if i not in config["crashed"]}
    assert live_regions == set(config["regions"])  # every region keeps a validator
    assert traffic["clients"] == 7 and traffic["tx_bytes"] == 512
    assert traffic["kill_at_round"] > 5 and traffic["settle_rounds"] == 8
    assert {"crashed", "kill", "settle", "clients", "rate_tx_per_s", "committee_seed",
            "one_way_delay_ms"} <= set(config["assumed"])
    assert any("crashed validator's log is a prefix" in g for g in config["guarantees"])
    (entry,) = [w for w in MANIFEST["workloads"] if w["name"] == CRASH]
    assert entry["chips"] == 1
    assert rule.owns(CRASH, rule.entry("commit_p95_ms", "end_to_end"))


def test_the_driver_is_thin_and_owns_its_copy_of_the_clusters():
    driver = cells.load_driver(ROOT, "cluster_crash")
    other = cells.load_driver(ROOT, "cluster")
    assert driver.cluster is not other and driver.close is driver.cluster.close
    assert other._forge is not driver.cluster._forge  # the cluster cell forges as it did
    with open(cells.driver_path(ROOT, "cluster_crash")) as fh:
        assert len(fh.readlines()) < 200


# -- the readers, on a hand-filled book ------------------------------------


def stat(count, total, child=0, longest=0):
    return {"count": count, "total_ns": int(total), "max_ns": int(longest), "child_ns": int(child)}


#: validator 0 of a committee of 4 with one down, over 20 rounds
BOOK0 = {
    "spans": {
        "node.tick": stat(9_000, 6_000 * MS, child=5_000 * MS),
        "node.checkpoint": stat(100, 900 * MS),
        "net.broadcast": stat(500, 80 * MS),
        "net.send": stat(1_200, 240 * MS),
        "rbc.val": stat(40, 40 * MS, child=10 * MS),
        "rbc.echo": stat(120, 30 * MS, child=10 * MS),
        "rbc.ready": stat(120, 50 * MS, child=10 * MS),
        "remote.verify": stat(30, 150 * MS),
        # four commits closed 1 + 1 + 3 + 1 waves
        "pump.chain_waves": stat(4, 6, longest=3),
    },
    "counts": {"pump.round_advance": 20, "pump.wave_commit": 4, "pump.wave_skip": 2,
               "net.messages": 2_000, "net.to_down_peer": 30, "net.retry": 10,
               "net.peer_down": 1, "pump.sync_request": 0},
}
CLUSTER_BOOK = {"spans": {}, "validators": 3,
                "counts": {"pump.round_advance": 60, "pump.sync_request": 4}}
EXPECTED = {
    "commit_p50_ms.crash": 2_000.0,
    "round_ms.crash": 51_000 / 120,
    "wan_floor_ms_per_round.crash": 3 * 40.0,
    "node_tick_ms_per_round.crash": 6_000 / 20,
    "checkpoint_ms_per_round.crash": 900 / 20,
    "net_send_ms_per_round.crash": (80 + 240) / 20,
    "rbc_ms_per_round.crash": (30 + 20 + 40) / 20,
    "remote_verify_ms_per_round.crash": 150 / 20,
    "device_idle_pct.crash": 75.0,
    "comb_program_us.crash": 2_500.0,
    "waves_without_commit_pct": 100 * 2 / 6,
    "leader_chain_waves_max": 3,
    "net_attempts_to_down_peers_pct": 100 * 30 / 2_000,
    "net_retries_per_round": 10 / 20,
    "net_peers_down": 1,
    # four requests over 60 validator-rounds of 3 validators = 20 rounds
    "sync_requests_per_round": 4 / 20,
}
OWN = ("waves_without_commit_pct", "leader_chain_waves_max", "net_attempts_to_down_peers_pct",
       "net_retries_per_round", "net_peers_down", "sync_requests_per_round")


def obs_with(book0=BOOK0, cluster=CLUSTER_BOOK) -> dict:
    counters = {"rounds_advanced": 120, "window_s": 51.0, "bucket": 16}
    if book0 is not None:
        counters.update(validator0_book=book0, cluster_book=cluster)
    return {
        "samples": {"commit_latency_s": [1.0, 2.0, 3.0]},
        "counters": counters,
        "seconds": 51.0,
        "config": {"n": 4, "f": 1, "regions": ["a"], "one_way_delay_ms": {"a": {"a": 40.0}}},
        "traffic": {"crashed": [3]},
        "trace": {"programs": {"jit__device_verify_comb": [0.002, 0.003]},
                  "busy_s": 0.5, "window_s": 2.0},
        "device_kind": "TPU v5 lite",
    }


def test_the_manifest_has_the_cells_metrics_each_with_a_reader():
    """The cell's names are a floor: a later PR appends a metric, or this
    cell to a metric's ``workloads`` (``program_loaded_pct``, which moves
    ``setup_s``, is every cell's)."""
    rule.check_cell(CRASH, per_layer=EXPECTED, end_to_end=["commit_p95_ms"])
    for name in EXPECTED:
        m = rule.entry(name)
        assert m["moves"] == "commit_p95_ms" and m["source"] != "program_span"
    for m in CRASH_METRICS:
        assert os.path.exists(cells.reader_path(ROOT, m["name"]))
    mine = {m["name"] for m in cells.load_cell(ROOT, CRASH)["per_layer"]}
    assert mine == rule.owned_names(CRASH)  # the harness loads what the manifest lists
    # no roofline, nothing that scales by n
    assert not {"comb_roofline", "net_messages_per_round", "net_delay_lag_ms_per_message"} & mine
    for name in OWN + ("wan_floor_ms_per_round.crash",):
        assert cells.reader_path(ROOT, name).endswith(name + ".py")
    # the cluster cell's own metrics share no name with this cell's
    wan = {m["name"] for m in rule.owned(WAN) if not rule.owns(CRASH, m)}
    rule.assert_floor(_sibling("test_cluster_cell").EXPECTED, wan, WAN)
    assert not wan & set(EXPECTED)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_works_its_number_out_of_a_hand_filled_book(name):
    assert READERS[name](obs_with()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", OWN)
def test_reader_returns_nothing_where_the_book_lacks_the_name(name):
    """The parent's program counts none of these; its validators' books
    hold the older names only."""
    assert READERS[name](obs_with(book0=None)) is None
    older = {"spans": {"node.tick": stat(10, MS)},
             "counts": {"pump.round_advance": 20, "net.messages": 2_000}}
    assert READERS[name](obs_with(book0=older, cluster={**older, "validators": 3})) is None


def test_a_committee_that_loses_nobody_reads_zero_not_nothing():
    quiet = {"spans": {}, "counts": {**BOOK0["counts"], "pump.wave_skip": 0,
                                     "net.to_down_peer": 0, "net.retry": 0,
                                     "net.peer_down": 0}}
    obs = obs_with(book0=quiet, cluster={**CLUSTER_BOOK, "counts": {
        "pump.round_advance": 60, "pump.sync_request": 0}})
    for name in ("waves_without_commit_pct", "net_attempts_to_down_peers_pct",
                 "net_retries_per_round", "net_peers_down", "sync_requests_per_round"):
        assert READERS[name](obs) == 0, name
