"""The ``cluster`` driver's cell (``narwhal20-wan.poisson512``) on the
CPU at n=4, the host verifier behind the sidecar: a window that comes
out ``correct`` with every compared number 0, the controls and the
planted faults that it has to refuse, the refusal of a program without
per-link delays, the new references, and each new reader against a
hand-filled book. ``sidecar256.colocated1`` is ``colocated4``'s driver
with one client: its files are held to the manifest here too.
"""

import copy
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import (  # noqa: E402
    cells,
    controls,
    reference,
    reference_cluster,
    validatorbook,
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

WAN = "narwhal20-wan.poisson512"
CO1 = "sidecar256.colocated1"
MANIFEST = rule.MANIFEST
WAN_METRICS = rule.owned(WAN)
READERS = cells.load_readers(ROOT, WAN_METRICS)
SECONDS = 3.0
MS = 1_000_000  # ns


def small_cell() -> dict:
    """The cell at n=4: four regions' delays, 100 tx/s, a wrong vertex
    every half second."""
    cell = copy.deepcopy(cells.load_cell(ROOT, WAN))
    cell["config"].update(n=4, f=1)
    cell["traffic"].update(
        clients=4, client_processes=2, rate_tx_per_s=100.0, forged_vertices_per_s=2.0
    )
    return cell


def window_over(backend, trace_on: int = 0) -> dict:
    """One window of the small cell with ``backend(registry)`` behind the
    sidecar: the result line, and the stack and what was observed as
    ``check`` saw them."""
    cell = small_cell()
    driver = cells.load_driver(ROOT, "cluster")
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
    """``check`` again on what the good window observed, with some of it
    replaced."""
    return good["check"](good["stack"], {**good["observed"], **changed})


def failing(compared: dict) -> set:
    return {k for k, v in compared.items() if v["value"] > v["limit"]}


# -- the window ------------------------------------------------------------


def test_the_window_is_correct_with_every_compared_number_zero(good):
    line = good["line"]
    base.check_line(line, good["cell"], 1)
    assert line["correct"], line["compared"]
    assert all(v["value"] == 0 and v["limit"] == 0 for v in line["compared"].values())
    assert {"mask_mismatches", "order_unexplained", "views_diverged",
            "vertices_delivered_twice", "tx_lost", "tx_delivered_twice",
            "delivered_bad_signatures", "runners_with_libtpu",
            "compiles_in_window"} <= set(line["compared"])
    assert line["failed"] == 0 and line["attempted"] > 200
    assert line["setup_parts"]["host_cpus"] == os.cpu_count()
    c = good["observed"]["counters"]
    assert c["forged_sent"] == 6 and c["rounds_advanced"] >= 4
    # every wrong vertex reached every verifier but its claimed source's
    assert sum(c["sig_rejects"]) == 6 * 3 and c["sig_rejects"][0] == 6


def test_the_traced_line_carries_every_new_metric_that_needs_no_device(good):
    metrics = good["line"]["metrics"]
    # the cell's own (program_loaded_pct needs the device verifier's program)
    want = {n for n in EXPECTED if rule.entry(n)["source"] != "device_trace"}
    assert want <= set(metrics), want - set(metrics)
    assert metrics["wan_floor_ms_per_round"]["value"] == pytest.approx(216.975)
    assert metrics["round_ms.wan"]["value"] > metrics["wan_floor_ms_per_round"]["value"]
    # VAL, ECHO and READY to each of 3 peers for each of 4 vertices, and
    # the sync traffic on top
    assert metrics["net_messages_per_round"]["value"] >= 2 * 4**3 * 3 / 4
    assert metrics["net_messages_per_rpc"]["value"] >= 1
    assert 1 <= metrics["remote_rpcs_per_round"]["value"] <= 4 + 1
    assert metrics["node_tick_ms_per_round"]["value"] > metrics["rbc_ms_per_round"]["value"]


def test_the_end_to_end_line_has_the_cells_two_metrics(good):
    cell, observed = good["cell"], good["observed"]
    line = base.bench.read_metrics(cell, "end_to_end", observed, setup_s=12.5)
    assert set(line) == rule.owned_names(WAN, "end_to_end")
    rule.assert_floor({"commit_p50_ms.wan", "setup_s"}, line)
    assert line["commit_p50_ms.wan"]["value"] > 3 * 216.975  # no commit under a wave
    # the tail of the same books stays in the traced line, ungated
    traced = good["line"]["metrics"]
    assert traced["commit_p95_ms.wan"]["value"] >= line["commit_p50_ms.wan"]["value"]
    assert "commit_p50_ms.wan" not in traced and "commit_p95_ms" not in traced


@pytest.mark.parametrize("control", (controls.LaxVerifier, controls.AcceptAll))
def test_the_control_behind_the_sidecar_is_refused(control):
    line = window_over(control)["line"]
    assert not line["correct"]
    assert line["compared"]["mask_mismatches"]["value"] > 0, line["compared"]
    assert line["compared"]["forged_not_refused_at_validator0"]["value"] > 0


def test_a_program_without_per_link_delays_is_refused_before_anything_is_built(monkeypatch):
    from dag_rider_tpu.transport import net

    class UniformOnly:
        def __init__(self, seed=0, *, delay_ms=(0.0, 0.0), delay_rate=1.0, drop=0.0):
            pass

    monkeypatch.setattr(net, "WanFault", UniformOnly)
    driver = cells.load_driver(ROOT, "cluster")
    cell = small_cell()
    for make in (driver.build, lambda c, t, s: driver.control_stack(base.host_backend, c, t, s)):
        with pytest.raises(SystemExit) as refusal:
            make(cell["config"], cell["traffic"], base.SEED)
        assert refusal.value.code not in (0, None)
        assert "cannot delay each link" in str(refusal.value.code)
        assert "one_way_ms" in str(refusal.value.code)


# -- planted faults: check again on what the good window left --------------


def test_a_lost_acknowledged_transaction_is_refused(good):
    books = good["observed"]["books"]
    lost = next(tx for tx, b in books.items() if b[3])
    logs = [
        [{**rec, "tx": [t for t in rec["tx"] if t != lost]} for rec in log]
        for log in good["observed"]["logs"]
    ]
    compared = recheck(good, logs=logs)
    # the vertex that carried it no longer verifies either
    assert failing(compared) == {"tx_lost", "delivered_bad_signatures"}
    assert compared["tx_lost"]["value"] == 1


def test_an_acknowledgement_the_wal_does_not_hold_is_refused(good):
    books = good["observed"]["books"]
    tx, validator = next((tx, b[2]) for tx, b in books.items() if b[3])
    wals = [list(w) for w in good["observed"]["wals"]]
    wals[validator].remove(tx)
    compared = recheck(good, wals=wals)
    assert failing(compared) == {"acked_not_in_wal"}


def test_a_reordered_log_that_every_validator_agrees_on_is_refused(good):
    logs = [list(log) for log in good["observed"]["logs"]]
    shortest = min(len(log) for log in logs)
    assert shortest > 8
    at = shortest // 2
    for log in logs:
        log[at], log[at + 1] = log[at + 1], log[at]
    compared = recheck(good, logs=logs)
    assert failing(compared) == {"order_unexplained"}


def test_one_validator_that_orders_otherwise_is_refused(good):
    logs = [list(log) for log in good["observed"]["logs"]]
    longest = max(range(len(logs)), key=lambda i: len(logs[i]))
    other = logs[(longest + 1) % len(logs)]
    other.pop()  # never the one the order is explained from
    other[2], other[3] = other[3], other[2]
    compared = recheck(good, logs=logs)
    assert failing(compared) == {"views_diverged"}
    assert compared["views_diverged"]["value"] == 1


def test_a_vertex_delivered_twice_is_refused(good):
    logs = [list(log) for log in good["observed"]["logs"]]
    for log in logs:
        log.insert(5, log[4])
    compared = recheck(good, logs=logs)
    assert {"vertices_delivered_twice", "order_unexplained"} <= failing(compared)


def test_a_delivered_vertex_whose_signature_does_not_verify_is_refused(good):
    logs = [list(log) for log in good["observed"]["logs"]]
    longest = max(range(len(logs)), key=lambda i: len(logs[i]))
    rec = dict(logs[longest][7])
    rec["sig"] = rec["sig"][:-2] + ("00" if rec["sig"][-2:] != "00" else "01")
    logs[longest][7] = rec
    compared = recheck(good, logs=logs)
    assert failing(compared) == {"delivered_bad_signatures"}


def test_a_refusal_that_never_reached_the_sidecar_is_told_by_the_count(good):
    """A failed RPC refuses its batch at the validator; the sidecar's
    books show nothing."""
    counters = dict(good["observed"]["counters"])
    counters["sig_rejects"] = [counters["sig_rejects"][0] + 2] + counters["sig_rejects"][1:]
    assert failing(recheck(good, counters=counters)) == {"validator0_rejects_off_expected"}


def test_a_wrong_vertex_the_sidecar_never_saw_is_told(good):
    calls = [
        (v, m) for v, m in good["observed"]["verify_calls"] if all(m)
    ]
    compared = recheck(good, verify_calls=calls)
    assert failing(compared) == {"forged_not_refused_at_validator0"}
    assert compared["forged_not_refused_at_validator0"]["value"] == 6


# -- the references --------------------------------------------------------


def test_cluster_keys_are_the_cluster_dealers():
    from dag_rider_tpu.node import generate_keys

    blob = generate_keys(5, 2, seed="dagrider-cluster-77")
    mine = reference_cluster.ClusterKeys(5, 77)
    assert [pk.hex() for pk in mine.public] == blob["ed25519_public"]
    assert mine.verify(3, b"m", mine.sign(3, b"m")) and not mine.verify(2, b"m", mine.sign(3, b"m"))


def test_the_committee_is_the_configurations_whatever_the_seed():
    """Keys, coin and with them the leaders are the deployment's; the
    seed draws the traffic: what it does to the wrong vertices differs."""
    cell = small_cell()
    driver = cells.load_driver(ROOT, "cluster")
    stacks = [driver.Stack(cell["config"], cell["traffic"], seed) for seed in (1, base.SEED)]
    try:
        specs = [driver.lay_out(stack) for stack in stacks]
        files = []
        for spec in specs:
            with open(os.path.join(spec.root, "keys.json")) as fh:
                files.append(fh.read())
        assert files[0] == files[1]
        assert stacks[0].keys.public == stacks[1].keys.public
        assert stacks[0].keys.public == reference_cluster.ClusterKeys(
            4, cell["config"]["committee_seed"]
        ).public
        wans = []
        for spec in specs:
            with open(spec.nodes[0].config) as fh:
                wans.append(json.load(fh)["node"]["wan"]["seed"])
        assert wans == [1, base.SEED]
        assert [s.rng.random() for s in stacks][0] != [s.rng.random() for s in stacks][1]
    finally:
        for stack in stacks:
            driver.close(stack)


def test_both_new_cells_take_one_chip():
    for name in (WAN, CO1):
        (entry,) = [w for w in MANIFEST["workloads"] if w["name"] == name]
        assert entry["chips"] == 1


def test_the_wan_floor_is_three_hops_where_every_link_is_alike():
    # VAL at d, the third ECHO at 2d, the third READY at 3d; a round once
    # three vertices are delivered, the validator's own at once
    uniform = {"a": {"a": 40.0}}
    assert reference_cluster.wan_round_floor_ms(4, 1, ["a"] * 4, uniform) == pytest.approx(120.0)
    # one validator far from three near ones: the near ones never wait for it
    far = {"a": {"a": 10.0, "b": 500.0}, "b": {"b": 10.0}}
    assert reference_cluster.wan_round_floor_ms(
        4, 1, ["a", "a", "a", "b"], far
    ) == pytest.approx(30.0)
    config = cells.load_cell(ROOT, WAN)["config"]
    names = config["regions"]
    assert reference_cluster.wan_round_floor_ms(
        20, 6, [names[i % 5] for i in range(20)], config["one_way_delay_ms"]
    ) == pytest.approx(252.5)


def test_transaction_faults_by_hand():
    logs = [
        [{"tx": ["aa", "bb"]}, {"tx": ["cc"]}, {"tx": ["aa"]}],
        [{"tx": ["aa", "bb"]}],
    ]
    faults = reference_cluster.transaction_faults(
        {"aa": 0, "bb": 1, "dd": 1, "ee": 0}, [["aa", "ee"], ["bb"]], logs
    )
    assert faults == {"acked_not_in_wal": 1, "tx_lost": 2, "tx_delivered_twice": 1}


def test_bad_signatures_verifies_a_record_from_its_own_fields():
    keys = reference_cluster.ClusterKeys(4, 1)
    msg = reference.signing_bytes(3, 2, [b"t" * 512], [(2, 0), (2, 1), (2, 3)], [(1, 1)], b"\x07")
    rec = {"r": 3, "s": 2, "tx": [(b"t" * 512).hex()], "se": [[2, 0], [2, 1], [2, 3]],
           "we": [[1, 1]], "cs": "07", "sig": keys.sign(2, msg).hex()}
    assert reference_cluster.bad_signatures(keys, [rec]) == 0
    assert reference_cluster.bad_signatures(keys, [{**rec, "cs": ""}]) == 1
    assert reference_cluster.bad_signatures(keys, [{**rec, "s": 1}]) == 1
    assert reference_cluster.edges_of(rec) == [(2, 0), (2, 1), (2, 3), (1, 1)]


def test_a_torn_last_line_of_a_delivery_log_is_left_out(tmp_path):
    path = tmp_path / "delivery.jsonl"
    path.write_text('{"r": 1, "s": 0, "tx": []}\n{"ts": 5}\n{"r": 2, "s": 1, "tx": [')
    assert reference_cluster.read_delivery_log(str(path)) == [{"r": 1, "s": 0, "tx": []}]
    assert reference_cluster.read_delivery_log(str(tmp_path / "none")) == []


# -- the readers, on a hand-filled book ------------------------------------


def stat(count, total_ms, child_ms=0.0):
    return {"count": count, "total_ns": int(total_ms * MS), "max_ns": 0,
            "child_ns": int(child_ms * MS)}


#: validator 0 of a committee of 4 over 10 rounds, and the four books summed
BOOK0 = {
    "spans": {
        "node.tick": stat(5_000, 4_000, child_ms=3_500),
        "node.checkpoint": stat(100, 800),
        "net.broadcast": stat(300, 60),
        "net.send": stat(900, 180),
        "net.recv": stat(950, 95),
        "net.delay": stat(900, 900 * 41.5),
        "rbc.val": stat(30, 30, child_ms=10),
        "rbc.echo": stat(120, 24, child_ms=4),
        "rbc.ready": stat(120, 36, child_ms=6),
        "wal.append": stat(50, 5),
        "remote.verify": stat(25, 125),
    },
    "counts": {"pump.round_advance": 10, "pump.wave_commit": 11, "pump.wave_skip": 1,
               "mempool.cut_at_propose": 45, "mempool.cut_ahead": 15},
}
CLUSTER_BOOK = {"spans": {"net.send": stat(960, 700)},
                "counts": {"pump.round_advance": 40, "net.messages": 3_840}}
EXPECTED = {
    "node_tick_ms_per_round": 4_000 / 10,
    "checkpoint_ms_per_round": 800 / 10,
    "net_send_ms_per_round": (60 + 180) / 10,
    "net_recv_ms_per_round": 95 / 10,
    # validator 0 is in east: 31, 55 and 0.5 ms to its three peers
    "net_delay_lag_ms_per_message": 41.5 - (31 + 55 + 0.5) / 3,
    "net_messages_per_round": 3_840 / 10,
    "net_messages_per_rpc": 3_840 / 960,
    "rbc_ms_per_round": (20 + 20 + 30) / 10,
    "wal_append_ms_per_tx": 5 / 50,
    "remote_verify_ms_per_round": 125 / 10,
    "remote_rpcs_per_round": 25 / 10,
    "round_ms.wan": 51_000 / 120,
    "wan_floor_ms_per_round": 3 * 40.0,
    "commit_p95_ms.wan": 3_000.0,
    "mempool_cut_at_propose_pct.wan": 75.0,
    # one of validator 0's twelve waves went without a commit
    "waves_without_commit_pct.wan": 100.0 / 12,
    "device_idle_pct.wan": 75.0,
    "comb_program_us.wan": 2_500.0,
}


def obs_with(book0=BOOK0, cluster=CLUSTER_BOOK) -> dict:
    counters = {"rounds_advanced": 120, "window_s": 51.0, "bucket": 32}
    if book0 is not None:
        counters.update(validator0_book=book0, cluster_book=cluster)
    return {
        "samples": {"commit_latency_s": [1.0, 2.0, 3.0]},
        "counters": counters,
        "seconds": 51.0,
        "config": {"n": 4, "f": 1, "regions": ["east", "west", "north"],
                   "one_way_delay_ms": {"east": {"east": 0.5, "west": 31.0, "north": 55.0}}},
        "trace": {"programs": {"jit__device_verify_comb": [0.002, 0.003]},
                  "busy_s": 0.5, "window_s": 2.0},
        "device_kind": "TPU v5 lite",
    }


def test_the_manifest_has_the_new_cells_metrics_each_with_a_reader():
    """Every expected name is there; a later PR adds a metric for this
    cell as a reader file and a manifest entry, so the list is a floor."""
    rule.check_cell(WAN, per_layer=EXPECTED, end_to_end=["commit_p50_ms.wan"])
    for name in EXPECTED:
        m = rule.entry(name)
        assert m["moves"] == "commit_p50_ms.wan" and m["source"] != "program_span"
    for m in WAN_METRICS:
        assert os.path.exists(cells.reader_path(ROOT, m["name"]))
    assert "comb_roofline" not in {m["name"] for m in cells.load_cell(ROOT, WAN)["per_layer"]}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_works_its_number_out_of_a_hand_filled_book(name):
    obs = obs_with()
    if name == "wan_floor_ms_per_round":
        obs["config"] = {"n": 4, "f": 1, "regions": ["a"], "one_way_delay_ms": {"a": {"a": 40.0}}}
    assert READERS[name](obs) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize(
    "name", sorted(n for n in EXPECTED if n.split(".")[0] not in
                   ("round_ms", "wan_floor_ms_per_round", "commit_p95_ms",
                    "device_idle_pct", "comb_program_us", "mempool_cut_at_propose_pct"))
)
def test_reader_returns_nothing_where_the_run_left_no_book(name):
    """The parent's validators write no span book; a book with other
    names in it reads as nothing too. (The mempool's share then reads
    this process's book: ``test_mempool_cut_metric.py``.)"""
    assert READERS[name](obs_with(book0=None)) is None
    empty = {"spans": {}, "counts": {}}
    assert READERS[name](obs_with(book0=empty, cluster=empty)) is None
    assert validatorbook.open_book({"counters": {}}) is None


# -- sidecar256.colocated1 -------------------------------------------------


def test_colocated1_is_colocated4_with_one_client():
    one, four = cells.load_cell(ROOT, CO1), cells.load_cell(ROOT, "sidecar256.colocated4")
    assert one["config"] == four["config"]
    differ = {k for k in four["traffic"] if one["traffic"][k] != four["traffic"][k]}
    assert differ == {"clients", "why"} and one["traffic"]["clients"] == 1
    # every end-to-end metric of colocated4's is colocated1's too
    rule.check_cell(
        CO1, end_to_end={m["name"] for m in four["end_to_end"]},
        per_layer={"verify_rpc_p50_ms", "sidecar_gap_ms_per_rpc", "verify_batch_ms_per_rpc",
                   "device_idle_pct.verify", "comb_program_us", "comb_roofline"},
    )
    # the harness loads what the manifest lists for the cell
    assert {m["name"] for m in one["per_layer"]} == rule.owned_names(CO1)


def test_colocated1_window_on_the_host_verifier():
    cell = copy.deepcopy(cells.load_cell(ROOT, CO1))
    cell["config"].update(n=4, f=1)
    cell["traffic"].update(pool_rounds=4, wrong_per_round=2)
    line = base.bench.drive(
        cell, base.SEED, 1.0, 0, base.cpu_devices(), build=base.sidecar_over(base.host_backend)
    )
    base.check_line(line, cell, 0)
    assert line["correct"], line["compared"]
    rule.assert_floor({"verify_rpc_p95_ms", "verified_sigs_per_s", "setup_s"}, line["metrics"])
    assert set(line["metrics"]) == rule.owned_names(CO1, "end_to_end")
