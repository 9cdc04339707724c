"""The ``sidecar256-certagg.colocated4-certs`` cell on the CPU at n=4:
the manifest's entries for it, the plain reference held to the program's
keys and hash and refusing every wrong kind, its eight readers'
arithmetic on a hand-filled book, the driver's window over the host
oracle, and the controls `correct` has to refuse."""

import copy
import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import bytecount_bls, cells, reference_bls  # noqa: E402


def _sibling(stem):
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{stem}", os.path.join(os.path.dirname(__file__), f"{stem}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


base = _sibling("test_cells")
rule = _sibling("manifest_rule")

CELL = "sidecar256-certagg.colocated4-certs"
SEED = 2**31 + 41
NEW = {
    "cert_decode_ms_per_rpc": ("ms", "lower", "program_span", "sidecar server"),
    "cert_hash_ms_per_rpc": ("ms", "lower", "program_span", "certificate check"),
    "cert_miller_ms_per_rpc": ("ms", "lower", "program_span", "certificate check"),
    "cert_final_exp_ms_per_rpc": ("ms", "lower", "program_span", "certificate check"),
    "cert_pairs_per_rpc": ("pairs", "higher", "program_counter", "certificate check"),
    "cert_line_tables_mib": ("MiB", "lower", "program_counter", "kernels"),
    "cert_miller_us": ("us", "lower", "device_trace", "kernels"),
    "cert_miller_roofline": ("%", "higher", "device_trace", "kernels"),
}
READERS = cells.load_readers(ROOT, [{"name": n} for n in NEW])


def small_cell() -> dict:
    cell = copy.deepcopy(cells.load_cell(ROOT, CELL))
    cell["config"].update(n=4, f=1)
    cell["traffic"].update(clients=2, pool_rounds=8)
    return cell


# -- the manifest --------------------------------------------------------------


def test_the_cell_reports_its_metrics_and_joins_the_sidecars_by_appends():
    rule.check_cell(
        CELL,
        per_layer=set(NEW) | {"verify_rpc_p50_ms", "device_idle_pct.verify"},
        end_to_end={"verify_rpc_p95_ms", "verified_sigs_per_s", "setup_s"},
    )
    for name, (unit, better, source, layer) in NEW.items():
        rule.assert_fields(
            name, cells_=[CELL], unit=unit, better=better, source=source, layer=layer,
            moves="verified_sigs_per_s",
        )
    for name in ("verify_rpc_p95_ms", "verified_sigs_per_s"):
        assert rule.owns("sidecar256.colocated4", rule.entry(name, "end_to_end"))
    cfg = rule.entry("sidecar256-certagg", "configs")
    assert cfg["reduced"] == ["validators_consensus"]
    cell = cells.load_cell(ROOT, CELL)
    assert cell["config"]["driver"] == "sidecar_cert" and cell["chips"] == 1
    assert (cell["config"]["n"], cell["config"]["f"]) == (256, 85)
    assert cell["traffic"]["pool_rounds"] == 24
    assert sorted(cell["traffic"]["wrong_kinds"]) == sorted(reference_bls.KINDS)


# -- the reference ---------------------------------------------------------------


def test_the_references_keys_and_hash_are_the_programs():
    from dag_rider_tpu.crypto import bls12381 as bls
    from dag_rider_tpu.verifier.base import KeyRegistry

    assert reference_bls.Keys(8).secret == KeyRegistry.generate_with_cert(8)[2]
    for msg in (b"", b"\x00" * 32, bytes(range(32))):
        assert reference_bls.hash_to_g1(msg) == bls.hash_to_g1(msg)
        p = reference_bls.hash_to_g1(msg)
        assert reference_bls.compress(p) == bls.g1_compress(p)
        assert reference_bls.decompress(reference_bls.compress(p)) == p


@pytest.mark.parametrize("kind", ("",) + reference_bls.KINDS)
def test_the_reference_refuses_each_wrong_kind_and_accepts_the_honest(kind):
    keys = reference_bls.Keys(4)
    pool = reference_bls.make_pool(keys, 8, reference_bls.KINDS, SEED)
    (cert,) = [c for c in pool if c.wrong == kind][:1] or [None]
    assert cert is not None
    assert reference_bls.verdict(keys, cert) is (kind == "")


def test_the_pool_is_the_seeds_and_has_one_of_each_kind():
    keys = reference_bls.Keys(4)
    pool = reference_bls.make_pool(keys, 8, reference_bls.KINDS, SEED)
    assert pool == reference_bls.make_pool(keys, 8, reference_bls.KINDS, SEED)
    assert sorted(c.wrong for c in pool if c.wrong) == sorted(reference_bls.KINDS)
    assert [c.rnd for c in pool] == list(range(1, 9))
    other = reference_bls.make_pool(keys, 8, reference_bls.KINDS, SEED + 1)
    assert [c.digests for c in other] != [c.digests for c in pool]
    with pytest.raises(ValueError):
        reference_bls.make_pool(keys, 4, reference_bls.KINDS, SEED)


def test_the_aggregate_outside_g1_is_on_the_curve_and_only_the_subgroup_check_refuses_it():
    keys = reference_bls.Keys(4)
    pool = reference_bls.make_pool(keys, 8, ["not_in_g1"], SEED)
    (bad,) = [c for c in pool if c.wrong]
    data = bytearray(bad.agg)
    x = int.from_bytes(bytes([data[0] & 0x1F]) + bytes(data[1:]), "big")
    y = pow((x**3 + 4) % reference_bls.P, (reference_bls.P + 1) // 4, reference_bls.P)
    assert reference_bls.on_curve((x, y)) and not reference_bls.in_subgroup((x, y))
    assert reference_bls.decompress(bad.agg) is None


# -- the readers -----------------------------------------------------------------

MS = 1_000_000
BOOK = {
    "spans": {
        "sidecar.cert_rpc": {"count": 10, "total_ns": 2_000 * MS, "max_ns": 0, "child_ns": 1_900 * MS},
        "sidecar.cert_decode": {"count": 10, "total_ns": 30 * MS, "max_ns": 0, "child_ns": 0},
        "cert.hash": {"count": 9, "total_ns": 450 * MS, "max_ns": 0, "child_ns": 0},
        "cert.miller": {"count": 9, "total_ns": 1_100 * MS, "max_ns": 0, "child_ns": 0},
        "cert.final_exp": {"count": 9, "total_ns": 220 * MS, "max_ns": 0, "child_ns": 0},
    },
    "counts": {"cert.pairs": 9 * 172, "cert.host_fallback": 172, "cert.table_bytes": 55_363_968},
}
TRACE = {"programs": {"jit__cert_miller": [0.1, 0.12], "jit_run": [0.5]},
         "busy_s": 1.0, "window_s": 6.0}
EXPECTED = {
    "cert_decode_ms_per_rpc": 3.0,
    "cert_hash_ms_per_rpc": 45.0,
    "cert_miller_ms_per_rpc": 110.0,
    "cert_final_exp_ms_per_rpc": 22.0,
    "cert_pairs_per_rpc": 8 * 172 / 10,
    "cert_line_tables_mib": 55_363_968 / 2**20,
    "cert_miller_us": 110_000.0,
    "cert_miller_roofline": 100 * bytecount_bls.miller_bytes(257) / 819e9 / 0.11,
}


def obs_with(trace, counters=None):
    return {"samples": {}, "counters": counters or {"pair_rows": 257}, "seconds": 51.0,
            "trace": trace, "device_kind": "TPU v5 lite", "config": {"n": 256}}


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_works_its_number_out_of_a_hand_filled_book_and_trace(name, monkeypatch):
    from dag_rider_tpu.obs import spans

    monkeypatch.setattr(spans, "snapshot", lambda: BOOK)
    assert READERS[name](obs_with(TRACE)) == pytest.approx(EXPECTED[name])
    assert READERS[name](obs_with(None)) is None
    monkeypatch.setattr(spans, "snapshot", lambda: {"spans": {}, "counts": {}})
    assert READERS[name](obs_with({"programs": {}, "busy_s": 0, "window_s": 1})) is None


def test_the_miller_bytes_by_the_formula():
    assert bytecount_bls.miller_bytes(1) == 68 * 2 * 12 * 33 * 4 + 2 * 33 * 4 + 5
    assert bytecount_bls.miller_bytes(257) == 257 * bytecount_bls.miller_bytes(1)
    # the gathered rows of 257 pairs are the tables' size
    assert 257 * 68 * 2 * 12 * 33 * 4 == 55_363_968


# -- the window, at n=4 ------------------------------------------------------------


def over(make_backend):
    def build(config, traffic, seed):
        driver = cells.load_driver(ROOT, "sidecar_cert")
        return driver.control_stack(make_backend, config, traffic, seed)

    return build


def host_oracle(registry, quorum):
    from dag_rider_tpu.verifier.cert import CertBackend

    return CertBackend(registry, quorum, pair="host")


@pytest.mark.parametrize("trace_on", (0, 1))
def test_cert_window_on_the_host_oracle(trace_on):
    cell = small_cell()
    line = base.bench.drive(cell, SEED, 2.0, trace_on, base.cpu_devices(), build=over(host_oracle))
    base.check_line(line, cell, trace_on)
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] >= 4
    assert set(line["compared"]) == {
        "verdict_mismatches", "rpcs_failed", "clients_with_libtpu", "compiles_in_window",
    }


def test_the_cells_book_metrics_have_values_after_a_window_at_n4():
    cell = small_cell()
    line = base.bench.drive(cell, SEED, 1.5, 0, base.cpu_devices(), build=over(host_oracle))
    assert line["correct"], line["compared"]
    observed = {"samples": {}, "counters": {}, "seconds": 1.5}
    metrics = base.bench.read_metrics(
        cell, "per_layer", observed, trace={"programs": {}, "busy_s": 0.1, "window_s": 1.5},
        device_kind="TPU v5 lite",
    )
    assert metrics["cert_decode_ms_per_rpc"]["value"] > 0
    # the host oracle takes every pair: none went through the device program
    assert metrics["cert_pairs_per_rpc"]["value"] == 0


class QuorumBlind:
    """The host oracle without the quorum rule."""

    def __init__(self, registry, quorum):
        self.inner = host_oracle(registry, 1)
        self.registry = registry

    def verify_certs(self, certs):
        return self.inner.verify_certs(certs)


class DigestBlind:
    """Structure and the aggregate's decompression, and no product check:
    the digests never reach the verdict."""

    def __init__(self, registry, quorum):
        self.registry, self.quorum = registry, quorum

    def verify_certs(self, certs):
        from dag_rider_tpu.verifier.cert import decompress_aggregate, structurally_valid

        return [
            structurally_valid(c, self.quorum, self.registry.n)
            and decompress_aggregate(c.agg_sig) is not None
            for c in certs
        ]


@pytest.mark.parametrize("control", (QuorumBlind, DigestBlind))
def test_the_controls_come_out_as_not_correct(control):
    line = base.bench.drive(small_cell(), SEED, 2.0, 0, base.cpu_devices(), build=over(control))
    assert not line["correct"]
    assert line["compared"]["verdict_mismatches"]["value"] > 0, line["compared"]


def test_a_verdict_flipped_where_it_is_produced_flips_correct():
    from dag_rider_tpu.verifier.cert import CertBackend

    class Flipping(CertBackend):
        calls = 0

        def verify_certs(self, certs):
            out = super().verify_certs(certs)
            self.calls += 1
            if self.calls % 3 == 0:
                out[0] = not out[0]
            return out

    build = over(lambda reg, q: Flipping(reg, q, pair="host"))
    line = base.bench.drive(small_cell(), SEED, 2.0, 0, base.cpu_devices(), build=build)
    assert not line["correct"] and line["compared"]["verdict_mismatches"]["value"] > 0


@pytest.mark.slow
def test_cert_window_on_the_device_lane():
    from dag_rider_tpu.verifier.cert import CertBackend

    cell = small_cell()
    build = over(lambda reg, q: CertBackend(reg, q, pair="device"))
    line = base.bench.drive(cell, SEED, 3.0, 1, base.cpu_devices(), build=build)
    base.check_line(line, cell, 1)
    assert line["correct"], line["compared"]
