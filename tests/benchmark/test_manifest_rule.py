"""The rule every test in this folder holds ``BENCHMARK.json`` by
(``manifest_rule.py``): its checks of every cell pass on a copy grown by
appends — a made-up per-layer entry at the end, a made-up cell at the end
of every ``workloads`` — and on that copy reordered, since no check may
hold an entry's position; no reader file in ``benchmarks/layer_metrics/``
is left out of the manifest; and no test here holds the manifest by
position, by count or by ``==`` on a ``workloads`` list.
"""

import copy
import glob
import importlib.util
import os
import re

import pytest

_spec = importlib.util.spec_from_file_location(
    "benchmark_manifest_rule", os.path.join(os.path.dirname(__file__), "manifest_rule.py")
)
rule = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(rule)

CELLS = rule.cell_names()
MADE_UP_CELL = "made_up.cell"
MADE_UP_METRIC = {
    "name": "made_up_ms_per_round", "unit": "ms", "better": "lower",
    "source": "program_counter", "layer": "host pump", "moves": "setup_s",
    "workloads": [MADE_UP_CELL],
}
#: readers that once stood without a manifest entry
ONCE_LEFT_OUT = ("program_loaded_pct", "sign_native_pct",
                 "sidecar_request_kib_per_rpc", "comb_tables_mib")


def grown(reorder: bool) -> dict:
    manifest = copy.deepcopy(rule.MANIFEST)
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if "workloads" in m:
                m["workloads"].append(MADE_UP_CELL)
    manifest["per_layer"].append(MADE_UP_METRIC)
    first = manifest["workloads"][0]
    manifest["workloads"].append({**first, "name": MADE_UP_CELL})
    if reorder:
        for group in ("workloads", "end_to_end", "per_layer"):
            manifest[group].reverse()
    return manifest


@pytest.mark.parametrize("reorder", (False, True), ids=("appended", "appended_and_reordered"))
@pytest.mark.parametrize("cell", CELLS)
def test_the_checks_of_a_cell_pass_on_a_manifest_grown_by_appends(cell, reorder):
    manifest = grown(reorder)
    per_layer = rule.owned_names(cell)
    end_to_end = rule.owned_names(cell, "end_to_end")
    rule.check_cell(cell, manifest, per_layer=per_layer, end_to_end=end_to_end)
    for group in ("end_to_end", "per_layer"):
        for m in rule.owned(cell, group):
            fields = {k: v for k, v in m.items() if k not in ("name", "workloads")}
            rule.assert_fields(m["name"], group, manifest, cells_=[cell], **fields)
    assert rule.owns(MADE_UP_CELL, rule.entry(MADE_UP_METRIC["name"], manifest=manifest))
    assert rule.orphan_readers(manifest) == set()


def test_no_reader_file_is_left_out_of_the_manifest():
    assert rule.orphan_readers() == set()
    # the manifest without the four entries appended for them: their
    # readers stood alone
    before = copy.deepcopy(rule.MANIFEST)
    before["per_layer"] = [m for m in before["per_layer"] if m["name"] not in ONCE_LEFT_OUT]
    assert rule.orphan_readers(before) == set(ONCE_LEFT_OUT)


#: how a test would hold the manifest by an entry's position, by a count
#: or by a whole ``workloads`` list
FORBIDDEN = {
    "an entry by index or slice": r'\[\s*"(?:per_layer|end_to_end|workloads|configs)"\s*\]\s*\[',
    "an entry's position": r'"(?:per_layer|end_to_end)"\s*\]\s*\]\s*\.index\(',
    "== on a workloads list": r'"workloads"\s*[\])]+\s*==',
    "a count of a workloads list": r'len\([^()\n]*"workloads"',
}


@pytest.mark.parametrize("what", sorted(FORBIDDEN))
def test_no_test_here_holds_the_manifest_by_position_count_or_list(what):
    here = os.path.dirname(os.path.abspath(__file__))
    pattern = re.compile(FORBIDDEN[what])
    found = []
    for path in sorted(glob.glob(os.path.join(here, "*.py"))):
        if os.path.basename(path) == os.path.basename(__file__):
            continue
        with open(path) as fh:
            for k, line in enumerate(fh, 1):
                if pattern.search(line):
                    found.append(f"{os.path.basename(path)}:{k}: {line.strip()}")
    assert not found, found
    # the pattern does find what it is for
    samples = {
        "an entry by index or slice": 'names = MANIFEST["per_layer"][-2:]',
        "an entry's position": 'assert [m["name"] for m in MANIFEST["per_layer"]].index(x) == 45',
        "== on a workloads list": 'assert m.get("workloads") == [CRASH]',
        "a count of a workloads list": 'assert len(m["workloads"]) == 1',
    }
    assert pattern.search(samples[what])
