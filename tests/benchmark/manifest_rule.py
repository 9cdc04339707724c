"""How every test in this folder holds ``BENCHMARK.json``: entries are
appended, never moved, so a test holds nothing that an append can break.

- An entry is found by its name (:func:`entry`), never by its index or
  by a slice of ``per_layer``.
- A cell owns a metric when the cell's name is in the metric's
  ``workloads`` (:func:`owns`; no ``workloads`` key: every cell), never
  when the list equals one, and never by the list's length.
- A set of names a test expects is a floor (:func:`assert_floor`,
  ``expected <= listed``), never an exact count or an exact list.

An entry's own fields (unit, better, source, layer, moves) may be held
exactly (:func:`assert_fields`); its ``workloads`` only by membership.
Where an entry stands is no test's to hold. Every function takes
the manifest to hold (``BENCHMARK.json`` by default), so that
``test_manifest_rule.py`` can apply the same checks to a grown copy.
"""

from __future__ import annotations

import os
import sys
from typing import Iterable, List, Optional, Set

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import cells  # noqa: E402

MANIFEST = cells.load_manifest(ROOT)


def _of(manifest: Optional[dict]) -> dict:
    return MANIFEST if manifest is None else manifest


def cell_names(manifest: Optional[dict] = None) -> List[str]:
    return [w["name"] for w in _of(manifest)["workloads"]]


def entry(name: str, group: str = "per_layer", manifest: Optional[dict] = None) -> dict:
    """The one entry of ``group`` called ``name``."""
    found = [m for m in _of(manifest)[group] if m["name"] == name]
    assert len(found) == 1, f"{len(found)} entries called {name!r} in {group}"
    return found[0]


def owns(cell: str, metric: dict, manifest: Optional[dict] = None) -> bool:
    return cell in metric.get("workloads", cell_names(manifest))


def owned(cell: str, group: str = "per_layer", manifest: Optional[dict] = None) -> List[dict]:
    """The entries of ``group`` that cell ``cell`` reports."""
    manifest = _of(manifest)
    return [m for m in manifest[group] if owns(cell, m, manifest)]


def owned_names(cell: str, group: str = "per_layer", manifest: Optional[dict] = None) -> Set[str]:
    return {m["name"] for m in owned(cell, group, manifest)}


def assert_floor(expected: Iterable[str], listed: Iterable[str], what: str = "") -> None:
    missing = set(expected) - set(listed)
    assert not missing, f"{what}: {sorted(missing)} not listed"


def assert_fields(
    name: str, group: str = "per_layer", manifest: Optional[dict] = None,
    cells_: Iterable[str] = (), **fields,
) -> dict:
    """Entry ``name`` has exactly these ``fields`` and is reported in
    each of ``cells_`` (and maybe in cells a later PR appended)."""
    m = entry(name, group, manifest)
    for key, want in fields.items():
        assert m[key] == want, (name, key, m[key], want)
    for cell in cells_:
        assert owns(cell, m, manifest), (name, cell)
    return m


def check_cell(
    cell: str, manifest: Optional[dict] = None,
    per_layer: Iterable[str] = (), end_to_end: Iterable[str] = (),
) -> None:
    """What a test may hold of cell ``cell``: the per-layer and
    end-to-end names it expects are listed for it; it reports
    ``setup_s``, another end-to-end metric and a per-layer one; every
    per-layer metric it owns moves an end-to-end metric it reports."""
    manifest = _of(manifest)
    mine = owned_names(cell, "per_layer", manifest)
    reported = owned_names(cell, "end_to_end", manifest)
    assert_floor(per_layer, mine, f"{cell} per_layer")
    assert_floor(end_to_end, reported, f"{cell} end_to_end")
    assert "setup_s" in reported and len(reported) >= 2, (cell, reported)
    assert mine, cell
    for m in owned(cell, "per_layer", manifest):
        assert m["moves"] in reported, (cell, m["name"], m["moves"])


def orphan_readers(manifest: Optional[dict] = None, root: str = ROOT) -> Set[str]:
    """Reader files in ``benchmarks/layer_metrics/`` that are neither a
    listed metric's own reader nor the quantity's reader of a listed
    dotted name (``cells.reader_path``)."""
    manifest = _of(manifest)
    folder = os.path.join(root, "benchmarks", "layer_metrics")
    files = {f for f in os.listdir(folder) if f.endswith(".py")}
    used = {
        os.path.basename(cells.reader_path(root, m["name"]))
        for group in ("end_to_end", "per_layer") for m in manifest[group]
    }
    return {f[: -len(".py")] for f in files - used}
