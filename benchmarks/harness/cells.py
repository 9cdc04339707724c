"""Finding a cell's files by the names in ``BENCHMARK.json``: the
configuration, the traffic mix, the driver the configuration names and
the reader of each per-layer metric. A later PR adds a cell, a
configuration or a metric by adding files and manifest entries; nothing
here lists them."""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List

TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


def load_manifest(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def traffic_path(root: str, mix: str) -> str:
    for suffix in TRAFFIC_SUFFIXES:
        path = os.path.join(root, "benchmarks", "traffic", mix + suffix)
        if os.path.exists(path):
            return path
    raise FileNotFoundError(f"no traffic file for mix {mix!r}")


def reader_path(root: str, metric: str) -> str:
    """The metric's own reader; a quantity split by what its cells report
    (``device_idle_pct.commit``, ``device_idle_pct.verify``) is read by
    the quantity's (``device_idle_pct.py``) unless a split has its own."""
    readers = os.path.join(root, "benchmarks", "layer_metrics")
    own = os.path.join(readers, metric + ".py")
    if "." in metric and not os.path.exists(own):
        return os.path.join(readers, metric.split(".", 1)[0] + ".py")
    return own


def driver_path(root: str, driver: str) -> str:
    return os.path.join(root, "benchmarks", "drivers", driver + ".py")


def load_cell(root: str, name: str) -> dict:
    """Everything one run of cell ``name`` needs, as plain data."""
    manifest = load_manifest(root)
    entries = [w for w in manifest["workloads"] if w["name"] == name]
    if not entries:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (has: {known})")
    entry = entries[0]
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    tpath = traffic_path(root, entry["traffic"])
    if not tpath.endswith(".json"):
        raise ValueError(f"{tpath}: this harness reads .json traffic files")

    def here(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return {
        "name": name,
        "chips": entry["chips"],
        "config": config,
        "traffic": _load_json(tpath),
        "end_to_end": [m for m in manifest["end_to_end"] if here(m)],
        "per_layer": [m for m in manifest["per_layer"] if here(m)],
    }


def load_driver(root: str, driver: str):
    return _module(driver_path(root, driver), f"benchmarks_driver_{driver}")


def load_readers(root: str, metrics: List[dict]) -> Dict[str, Callable]:
    """metric name -> its ``read(obs)``, end-to-end and per-layer alike;
    ``obs`` is what the driver and the trace reduction observed, and a
    reader that finds nothing to read returns None."""
    out = {}
    for m in metrics:
        mod = _module(
            reader_path(root, m["name"]),
            "benchmarks_reader_" + m["name"].replace(".", "_").replace("-", "_"),
        )
        out[m["name"]] = mod.read
    return out
