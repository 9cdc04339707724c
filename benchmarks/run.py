"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, with no result line, when jax's platform is not ``tpu``
or fewer chips are there than the cell asks for. Otherwise: set-up
(imports, comb tables, the one program compiled or loaded from the
persistent cache, the stack, the traffic), the measured window, the
reference's comparison once the window has closed, and as the last line
of standard output one JSON object — ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, in a traced run, ``breakdown``;
then ``setup_parts`` and, last, ``compared``: each number compared
beside its limit. With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.time()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: a traced run traces this much of the end of its window
TRACE_FOR_S = 6.0


def note(msg: str) -> None:
    print(f"[bench +{time.time() - T_START:6.1f}s] {msg}", file=sys.stderr, flush=True)


def device_or_exit(chips: int):
    """jax's devices, or exit 2 before any work where they are not the
    TPU chips the cell asks for."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" or len(devices) < chips:
        print(
            f"benchmarks/run.py measures on the TPU only: jax reports "
            f"{len(devices)} x {platform!r}, the cell asks for {chips} x 'tpu'",
            file=sys.stderr,
        )
        raise SystemExit(2)
    return devices[:chips]


class CompileCounter:
    """Counts XLA compilations (jax's own duration events) while open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.open = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.open and event == self.EVENT:
            self.count += 1


def describe(observed: dict) -> None:
    """What the window saw, on standard error, for whoever reads a run
    that went wrong: the counters, each sample's distribution and, where
    the driver keeps one, the window's timeline."""
    from benchmarks.harness import stats

    note("counters " + json.dumps(observed["counters"]))
    for name, values in observed["samples"].items():
        note(
            f"{name}: n={len(values)} mean={stats.mean(values)} "
            + " ".join(f"p{q}={stats.percentile(values, q)}" for q in (50, 75, 90, 95, 99))
        )
    if "timeline" in observed:
        note("timeline " + json.dumps(observed["timeline"]))


def read_metrics(cell: dict, group: str, observed: dict, **more) -> dict:
    """Each metric of the cell's ``group`` (``end_to_end`` or
    ``per_layer``) through its reader; one that finds nothing to read is
    left out of the line."""
    from benchmarks.harness import cells

    metrics = cell[group]
    obs = {
        "samples": observed["samples"],
        "counters": observed["counters"],
        "seconds": observed["seconds"],
        "config": cell["config"],
        "traffic": cell["traffic"],
        **more,
    }
    readers = cells.load_readers(ROOT, metrics)
    out = {}
    for m in metrics:
        value = readers[m["name"]](obs)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def drive(cell: dict, seed: int, seconds: float, trace: int, devices, *, build=None) -> dict:
    """Everything of a run after the look for a chip: set-up, window,
    comparison, and the result line as a dict. ``build`` stands in for
    the driver's own (a test hands in a stack over the host verifier)."""
    from benchmarks.harness import cells
    from benchmarks.harness.trace import WindowTracer

    compiles = CompileCounter()
    driver = cells.load_driver(ROOT, cell["config"]["driver"])
    imports_s = time.time() - T_START
    note(f"{cell['name']} seed {seed}: building")
    stack = (build or driver.build)(cell["config"], cell["traffic"], seed)
    try:
        tracer = None
        if trace:
            tracer = WindowTracer(
                os.path.join(ROOT, ".bench_trace", f"{cell['name']}-{seed}"),
                seconds,
                TRACE_FOR_S,
            )
        note("window opens")
        compiles.open = True
        observed = driver.run_window(stack, seconds, tracer)
        compiles.open = False
        setup_s = observed["t_open"] - time.monotonic() + time.time() - T_START
        note("window and drain done")
        describe(observed)
        stats = [d.memory_stats() or {} for d in devices]
        compared = driver.check(stack, observed)
    finally:
        driver.close(stack)
    compared["compiles_in_window"] = {"value": compiles.count, "limit": 0}

    reduced = tracer.reduce() if tracer is not None else None
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0) for s in stats),
    }
    line = {
        "correct": all(v["value"] <= v["limit"] for v in compared.values()),
        "attempted": observed["attempted"],
        "failed": observed["failed"],
    }
    if trace:
        line["metrics"] = read_metrics(
            cell, "per_layer", observed, trace=reduced, device_kind=devices[0].device_kind
        )
    else:
        line["metrics"] = read_metrics(cell, "end_to_end", observed, setup_s=setup_s)
    line["device"] = device
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reduced["idle_gaps"],
        }
    line["setup_parts"] = {"setup_s": setup_s, "imports_s": imports_s, **stack.setup_parts}
    line["compared"] = compared
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from benchmarks.harness import cells

    cell = cells.load_cell(ROOT, args.workload)
    devices = device_or_exit(cell["chips"])
    try:
        import dag_rider_tpu  # noqa: F401
    except ImportError as e:
        print(f"the program is not in this checkout: {e}", file=sys.stderr)
        return 3
    line = drive(cell, args.seed, args.seconds, args.trace, devices)
    print("setup_parts " + json.dumps(line["setup_parts"]))
    print(json.dumps(line))
    sys.stdout.flush()
    for name, v in line["compared"].items():
        print(f"compared {name}: {v['value']} (limit {v['limit']})", file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
