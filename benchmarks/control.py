"""The control of a cell, at the cell's own size: the same run as
``run.py`` makes, with :class:`controls.LaxVerifier` in the device
verifier's place. It has to come out as NOT correct.

    python3 benchmarks/control.py --workload <name> --seed <n> --seconds <s>

Prints the numbers compared and exits 0 when the control was refused,
1 when it passed as correct. Not part of a measured run.
"""

from __future__ import annotations

import argparse
import json
import sys

import run as bench  # noqa: E402  (this directory is sys.path[0])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from benchmarks.harness import cells, controls

    cell = cells.load_cell(bench.ROOT, args.workload)
    devices = bench.device_or_exit(cell["chips"])
    driver = cells.load_driver(bench.ROOT, cell["config"]["driver"])

    def build(config, traffic, seed):
        return driver.control_stack(controls.LaxVerifier, config, traffic, seed)

    line = bench.drive(cell, args.seed, args.seconds, 0, devices, build=build)
    print(json.dumps({"control_correct": line["correct"], "compared": line["compared"]}))
    return 1 if line["correct"] else 0


if __name__ == "__main__":
    raise SystemExit(main())
