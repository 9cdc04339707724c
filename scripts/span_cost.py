"""What one ``obs.span`` costs on this host with no profiler session.

    python scripts/span_cost.py

Prints one JSON line, ns a pass (best of five loops): the two clock
reads alone, a bare context manager that keeps only its seconds (what a
``Timer`` cost), and ``obs.span`` — alone, and nested under an open
span, which adds the parent's ``child_ns``. jax's profiler is imported
first, as in a process that holds a chip, so that the span pays its
``is_enabled()``; no device is touched. PERF.md holds the reading from
the chip's host beside ISSUE 26's budget.
"""

from __future__ import annotations

import json
import os
import sys
from time import perf_counter_ns

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.profiler  # noqa: E402,F401

from dag_rider_tpu.obs import spans  # noqa: E402

N = 200_000


class Bare:
    def __enter__(self):
        self.t0 = perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.ns = perf_counter_ns() - self.t0


def clock_pair():
    for _ in range(N):
        perf_counter_ns() - perf_counter_ns()


def bare():
    for _ in range(N):
        with Bare():
            pass


def span():
    for _ in range(N):
        with spans.span("pump.insert"):
            pass


def nested():
    with spans.span("pump.step"):
        span()


def ns_a_pass(loop) -> float:
    best = None
    for _ in range(5):
        t0 = perf_counter_ns()
        loop()
        took = perf_counter_ns() - t0
        best = took if best is None else min(best, took)
    return round(best / N, 1)


if __name__ == "__main__":
    print(json.dumps({
        "clock_pair_ns": ns_a_pass(clock_pair),
        "bare_timer_ns": ns_a_pass(bare),
        "span_ns": ns_a_pass(span),
        "span_nested_ns": ns_a_pass(nested),
        "cores": os.cpu_count(),
    }))
