"""Reading the program's span book (``dag_rider_tpu.obs.spans``): the
totals of every span and counter the process has closed since it
started — the warm rounds, the window and the drain — not only what the
profiler's few traced seconds caught, which are shorter than a wave and
would depend on which rounds they held.

The window's own share would be the difference of two snapshots taken
at its edges; only a driver can take those (``run_window``), and the
drivers are not this PR's to edit, so a life it is (PERF.md §7).

A reader gets ``None`` for everything from a program that has no such
module, for a name nothing ever recorded, and where ``obs`` holds no
reduced trace: ``run.py`` reads the per-layer metrics in traced runs
only, so an ``obs`` without one is not a run's, and the book — which is
process-wide — may hold another test's simulation.
"""

from __future__ import annotations

from typing import Optional


class Book:
    def __init__(self, snapshot: dict):
        self.spans = snapshot["spans"]
        self.counts = snapshot["counts"]

    def _sum(self, names, of) -> Optional[float]:
        """``of(stat)`` over the names that were recorded; None if none
        of them was."""
        stats = [self.spans[n] for n in names if n in self.spans]
        return float(sum(of(s) for s in stats)) if stats else None

    def total_ns(self, *names: str) -> Optional[float]:
        return self._sum(names, lambda s: s["total_ns"])

    def self_ns(self, *names: str) -> Optional[float]:
        """Time inside these spans that no span opened inside them (on
        the same thread) covered."""
        return self._sum(names, lambda s: s["total_ns"] - s["child_ns"])

    def count(self, name: str) -> Optional[int]:
        return self.spans[name]["count"] if name in self.spans else None

    def max_ns(self, name: str) -> Optional[int]:
        return self.spans[name]["max_ns"] if name in self.spans else None


def open_book(obs: dict) -> Optional[Book]:
    if obs.get("trace") is None:
        return None
    try:
        from dag_rider_tpu.obs import spans
    except ImportError:  # a program from before the span primitive
        return None
    return Book(spans.snapshot())


def ratio(num: Optional[float], den: Optional[float], scale: float = 1.0):
    """``scale * num / den``, or None where either is missing or the
    denominator is zero."""
    if num is None or not den:
        return None
    return scale * num / den


def rounds(book: Book, obs: dict) -> Optional[float]:
    """DAG rounds the committee advanced, by the program's own counter
    (one bump per validator per round)."""
    return ratio(book.counts.get("pump.round_advance"), obs["config"]["n"])


def self_ms_per_round(obs: dict, *names: str) -> Optional[float]:
    book = open_book(obs)
    if book is None:
        return None
    return ratio(book.self_ns(*names), rounds(book, obs), 1e-6)


def gc_pct(obs: dict, *over: str) -> Optional[float]:
    """The collector's full collections as a share of the time of the
    spans ``over``; 0 where those ran and no full collection did."""
    book = open_book(obs)
    if book is None:
        return None
    return ratio(book.total_ns("host.gc") or 0.0, book.total_ns(*over), 100.0)
