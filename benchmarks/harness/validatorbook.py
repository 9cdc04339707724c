"""Reading the span books that validators leave beside their ``final``
files (``cluster/runner.py`` writes its process's
``obs.spans.snapshot()`` there when it stops): a validator's book is its
process's and has to leave it to be read. The ``cluster`` driver hands
validator 0's on as ``counters["validator0_book"]`` — the validator
whose path is the deployment's, its verifier behind a sidecar — and the
sum of all of them as ``counters["cluster_book"]``.

A book covers its process's life: boot, the warm rounds, the window and
the drain. A reader gets ``None`` where the run left no such book (the
parent's program writes none) or the book has no such name.
"""

from __future__ import annotations

from typing import Optional

from benchmarks.harness.spanbook import Book, ratio

VALIDATOR0 = "validator0_book"
CLUSTER = "cluster_book"


def open_book(obs: dict, which: str = VALIDATOR0) -> Optional[Book]:
    snapshot = obs.get("counters", {}).get(which)
    return Book(snapshot) if snapshot else None


def rounds(book: Book) -> Optional[int]:
    """DAG rounds advanced, by the program's own counter: one bump a
    validator a round, so the cluster's book counts each round n times."""
    return book.counts.get("pump.round_advance")


def self_ms_per_round(obs: dict, *names: str) -> Optional[float]:
    """Validator 0's time inside these spans that no span opened inside
    them covered, per round it advanced."""
    book = open_book(obs)
    if book is None:
        return None
    return ratio(book.self_ns(*names), rounds(book), 1e-6)


def total_ms_per_round(obs: dict, *names: str) -> Optional[float]:
    book = open_book(obs)
    if book is None:
        return None
    return ratio(book.total_ns(*names), rounds(book), 1e-6)


def count_per_round(obs: dict, name: str) -> Optional[float]:
    """How often validator 0 closed span ``name`` per round it advanced."""
    book = open_book(obs)
    if book is None:
        return None
    return ratio(book.count(name), rounds(book))
