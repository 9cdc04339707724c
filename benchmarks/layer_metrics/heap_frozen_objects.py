"""host runtime: the objects the program took out of the collector's
reach once its one program was compiled (its counter
``heap.frozen_objects``); nothing where it never did."""

from benchmarks.harness import spanbook


def read(obs):
    book = spanbook.open_book(obs)
    if book is None:
        return None
    return book.counts.get("heap.frozen_objects")
