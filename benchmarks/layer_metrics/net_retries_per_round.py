"""transport: failed attempts validator 0 put back on its delay heap for
another try (``net.retry``), per round it advanced. 0 while every peer
answers; a dead peer that the failure detector does not shield costs two
a frame. Nothing from a program that does not count it."""

from benchmarks.harness import validatorbook
from benchmarks.harness.spanbook import ratio


def read(obs):
    book = validatorbook.open_book(obs)
    if book is None:
        return None
    return ratio(book.counts.get("net.retry"), validatorbook.rounds(book))
