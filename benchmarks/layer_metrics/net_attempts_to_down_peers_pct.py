"""transport: of the frames validator 0 handed to its senders
(``net.messages``), the share for a peer its failure detector held down
at that moment (``net.to_down_peer``): work for a socket nobody listens
on. 0 while every peer is up; where a down peer costs a probe now and
then, near 0; f in n-1 where every frame for it still takes a sender's
turn. Nothing from a program that does not count it."""

from benchmarks.harness import validatorbook
from benchmarks.harness.spanbook import ratio


def read(obs):
    book = validatorbook.open_book(obs)
    if book is None:
        return None
    return ratio(book.counts.get("net.to_down_peer"), book.counts.get("net.messages"), 100.0)
