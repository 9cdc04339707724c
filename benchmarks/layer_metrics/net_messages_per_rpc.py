"""transport: frames a network attempt carries (the counter
``net.messages`` over the ``net.send`` spans, all validators together):
1 where every frame's delay ends alone, more where the delay thread was
late and the frames for a peer that fell due together share one RPC —
how loaded the hosts are, and what a frame's RPC costs less for it."""

from benchmarks.harness import validatorbook
from benchmarks.harness.spanbook import ratio


def read(obs):
    book = validatorbook.open_book(obs, validatorbook.CLUSTER)
    if book is None:
        return None
    return ratio(book.counts.get("net.messages"), book.count("net.send"))
