"""transport: frames handed to gRPC (the counter ``net.messages``) by all
validators together per round the committee advanced: 2n^2(n-1) where
reliable broadcast sends a VAL, an ECHO and a READY to every peer for
each of n vertices and nothing else is sent."""

from benchmarks.harness import validatorbook
from benchmarks.harness.spanbook import ratio


def read(obs):
    book = validatorbook.open_book(obs, validatorbook.CLUSTER)
    if book is None:
        return None
    return ratio(book.counts.get("net.messages"), validatorbook.rounds(book), obs["config"]["n"])
