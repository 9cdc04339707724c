"""transport: how often validator 0's failure detector reported a peer
down (``net.peer_down``: the third message in a row whose every attempt
failed): the crashed validators once each where the detector trips and
nobody flaps. Nothing from a program that does not count it."""

from benchmarks.harness import validatorbook


def read(obs):
    book = validatorbook.open_book(obs)
    return None if book is None else book.counts.get("net.peer_down")
