"""consensus: catch-up requests (``pump.sync_request``) of all the
validators that left a book, per round the committee advanced. Under
reliable broadcast one request is answered with a window of vertices
re-broadcast to everybody, so in a committee that lost nobody's vertices
it should read 0: a request there is the silence rule misfiring. Nothing
from a program that does not count it."""

from benchmarks.harness import validatorbook
from benchmarks.harness.spanbook import ratio


def read(obs):
    book = validatorbook.open_book(obs, validatorbook.CLUSTER)
    if book is None:
        return None
    validators = obs["counters"][validatorbook.CLUSTER].get("validators")
    return ratio(book.counts.get("pump.sync_request"), validatorbook.rounds(book), validators)
