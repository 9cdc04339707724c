"""verify seam: batches handed to the device a round — how often the
span ``verify_batch.dispatch`` closed, over the rounds the committee
advanced. About 2 where the views' batches merge into the shared
verifier's one dispatch (the round, and the small one of the cycle's
spare messages); n and more where every view verifies its own."""

from benchmarks.harness import spanbook

DISPATCH = "verify_batch.dispatch"


def read(obs):
    book = spanbook.open_book(obs)
    if book is None:
        return None
    return spanbook.ratio(book.count(DISPATCH), spanbook.rounds(book, obs))
