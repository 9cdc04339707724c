"""mempool: from a block's earliest submit to the vertex that carries it
(``mempool.wait``, one closed span per non-empty block)."""

from benchmarks.harness import spanbook


def read(obs):
    book = spanbook.open_book(obs)
    if book is None:
        return None
    return spanbook.ratio(
        book.total_ns("mempool.wait"), book.count("mempool.wait"), 1e-6
    )
