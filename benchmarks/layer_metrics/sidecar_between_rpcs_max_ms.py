"""sidecar server: the longest ``sidecar.between_rpcs``."""

from benchmarks.harness import spanbook


def read(obs):
    book = spanbook.open_book(obs)
    if book is None:
        return None
    return spanbook.ratio(book.max_ns("sidecar.between_rpcs"), 1, 1e-6)
