"""sidecar server: decoding a request's vertices (``sidecar.decode``)."""

from benchmarks.harness import spanbook


def read(obs):
    book = spanbook.open_book(obs)
    if book is None:
        return None
    return spanbook.ratio(
        book.self_ns("sidecar.decode"), book.count("sidecar.rpc"), 1e-6
    )
