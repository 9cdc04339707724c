"""certificate check: the Miller program from its dispatch to the
product on the host (``cert.miller``), per served call."""

from benchmarks.harness import spanbook


def read(obs):
    book = spanbook.open_book(obs)
    if book is None:
        return None
    return spanbook.ratio(book.total_ns("cert.miller"), book.count("sidecar.cert_rpc"), 1e-6)
