"""certificate check: the digests' H(d) (``cert.hash``: host SHA, the
device's square roots and cofactor ladder), per served call."""

from benchmarks.harness import spanbook


def read(obs):
    book = spanbook.open_book(obs)
    if book is None:
        return None
    return spanbook.ratio(book.total_ns("cert.hash"), book.count("sidecar.cert_rpc"), 1e-6)
