"""verify seam: host prep of a dispatch (``verify_batch.prepare``) per
RPC served."""

from benchmarks.harness import spanbook


def read(obs):
    book = spanbook.open_book(obs)
    if book is None:
        return None
    return spanbook.ratio(
        book.self_ns("verify_batch.prepare"), book.count("sidecar.rpc"), 1e-6
    )
