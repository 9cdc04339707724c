"""certificate check: the final exponentiation on the host
(``cert.final_exp``), per served call."""

from benchmarks.harness import spanbook


def read(obs):
    book = spanbook.open_book(obs)
    if book is None:
        return None
    return spanbook.ratio(
        book.total_ns("cert.final_exp"), book.count("sidecar.cert_rpc"), 1e-6
    )
