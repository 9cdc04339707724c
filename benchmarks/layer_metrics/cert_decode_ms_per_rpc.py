"""sidecar server: decoding a VerifyCerts request's certificates
(``sidecar.cert_decode``), per served call (``sidecar.cert_rpc``)."""

from benchmarks.harness import spanbook


def read(obs):
    book = spanbook.open_book(obs)
    if book is None:
        return None
    return spanbook.ratio(
        book.self_ns("sidecar.cert_decode"), book.count("sidecar.cert_rpc"), 1e-6
    )
