"""certificate check: pairs the device's Miller program multiplied, per
served call — ``cert.pairs`` less those the host oracle took
(``cert.host_fallback``): ~2f+2 where every call is a fresh check of an
honest-sized certificate, 0 where the host checked them all or a memo
answered; nothing where no pair was counted."""

from benchmarks.harness import spanbook


def read(obs):
    book = spanbook.open_book(obs)
    if book is None:
        return None
    pairs = book.counts.get("cert.pairs")
    if pairs is None:
        return None
    device = pairs - book.counts.get("cert.host_fallback", 0)
    return spanbook.ratio(device, book.count("sidecar.cert_rpc"))
