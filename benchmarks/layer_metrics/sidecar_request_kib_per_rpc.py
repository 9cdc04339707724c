"""sidecar server: the mean request the handler took
(``sidecar.request_bytes`` over the count of ``sidecar.rpc``), in KiB: a
whole round an RPC, so the round's size on the wire. Nothing from a
program that does not count its requests' bytes."""

from benchmarks.harness import spanbook


def read(obs):
    book = spanbook.open_book(obs)
    if book is None:
        return None
    return spanbook.ratio(
        book.counts.get("sidecar.request_bytes"), book.count("sidecar.rpc"), 1 / 1024
    )
