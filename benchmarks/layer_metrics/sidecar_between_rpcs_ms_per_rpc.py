"""sidecar server: from one handler call's return to the next one's entry
on the single worker thread — gRPC's share — as the mean."""

from benchmarks.harness import spanbook


def read(obs):
    book = spanbook.open_book(obs)
    if book is None:
        return None
    return spanbook.ratio(
        book.total_ns("sidecar.between_rpcs"),
        book.count("sidecar.between_rpcs"),
        1e-6,
    )
