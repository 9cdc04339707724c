"""host pump: the share of ``Simulation.run`` that no phase span covers —
self times of ``pump.run`` and ``pump.step`` over ``pump.run``'s total."""

from benchmarks.harness import spanbook


def read(obs):
    book = spanbook.open_book(obs)
    if book is None:
        return None
    return spanbook.ratio(
        book.self_ns("pump.run", "pump.step"), book.total_ns("pump.run"), 100.0
    )
