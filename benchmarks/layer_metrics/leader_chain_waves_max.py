"""consensus: the longest run of waves one commit of validator 0's
closed — the committing wave and every undecided wave before it, whose
leaders the retroactive chain walks (``pump.chain_waves``: a length, not
a time, booked once a commit; this is its largest). 1 where every wave
commits at its own last round; k where k-1 waves in a row had waited."""

from benchmarks.harness import validatorbook


def read(obs):
    book = validatorbook.open_book(obs)
    return None if book is None else book.max_ns("pump.chain_waves")
