"""consensus: of the waves validator 0 tried at their last round, the
share that committed nothing (``pump.wave_skip``: the coin named a
leader whose vertex is not there, or fewer than 2f+1 vertices of the
wave's last round reach it) and left their vertices to a later wave's
leader chain; ``pump.wave_commit`` counts the others. 0 in a committee
that loses nobody; with f of 3f+1 crashed about f in n by the coin.
Nothing from a program that counts neither."""

from benchmarks.harness import validatorbook


def read(obs):
    book = validatorbook.open_book(obs)
    if book is None or "pump.wave_commit" not in book.counts:
        return None
    skipped = book.counts.get("pump.wave_skip", 0)
    tried = skipped + book.counts["pump.wave_commit"]
    return 100.0 * skipped / tried if tried else None
