"""host pump: of the vertices the buffer drain admitted into the DAG, the
share the round-batched drain admitted (``pump.admit_batched``: a whole
round group checked against one row of the dense mirror and landed as
one insert) and not the scalar walk that is its oracle
(``pump.admit_scalar``: a vertex at a time). 100: every view ran the
round-batched pump; 0: every view the scalar one. Nothing from a
program that counts neither."""

from benchmarks.harness import spanbook


def read(obs):
    book = spanbook.open_book(obs)
    if book is None:
        return None
    batched = book.counts.get("pump.admit_batched", 0)
    admitted = batched + book.counts.get("pump.admit_scalar", 0)
    if not admitted:
        return None
    return 100.0 * batched / admitted
