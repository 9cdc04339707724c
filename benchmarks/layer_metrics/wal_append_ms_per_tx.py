"""WAL: validator 0's ``wal.append`` — an acknowledged transaction's line
into the write-ahead log before the answer leaves — per acknowledged
transaction (the client sends one an RPC)."""

from benchmarks.harness import validatorbook
from benchmarks.harness.spanbook import ratio


def read(obs):
    book = validatorbook.open_book(obs)
    if book is None:
        return None
    return ratio(book.total_ns("wal.append"), book.count("wal.append"), 1e-6)
