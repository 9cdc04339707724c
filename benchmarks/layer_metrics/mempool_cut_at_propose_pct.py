"""mempool: of the blocks the mempool cut, the share it cut for a vertex
that was being made (``mempool.cut_at_propose``: the proposer asked) and
not ahead of one (``mempool.cut_ahead``: ``build_blocks``, for a caller
that stages them). 100: nothing was staged in front of consensus; 0: a
driver that feeds ``Process.submit`` itself. Read from validator 0's
book where the validators are processes of their own and left one, else
from this process's, which then holds the mempools. Nothing from a
program that counts neither."""

from benchmarks.harness import spanbook, validatorbook


def read(obs):
    book = validatorbook.open_book(obs) or spanbook.open_book(obs)
    if book is None:
        return None
    asked = book.counts.get("mempool.cut_at_propose", 0)
    cut = asked + book.counts.get("mempool.cut_ahead", 0)
    if not cut:
        return None
    return 100.0 * asked / cut
