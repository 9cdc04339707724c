"""kernels: the comb tables the verifier keeps on the device — every
key's and the base point's, in the gather layout — by the program's
counter ``verifier.table_bytes``, in MiB. Nothing from a program that
does not count them."""

from benchmarks.harness import spanbook


def read(obs):
    book = spanbook.open_book(obs)
    if book is None:
        return None
    tables = book.counts.get("verifier.table_bytes")
    return None if tables is None else tables / 2**20
