"""kernels: the committee's Miller line tables the certificate backend
keeps on the device — every key's and -G2's, dense Fp12 slope and
constant a slot — by the program's counter ``cert.table_bytes``, in MiB.
Nothing from a program that does not count them."""

from benchmarks.harness import spanbook


def read(obs):
    book = spanbook.open_book(obs)
    if book is None:
        return None
    tables = book.counts.get("cert.table_bytes")
    return None if tables is None else tables / 2**20
