"""verify seam: of the comb programs the verifier built, the share it
loaded from the lowered program stored beside the compile cache and did
not trace — 100 × ``verifier.program_loaded`` / (that +
``verifier.program_lowered``); nothing from a program that counts
neither."""

from benchmarks.harness import spanbook


def read(obs):
    book = spanbook.open_book(obs)
    if book is None:
        return None
    loaded = book.counts.get("verifier.program_loaded", 0)
    lowered = book.counts.get("verifier.program_lowered", 0)
    return spanbook.ratio(loaded, loaded + lowered, 100.0)
