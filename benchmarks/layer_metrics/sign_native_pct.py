"""host pump: of the vertices ``VertexSigner`` signed, the share it
signed through libcrypto's Ed25519 and not in pure Python — 100 ×
``sign.native`` / (that + ``sign.python``); nothing from a program that
counts neither."""

from benchmarks.harness import spanbook


def read(obs):
    book = spanbook.open_book(obs)
    if book is None:
        return None
    native = book.counts.get("sign.native", 0)
    python = book.counts.get("sign.python", 0)
    return spanbook.ratio(native, native + python, 100.0)
