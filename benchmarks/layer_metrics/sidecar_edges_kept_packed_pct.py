"""sidecar server: the share of the vertices its requests decoded
(``sidecar.vertices_decoded``) whose edge lists nobody read, so that
they stayed the frame's bytes (``codec.edges_unpacked`` counts the
others). 100: no vertex of any RPC had its edges unpacked; 0: every one
had. Nothing from a program that does not count what it decodes."""

from benchmarks.harness import spanbook


def read(obs):
    book = spanbook.open_book(obs)
    if book is None:
        return None
    decoded = book.counts.get("sidecar.vertices_decoded")
    if not decoded:
        return None
    return 100.0 * (1.0 - book.counts.get("codec.edges_unpacked", 0) / decoded)
