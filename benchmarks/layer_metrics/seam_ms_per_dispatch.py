"""verify seam: the dispatch window's seam seconds
(``VerifierPipeline.last_seam_s``) of one dispatch that carries a round —
one chunk, more than half full — as the median over the window's. The
few rows a forged vertex or a cycle's leftover messages add go out as
padded dispatches of their own and are left out; the median, because a
collection of the interpreter's garbage that falls into one call is not
the seam's."""

from benchmarks.harness import stats


def read(obs):
    seam = stats.percentile(obs["samples"].get("seam_full_s", ()), 50)
    return None if seam is None else 1e3 * seam
