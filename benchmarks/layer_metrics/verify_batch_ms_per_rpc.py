"""verify seam: the span of ``backend.verify_batch`` inside the server."""

from benchmarks.harness import stats


def read(obs):
    span = stats.mean(obs["samples"].get("server_span_s", ()))
    return None if span is None else 1e3 * span
