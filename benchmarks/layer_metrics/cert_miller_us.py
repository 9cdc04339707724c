"""kernels: device time of one run of the certificate Miller program,
from the trace's program events."""

from benchmarks.harness import stats, trace


def read(obs):
    span = stats.mean(trace.program_seconds(obs["trace"], "cert_miller"))
    return None if span is None else 1e6 * span
