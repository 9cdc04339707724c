"""kernels: the least time the chip's memory could take to move the
bytes one run of the certificate Miller program reads — every pair's
gathered line rows and its point (``harness/bytecount_bls.py``) — over
the program's time. Bound by bytes only, as ``comb_roofline`` is: the
program is int32 multiplies on the VPU, for which no sourced peak
exists, so this share reads low."""

from benchmarks.harness import bytecount_bls, peaks, stats, trace


def read(obs):
    span = stats.mean(trace.program_seconds(obs["trace"], "cert_miller"))
    rows = obs["counters"].get("pair_rows")
    if span is None or not rows:
        return None
    least_s = bytecount_bls.miller_bytes(rows) / peaks.peak(
        obs["device_kind"], "hbm_bytes_per_s"
    )
    return 100.0 * least_s / span
