"""kernels: the least time the chip's memory could take to move the bytes
of one dispatch's fixed-key comb walk, over the comb program's time.
Bound by bytes only: the walk is int32 multiplies on the VPU, for which
no sourced peak exists, so this share reads low."""

from benchmarks.harness import bytecount, peaks, stats, trace


def read(obs):
    span = stats.mean(trace.program_seconds(obs["trace"], "device_verify_comb"))
    bucket = obs["counters"].get("bucket")
    if span is None or not bucket:
        return None
    least_s = bytecount.comb_walk_bytes(bucket) / peaks.peak(
        obs["device_kind"], "hbm_bytes_per_s"
    )
    return 100.0 * least_s / span
