"""Published peaks of the chips the benchmark may run on, keyed by
jax's ``device_kind``. A device that is not here is an error."""

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 16 GB HBM2e, "
        "819 GB/s per chip",
    },
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(
            f"no published {what!r} for device kind {device_kind!r}; add it "
            "to benchmarks/harness/peaks.py with its source"
        ) from None
