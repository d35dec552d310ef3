"""Published per-chip peaks, keyed by the exact ``device_kind`` jax reports.

One row today. Source: Google Cloud documentation, "TPU v5e" system
architecture page: 197 TFLOP/s bf16, 16 GB of HBM2e at 819 GB/s per chip.
A device that is not in the table is an error, never a neighbour's number:
a later PR that runs on another chip adds its row, with its source.
"""

PEAKS = {
    # A v5e chip reports itself as "TPU v5 lite".
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"perfbench/harness/peaks.py (known: {sorted(PEAKS)})"
        ) from None
