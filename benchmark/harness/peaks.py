"""Published per-chip peaks, keyed by jax's `device_kind`.  A device that
is not in the table is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 16 GB HBM2e at
819 GB/s per chip.
"""

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "memory_bytes": 16e9},
    "TPU v5e": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                "memory_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add it to "
            f"benchmark/harness/peaks.py with its source")
    return PEAKS[device_kind]
