"""Published peaks of the chips this benchmark may run on, keyed by the
``device_kind`` JAX reports. A device that is not in the table is an error,
never a default: a share of an assumed peak is not a measurement.

Source: Google Cloud documentation, "TPU v5e" system architecture (one chip:
197 TFLOP/s bf16, HBM2e at 819 GB/s).
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e, per chip)",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(PEAKS)}. Add the chip with "
                       f"its source, do not assume one.")
    return PEAKS[device_kind]


def roofline_seconds(flops: float, bytes_moved: float, device_kind: str):
    """The least time the chip could take, and which bound sets it."""
    p = peaks_for(device_kind)
    by_compute = flops / p["bf16_flops_per_s"]
    by_memory = bytes_moved / p["hbm_bytes_per_s"]
    return (by_compute, "compute") if by_compute >= by_memory \
        else (by_memory, "memory")
