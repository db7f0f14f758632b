"""Published peaks of each accelerator the benchmark may run on, keyed by
the `device_kind` that JAX reports. A kind that is not here is an error:
a share of a peak that was guessed is worth nothing."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture): one chip
    # gives 197 TFLOP/s in bf16 and 819 GB/s of HBM bandwidth (16 GB).
    "TPU v5 lite": {"bf16_flop_s": 197e12, "hbm_bytes_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of `device_kind`; raises KeyError for a kind
    the table does not hold."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
