"""Peaks of one chip, keyed by ``jax.Device.device_kind`` exactly as the
runtime reports it.  A kind that is not here is an error, never a
default: there is no peak to divide a CPU's rate by."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
    # HBM at 819 GB/s per chip
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9,
                    "memory_bytes": 16e9},
}


def peaks_of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks on file for device_kind {device_kind!r};"
                       f" known: {sorted(PEAKS)} (add the row with its "
                       "source)")
    return PEAKS[device_kind]
