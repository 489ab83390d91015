"""Hardware peak-FLOPs table: what ``Model.fit``'s telemetry divides
its 6N-FLOPs-a-token rate by for the step record's ``mfu``."""

from __future__ import annotations

__all__ = ["PEAK_BF16_FLOPS", "peak_flops_per_chip"]


#: bf16 peak FLOP/s of one chip, keyed by ``jax.Device.device_kind``
#: exactly as the runtime reports it, each with its source.  A kind
#: that is not here is an error, never a default.
PEAK_BF16_FLOPS = {
    # Google Cloud documentation, "TPU v4": 275 TFLOP/s per chip
    "TPU v4": 275e12,
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    "TPU v5 lite": 197e12,
    # Google Cloud documentation, "TPU v5p": 459 TFLOP/s bf16 per chip
    "TPU v5": 459e12,
    # Google Cloud documentation, "TPU v6e": 918 TFLOP/s bf16 per chip
    "TPU v6 lite": 918e12,
}


def peak_flops_per_chip(device) -> float:
    """bf16 peak FLOP/s of ``device`` from :data:`PEAK_BF16_FLOPS`;
    raises ``KeyError`` for a device kind the table does not hold (a
    CPU among them — there is no peak to divide a CPU rate by)."""
    kind = device.device_kind
    if kind not in PEAK_BF16_FLOPS:
        raise KeyError(
            f"no peak FLOP/s on file for device_kind {kind!r}; known: "
            f"{sorted(PEAK_BF16_FLOPS)} (add the row with its source)")
    return PEAK_BF16_FLOPS[kind]
