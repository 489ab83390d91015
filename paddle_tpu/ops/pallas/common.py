"""Shared helpers for Pallas TPU kernels."""

from __future__ import annotations

__all__ = ["use_interpret", "NEG_INF"]

NEG_INF = -1e30


def use_interpret() -> bool:
    """Run kernels in interpreter mode off-TPU (CPU tests) or when forced.

    FLAGS.pallas_force_compile routes kernels onto the real Mosaic
    compile path regardless of the local backend — used by the TPU
    cross-lowering lane (tests/test_pallas_tpu_lowering.py), where
    ``jax.export(..., platforms=["tpu"])`` Mosaic-compiles every kernel
    on a CPU-only host."""
    from ...core.device import on_tpu
    from ...core.flags import FLAGS
    if FLAGS.pallas_force_compile:
        return False
    if FLAGS.pallas_interpret:
        return True
    return not on_tpu()
