"""``paddle_tpu.nn.quant`` — weight-only quantization.

Reference: python/paddle/nn/quant/quantized_linear.py (``weight_quantize``,
``weight_dequantize``, ``weight_only_linear``, ``llm_int8_linear``) backed
by phi/kernels/weight_only_linear_kernel.h + fusion/cutlass gemms.

Layout note: the reference's weight_quantize returns a CUTLASS-tiled
layout; here the quantized weight keeps the LOGICAL [in, out] layout of
``paddle_tpu.nn.Linear`` (the Pallas kernel does its own tiling), so
quantized checkpoints are human-readable and resharding-friendly.

int4 is stored two nibbles per int8 byte along the input dim (rows 2k and
2k+1 packed), halving HBM again; the unpack happens at dequant.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ...core.dispatch import run_op
from ...core.tensor import Tensor

__all__ = ["weight_quantize", "weight_dequantize", "weight_only_linear",
           "llm_int8_linear"]


def _unwrap(t):
    return t._value if isinstance(t, Tensor) else jnp.asarray(t)


def _group_expand(scale, K, group_size):
    """[G, N] group scales -> [K, N] per-row scales."""
    s = jnp.repeat(scale, group_size, axis=0)
    return s[:K]


def weight_quantize(x, algo: str = "weight_only_int8", arch=None,
                    group_size: int = -1):
    """Absmax quantization.  Returns (out, scale).

    algo: "weight_only_int8" | "llm.int8" -> int8 [K, N];
          "weight_only_int4" -> packed int8 [ceil(K/2), N] (two rows per
          byte: low nibble = even row, high nibble = odd row).

    group_size: -1 = one scale per output channel (scale [N]); 64/128 =
    group-wise — one scale per (group of input rows x output channel)
    (scale [ceil(K/group_size), N], the reference weight_quantize's
    group_size semantics).
    """
    if algo not in ("weight_only_int8", "weight_only_int4", "llm.int8"):
        raise ValueError(f"unknown quantize algo {algo!r}")
    if group_size not in (-1, None, 64, 128):
        raise ValueError(f"group_size must be -1/64/128, got {group_size}")
    grouped = group_size in (64, 128)
    if grouped and algo == "llm.int8":
        # llm_int8_linear's vector-wise int8 dot consumes a [N] scale;
        # grouped scales belong to the weight_only_* paths
        raise ValueError("group_size is only supported for "
                         "weight_only_int8/int4, not llm.int8")

    def impl(w):
        wf = w.astype(jnp.float32)
        K = wf.shape[0]
        if grouped:
            G = -(-K // group_size)
            wp = jnp.pad(wf, ((0, G * group_size - K), (0, 0)))
            absmax = jnp.max(jnp.abs(wp.reshape(G, group_size, -1)), axis=1)
        else:
            absmax = jnp.max(jnp.abs(wf), axis=0)
        qmax = 7.0 if algo == "weight_only_int4" else 127.0
        scale = jnp.maximum(absmax, 1e-8) / qmax
        srow = _group_expand(scale, K, group_size) if grouped else scale
        q = jnp.clip(jnp.round(wf / srow), -qmax - 1, qmax).astype(jnp.int8)
        if algo != "weight_only_int4":
            return q, scale
        if q.shape[0] % 2:
            q = jnp.pad(q, ((0, 1), (0, 0)))
        half = q.shape[0] // 2
        # HALVES packing: rows [0, K/2) in the low nibble, rows
        # [K/2, K) in the high nibble — lets the matmul kernel unpack
        # as two contiguous nibble-plane matmuls (x_lo @ lo + x_hi @ hi)
        # with no row interleave.
        lo = q[:half]
        hi = q[half:]
        packed = (lo & 0x0F) | (hi << 4)
        return packed.astype(jnp.int8), scale

    return run_op("weight_quantize", impl, (x,), {}, differentiable=False)


def _unpack_int4(packed, k_orig):
    lo = (packed << 4).astype(jnp.int8) >> 4       # sign-extend low nibble
    hi = packed >> 4                               # arithmetic shift
    q = jnp.concatenate([lo, hi], axis=0)          # halves packing
    return q[:k_orig]


def weight_dequantize(x, scale, algo: str = "weight_only_int8",
                      out_dtype="float32", k: Optional[int] = None,
                      group_size: int = -1):
    """Inverse of :func:`weight_quantize` (reference weight_dequantize),
    incl. group-wise scales ([G, N] with ``group_size`` rows/group)."""
    if group_size not in (-1, None, 64, 128):
        raise ValueError(f"group_size must be -1/64/128, got {group_size}")
    grouped = group_size in (64, 128)

    def impl(q, s):
        if algo == "weight_only_int4":
            kk = k if k is not None else q.shape[0] * 2
            qq = _unpack_int4(q, kk)
        else:
            qq = q
        sf = s.astype(jnp.float32)
        if grouped:
            sf = _group_expand(sf, qq.shape[0], group_size)
        return (qq.astype(jnp.float32) * sf).astype(jnp.dtype(out_dtype))

    return run_op("weight_dequantize", impl, (x, scale), {},
                  differentiable=False)


def weight_only_linear(x, weight, bias=None, weight_scale=None,
                       weight_dtype: str = "int8", arch=None,
                       group_size: int = -1):
    """y = x @ dequant(weight) + bias (reference
    nn/quant/quantized_linear.py:weight_only_linear).

    weight: int8 [K, N] ("int8") or packed int4 [ceil(K/2), N] ("int4").
    Dispatches to the Pallas streaming-dequant matmul on TPU
    (ops/pallas/quant_linear.py); jnp dequant+matmul elsewhere.
    """
    if weight_dtype not in ("int8", "int4"):
        raise ValueError(f"weight_dtype must be int8/int4, got "
                         f"{weight_dtype!r}")
    if weight_scale is None:
        raise ValueError("weight_only_linear needs weight_scale from "
                         "weight_quantize")
    if group_size not in (-1, None, 64, 128):
        raise ValueError(f"group_size must be -1/64/128, got {group_size}")
    grouped = group_size in (64, 128)

    def impl(xv, wq, s, b):
        K = xv.shape[-1]
        from ...core.device import on_tpu
        from ...core.flags import FLAGS
        # the int4 grouped kernel needs nibble planes aligned to groups
        int4_ok = (not grouped) or (wq.shape[0] % group_size == 0)
        if (FLAGS.pallas_interpret or on_tpu()) and \
                (weight_dtype == "int8" or int4_ok):
            gs = group_size if grouped else -1
            if weight_dtype == "int4":
                # packed nibbles stream straight into the kernel — half
                # the HBM bytes of int8; unpack happens in VMEM
                from ...ops.pallas.quant_linear import (
                    weight_only_matmul_int4)
                y = weight_only_matmul_int4(xv, wq, s, group_size=gs)
            else:
                from ...ops.pallas.quant_linear import weight_only_matmul
                y = weight_only_matmul(xv, wq, s, group_size=gs)
        else:
            wd = _unpack_int4(wq, K) if weight_dtype == "int4" else wq
            sf = s.astype(xv.dtype)
            if grouped:
                y = xv @ (wd.astype(xv.dtype)
                          * _group_expand(sf, wd.shape[0], group_size))
            else:
                y = (xv @ wd.astype(xv.dtype)) * sf
        if b is not None:
            y = y + b
        return y

    return run_op("weight_only_linear", impl, (x, weight, weight_scale,
                                               bias), {})


def llm_int8_linear(x, weight, bias=None, weight_scale=None,
                    threshold: float = 6.0):
    """LLM.int8() mixed decomposition (reference llm_int8_linear):
    outlier activation columns (|x| > threshold) run in fp, the rest on
    the int8 weight path, summed."""
    if weight_scale is None:
        raise ValueError("llm_int8_linear needs weight_scale")

    def impl(xv, wq, s, b):
        wf = wq.astype(jnp.float32) * s.astype(jnp.float32)
        col_amax = jnp.max(jnp.abs(xv.astype(jnp.float32)), axis=tuple(
            range(xv.ndim - 1)))
        outlier = col_amax > threshold                     # [K]
        x_in = jnp.where(outlier, 0.0, xv.astype(jnp.float32))
        # inlier path: quantize activations to int8 per-row absmax and run
        # an integer dot (LLM.int8()'s vector-wise scheme); outliers stay fp
        row_amax = jnp.max(jnp.abs(x_in), axis=-1, keepdims=True)
        xs = jnp.maximum(row_amax, 1e-8) / 127.0
        x8 = jnp.clip(jnp.round(x_in / xs), -127, 127).astype(jnp.int8)
        y_in = jax.lax.dot_general(
            x8, wq, (((x8.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32)
        y_in = y_in * xs * s.astype(jnp.float32)
        x_out = jnp.where(outlier, xv.astype(jnp.float32), 0.0)
        y = y_in + (x_out @ wf)
        if b is not None:
            y = y + b
        return y.astype(xv.dtype)

    return run_op("llm_int8_linear", impl, (x, weight, weight_scale, bias),
                  {})


# ---------------------------------------------------------------------------
# fp8 gemm (reference paddle/phi/kernels/fusion/fp8_gemm/ +
# incubate fp8_fp8_half_gemm_fused): e4m3 storage with per-tensor scales,
# MXU matmul in fp8 with fp32 accumulation.
# ---------------------------------------------------------------------------
_FP8_E4M3_MAX = 448.0


def quantize_to_fp8(x, dtype="float8_e4m3fn"):
    """Per-tensor absmax scaling into fp8.  Returns (x_fp8, scale) with
    ``x ≈ x_fp8.astype(f32) * scale``."""
    from ...core.dispatch import run_op

    def impl(xv):
        absmax = jnp.max(jnp.abs(xv.astype(jnp.float32)))
        scale = jnp.maximum(absmax, 1e-12) / _FP8_E4M3_MAX
        q = (xv.astype(jnp.float32) / scale).astype(jnp.dtype(dtype))
        return q, scale

    return run_op("quantize_to_fp8", impl, (x,), {}, differentiable=False)


def fp8_gemm(x, y, x_scale=None, y_scale=None, bias=None,
             transpose_x=False, transpose_y=False, activation=None,
             output_dtype="float32"):
    """out = act((x_fp8 @ y_fp8) * x_scale * y_scale + bias) (reference
    fp8_fp8_half_gemm_fused).  Inputs may be pre-quantized fp8 (+ scales)
    or float tensors (quantized here).  The dot runs in fp8 with fp32
    accumulation — the MXU's native fp8 path on v5p+; elsewhere XLA
    emulates, numerics identical."""
    from ...core.dispatch import run_op

    def impl(xv, yv, xs, ys, b):
        def prep(v, s):
            if v.dtype in (jnp.float8_e4m3fn, jnp.float8_e5m2):
                return v, (jnp.asarray(1.0, jnp.float32) if s is None
                           else s.astype(jnp.float32))
            absmax = jnp.max(jnp.abs(v.astype(jnp.float32)))
            sc = jnp.maximum(absmax, 1e-12) / _FP8_E4M3_MAX
            return ((v.astype(jnp.float32) / sc).astype(jnp.float8_e4m3fn),
                    sc)

        xq, xsc = prep(xv, xs)
        yq, ysc = prep(yv, ys)
        if transpose_x:
            xq = jnp.swapaxes(xq, -1, -2)
        if transpose_y:
            yq = jnp.swapaxes(yq, -1, -2)
        out = jax.lax.dot_general(
            xq, yq, (((xq.ndim - 1,), (yq.ndim - 2,)), ((), ())),
            preferred_element_type=jnp.float32)
        out = out * xsc * ysc
        if b is not None:
            out = out + b.astype(jnp.float32)
        if activation in ("gelu", "relu", "silu", "sigmoid", "tanh"):
            out = getattr(jax.nn, activation)(out) \
                if activation != "tanh" else jnp.tanh(out)
        elif activation not in (None, "", "identity"):
            raise ValueError(f"fp8_gemm: unknown activation {activation!r}")
        return out.astype(jnp.dtype(output_dtype))

    return run_op("fp8_gemm", impl, (x, y, x_scale, y_scale, bias), {},
                  differentiable=False)


__all__ += ["quantize_to_fp8", "fp8_gemm"]
