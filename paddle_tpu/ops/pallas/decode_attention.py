"""Pallas TPU decode attention (MMHA analog) over a KV cache.

Port target: the reference's masked multi-head attention decode kernel
(/root/reference/paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu)
— one new query token per sequence attending to a preallocated KV cache
with a per-sequence valid length.  GQA native (q heads grouped onto kv
heads).  The block/paged variant (block_multi_head_attention_kernel.cu) maps
onto the same kernel via gather-free contiguous caches here; paged KV is
tracked separately.

Layouts (static shapes, XLA-friendly):
    q:        [B, Hq, D]       — the current step's query
    k_cache:  [B, T, Hkv, D]   — rows >= length are ignored
    v_cache:  [B, T, Hkv, D]
    lengths:  [B] int32        — number of valid cache rows per sequence
Returns [B, Hq, D].
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import NEG_INF, use_interpret

__all__ = ["decode_attention", "decode_attention_ref"]

DEFAULT_BLOCK_T = 512


def decode_attention_ref(q, k_cache, v_cache, lengths, scale=None):
    """Dense jnp reference (and CPU fallback)."""
    B, Hq, D = q.shape
    T, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    s = scale if scale is not None else 1.0 / math.sqrt(D)
    qg = q.reshape(B, Hkv, G, D)
    logits = jnp.einsum("bkgd,btkd->bkgt", qg.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * s
    mask = jnp.arange(T)[None, None, None, :] < lengths[:, None, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p,
                     v_cache.astype(jnp.float32))
    return out.reshape(B, Hq, D).astype(q.dtype)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale, block_t, nt):
    b = pl.program_id(0)
    jt = pl.program_id(2)

    @pl.when(jt == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    q = q_ref[:]                                   # [G, D]
    k = k_ref[:]                                   # [bt, D]
    v = v_ref[:]                                   # [bt, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    t_pos = jt * block_t + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    s = jnp.where(t_pos < length, s, NEG_INF)
    m_prev = m_scr[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[:] = m_new

    @pl.when(jt == nt - 1)
    def _final():
        o_ref[:] = (acc_scr[:]
                    / jnp.maximum(l_scr[:], 1e-30)).astype(o_ref.dtype)


def _decode_pallas(q, k_cache, v_cache, lengths, scale):
    B, Hq, D = q.shape
    T, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    bt = min(DEFAULT_BLOCK_T, T)
    pad_t = (-T) % bt
    if pad_t:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad_t), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad_t), (0, 0), (0, 0)))
    Tp = T + pad_t
    nt = Tp // bt
    # [B, T, Hkv, D] -> [B, Hkv, T, D];  q -> [B, Hkv, G, D]
    kt = jnp.swapaxes(k_cache, 1, 2)
    vt = jnp.swapaxes(v_cache, 1, 2)
    qg = q.reshape(B, Hkv, G, D)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_t=bt, nt=nt),
        grid=(B, Hkv, nt),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # lengths, whole array
            pl.BlockSpec((None, None, G, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((None, None, bt, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((None, None, bt, D), lambda b, h, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, G, D),
                               lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=use_interpret(),
    )(lengths.astype(jnp.int32), qg, kt, vt)
    return out.reshape(B, Hq, D)


def decode_attention(q, k_cache, v_cache, lengths,
                     scale: Optional[float] = None,
                     use_pallas: Optional[bool] = None):
    """Single-step masked decode attention over a KV cache (MMHA analog).

    Differentiation is not needed on the decode path; this is forward-only.
    """
    B, Hq, D = q.shape
    Hkv = k_cache.shape[2]
    if Hq % Hkv != 0:
        raise ValueError(f"q heads ({Hq}) must be a multiple of kv heads "
                         f"({Hkv})")
    s = scale if scale is not None else 1.0 / math.sqrt(D)
    if use_pallas is None:
        # same dispatch as every other kernel: real accelerator, forced
        # interpret (CPU tests), or forced Mosaic compile (TPU cross-
        # lowering lane)
        from ...core.device import on_tpu
        from ...core.flags import FLAGS
        use_pallas = bool(FLAGS.pallas_interpret
                          or FLAGS.pallas_force_compile or on_tpu())
    if use_pallas:
        return _decode_pallas(q, k_cache, v_cache, lengths, s)
    return decode_attention_ref(q, k_cache, v_cache, lengths, s)
