"""Pallas TPU megakernel for the fused chunked-prefill transformer block.

The chunked-prefill twin of ``decode_block.py`` (ISSUE 18, ROADMAP
item 3): one kernel invocation runs ONE layer for one ``[chunk, H]``
tile of prompt tokens of ONE sequence — norm → qkv projection → RoPE at
the tile's absolute positions → flash-style CAUSAL attention over the
sequence's committed KV pages plus the in-chunk tokens → out-projection
+ residual → norm → FFN → residual.  The residual tile, the projected
q/k/v, and the online-softmax state live in VMEM scratch for the whole
layer; the only HBM traffic is the weights (streamed once), the KV
pages the attention DMA-gathers through the block table, and the tile's
read + write-back — versus ~8 full round-trips of the ``[chunk, H]``
stream per layer in the per-op chain (docs/performance.md).

Shape of the kernel:

* grid ``(nt,)`` — ``nt`` page-chunks of the sequence's block-table
  row; the whole ``[chunk, H]`` tile is resident at every step.
* the prologue at chunk 0 runs norm/qkv/rope for all ``chunk`` tokens,
  writing q and the tile's (quantize-round-tripped, when the pool is
  int8) k/v to scratch; pages DMA-copy through the same revolving
  TWO-SLOT staging buffer as the decode kernel — each grid step starts
  the NEXT page-chunk's copies before waiting on its own
  (``cost.DMA_STAGING_SLOTS``) — and fold into the causal online
  softmax (committed positions ``t < start`` only); the epilogue folds
  the IN-CHUNK tokens under the causal mask (the pool scatter happens
  host-side after the kernel, so pool semantics match the per-op
  tier's positional ``.at[blk, off].set``), then runs out-proj, norm,
  FFN and both residual adds.
* pages per chunk is the autotuned knob (``"prefill_block"`` key in
  ``ops/pallas/autotune``), candidates filtered through
  ``cost.prefill_block_vmem`` with the SAME floor convention as the
  decode kernel (``decode_block._floor_candidates``).

Limits (the ``ops/decode_block.prefill_block`` dispatch falls back to
the reference tier outside them, or raises the typed
``PrefillBlockUnsupportedError`` when the kernel is forced): the
layer's full weight set plus the double-buffered page staging plus the
chunk-tile scratch must fit the shared VMEM budget, and ``head_dim``
is capped — both read from ``analysis/kernel/cost.py``, never a local
constant.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...analysis.kernel import cost
from ..paged_kv import (KV_SCALE_EPS, QuantizedKVPool, is_quantized_pool,
                        quantize_kv)
from .common import NEG_INF, use_interpret
from .decode_block import (DEFAULT_PAGES, MAX_HEAD_DIM, VMEM_BUDGET_BYTES,
                           _PAGE_CANDIDATES, _floor_candidates, _mmw,
                           _norm_rows, _param_keys, _pool_itemsize,
                           _rot_half)

__all__ = ["prefill_block_pallas", "tune_prefill_block",
           "unsupported_reason"]


class _Meta(NamedTuple):
    hidden: int
    num_heads: int
    kv_heads: int
    head_dim: int
    block_size: int
    norm: str
    activation: str
    eps: float
    rope: bool
    fused_qkv: bool
    bias: bool
    pages: int           # pages staged per attention chunk
    nt: int              # number of page-chunks (grid length)
    mb: int              # block-table width
    chunk: int           # resident prompt-tile length (Ts)
    scale: float
    weight_dtype: Optional[str] = None
    group_size: int = -1
    kv_quant: bool = False
    param_keys: Tuple[str, ...] = ()


def _vmem_total(spec, pages: int, chunk: int, wbytes: int,
                pool_itemsize: int, x_itemsize: int,
                kv_quant: bool = False) -> int:
    """One layer invocation's VMEM bytes — the shared cost model's
    number (analysis/kernel/cost.py), never a local formula."""
    return cost.prefill_block_vmem(
        hidden=spec.hidden, num_heads=spec.num_heads,
        kv_heads=spec.kv_heads, head_dim=spec.head_dim,
        block_size=spec.block_size, pages=pages, chunk=chunk,
        weight_bytes=wbytes, pool_itemsize=pool_itemsize,
        x_itemsize=x_itemsize, kv_quant=kv_quant)["total"]


def unsupported_reason(spec, lp, pool_k, chunk: int) -> Optional[str]:
    """None when this layer + chunk length fits the kernel, else the
    reason (the ``ops/decode_block.prefill_block`` dispatch signal).
    Layout checks (a dense layer dict) live here; every byte/cap limit
    is delegated to the shared cost model so the static analysis and
    this runtime gate cannot drift."""
    keys = _param_keys(spec, lp)
    missing = [n for n in keys if n not in lp]
    if missing:
        return (f"layer dict lacks {missing} — not a dense "
                f"{spec.activation} block"
                + (" in the quantized export layout"
                   if getattr(spec, "weight_dtype", None) else
                   " (MoE FFNs run the reference tier)"))
    if not use_interpret():
        lanes = cost.head_dim_lane_reason(spec.head_dim)
        if lanes is not None:
            return lanes
    wbytes = sum(lp[n].size * lp[n].dtype.itemsize for n in keys)
    return cost.prefill_block_unsupported_reason(
        hidden=spec.hidden, num_heads=spec.num_heads,
        kv_heads=spec.kv_heads, head_dim=spec.head_dim,
        block_size=spec.block_size, chunk=int(chunk), rope=spec.rope,
        weight_bytes=wbytes, pool_itemsize=_pool_itemsize(pool_k),
        x_itemsize=lp[keys[0]].dtype.itemsize,
        kv_quant=is_quantized_pool(pool_k),
        budget=VMEM_BUDGET_BYTES)


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------
def _kernel(*refs, meta: _Meta):
    nw = len(meta.param_keys)
    np_ = 4 if meta.kv_quant else 2
    start_ref, bt_ref, x_ref, cos_ref, sin_ref = refs[:5]
    w = dict(zip(meta.param_keys, refs[5:5 + nw]))
    pool_refs = refs[5 + nw:5 + nw + np_]
    x_out_ref, kn_ref, vn_ref = refs[5 + nw + np_:8 + nw + np_]
    if meta.kv_quant:
        pool_k_ref, pool_v_ref, pool_ks_ref, pool_vs_ref = pool_refs
        (q_scr, kn_scr, vn_scr, m_scr, l_scr, acc_scr, kbuf, vbuf,
         ksbuf, vsbuf, sem) = refs[8 + nw + np_:]
    else:
        pool_k_ref, pool_v_ref = pool_refs
        (q_scr, kn_scr, vn_scr, m_scr, l_scr, acc_scr, kbuf, vbuf,
         sem) = refs[8 + nw + np_:]

    jt = pl.program_id(0)
    Hq, Hkv, D = meta.num_heads, meta.kv_heads, meta.head_dim
    G = Hq // Hkv
    P, BS, Ts = meta.pages, meta.block_size, meta.chunk
    start = start_ref[0]

    # ---- prologue: norm1 + qkv + rope for the whole tile, once -------
    @pl.when(jt == 0)
    def _pro():
        x = x_ref[:].astype(jnp.float32)                    # [Ts, H]
        y = _norm_rows(x, w["ln1_w"][:],
                       w["ln1_b"][:] if meta.fused_qkv else None, meta)
        if meta.fused_qkv:
            z = _mmw(y, w, "qkv_w", meta) + w["qkv_b"][:][None, :]
            z = z.reshape(Ts, Hq, 3 * D)
            q, k, v = z[..., :D], z[..., D:2 * D], z[..., 2 * D:]
        else:
            q = _mmw(y, w, "q_w", meta).reshape(Ts, Hq, D)
            k = _mmw(y, w, "k_w", meta).reshape(Ts, Hkv, D)
            v = _mmw(y, w, "v_w", meta).reshape(Ts, Hkv, D)
        if meta.rope:
            cos = cos_ref[:].astype(jnp.float32)[:, None, :]
            sin = sin_ref[:].astype(jnp.float32)[:, None, :]
            q = q * cos + _rot_half(q) * sin
            k = k * cos + _rot_half(k) * sin
        q_scr[:] = q.transpose(1, 0, 2)                     # [Hq, Ts, D]
        if meta.kv_quant:
            # attend the int8-ROUND-TRIPPED in-chunk k/v: the host-side
            # scatter quantizes these rows into the pool, so attending
            # the stored value keeps this fill consistent with the XLA
            # tier (which gathers its own freshly-quantized pages) and
            # with what every future step reads back
            ks = jnp.maximum(jnp.max(jnp.abs(k), axis=-1,
                                     keepdims=True),
                             KV_SCALE_EPS) / 127.0
            vs = jnp.maximum(jnp.max(jnp.abs(v), axis=-1,
                                     keepdims=True),
                             KV_SCALE_EPS) / 127.0
            kn_scr[:] = (jnp.clip(jnp.round(k / ks), -127, 127)
                         * ks).transpose(1, 0, 2)
            vn_scr[:] = (jnp.clip(jnp.round(v / vs), -127, 127)
                         * vs).transpose(1, 0, 2)
        else:
            kn_scr[:] = k.transpose(1, 0, 2)                # [Hkv, Ts, D]
            vn_scr[:] = v.transpose(1, 0, 2)
        kn_ref[:] = k.astype(kn_ref.dtype)                  # [Ts, Hkv, D]
        vn_ref[:] = v.astype(vn_ref.dtype)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # ---- attention page-chunk: double-buffered DMA (chunk jt's copies
    # started one grid step earlier; start jt+1's into the other slot
    # before waiting) then fold COMMITTED positions (t < start) into the
    # online softmax of every in-chunk query --------------------------
    def _page_copies(ct, slot):
        copies = []
        for p in range(P):
            idx = jnp.minimum(ct * P + p, meta.mb - 1)
            phys = jnp.maximum(bt_ref[idx], 0)
            copies += [pltpu.make_async_copy(pool_k_ref.at[phys],
                                             kbuf.at[slot, p],
                                             sem.at[slot, p, 0]),
                       pltpu.make_async_copy(pool_v_ref.at[phys],
                                             vbuf.at[slot, p],
                                             sem.at[slot, p, 1])]
            if meta.kv_quant:
                copies += [pltpu.make_async_copy(pool_ks_ref.at[phys],
                                                 ksbuf.at[slot, p],
                                                 sem.at[slot, p, 2]),
                           pltpu.make_async_copy(pool_vs_ref.at[phys],
                                                 vsbuf.at[slot, p],
                                                 sem.at[slot, p, 3])]
        return copies

    slot = jax.lax.rem(jt, 2)

    @pl.when(jt == 0)
    def _warm_dma():
        for c in _page_copies(0, 0):
            c.start()

    @pl.when(jt + 1 < meta.nt)
    def _start_next():
        for c in _page_copies(jt + 1, jax.lax.rem(jt + 1, 2)):
            c.start()

    for c in _page_copies(jt, slot):
        c.wait()

    if meta.kv_quant:
        k_all = (kbuf[slot].astype(jnp.float32)
                 * ksbuf[slot].astype(jnp.float32)[..., None])
        v_all = (vbuf[slot].astype(jnp.float32)
                 * vsbuf[slot].astype(jnp.float32)[..., None])
        k_all = k_all.reshape(P * BS, Hkv, D)
        v_all = v_all.reshape(P * BS, Hkv, D)
    else:
        k_all = kbuf[slot].reshape(P * BS, Hkv, D).astype(jnp.float32)
        v_all = vbuf[slot].reshape(P * BS, Hkv, D).astype(jnp.float32)
    t_pos = jt * (P * BS) + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, P * BS), 2)                       # [1, 1, T]
    valid = t_pos < start
    for kv in range(Hkv):
        sl = slice(kv * G, (kv + 1) * G)
        qh = q_scr[sl]                                      # [G, Ts, D]
        s = jax.lax.dot_general(qh, k_all[:, kv, :],
                                (((2,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(valid, s * meta.scale, NEG_INF)       # [G, Ts, T]
        m_prev = m_scr[sl]                                  # [G, Ts]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        pw = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[sl] = alpha * l_scr[sl] + jnp.sum(pw, axis=-1)
        acc_scr[sl] = acc_scr[sl] * alpha[..., None] + jax.lax.dot_general(
            pw, v_all[:, kv, :], (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[sl] = m_new

    # ---- epilogue: fold the IN-CHUNK tokens under the causal mask,
    # then proj/norm/FFN for the whole tile ---------------------------
    @pl.when(jt == meta.nt - 1)
    def _epi():
        qi = jax.lax.broadcasted_iota(jnp.int32, (Ts, Ts), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (Ts, Ts), 1)
        causal = (ki <= qi)[None, :, :]                     # [1, Ts, Ts]
        heads = []
        for kv in range(Hkv):
            sl = slice(kv * G, (kv + 1) * G)
            qh = q_scr[sl]                                  # [G, Ts, D]
            s = jax.lax.dot_general(qh, kn_scr[kv],
                                    (((2,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(causal, s * meta.scale, NEG_INF)  # [G, Ts, Ts]
            m_prev = m_scr[sl]
            m_f = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            pw = jnp.exp(s - m_f[..., None])
            alpha = jnp.exp(m_prev - m_f)
            l_f = alpha * l_scr[sl] + jnp.sum(pw, axis=-1)
            acc_f = acc_scr[sl] * alpha[..., None] \
                + jax.lax.dot_general(pw, vn_scr[kv],
                                      (((2,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            heads.append(acc_f / jnp.maximum(l_f, 1e-30)[..., None])
        attn = jnp.concatenate(heads, axis=0)               # [Hq, Ts, D]
        attn = attn.transpose(1, 0, 2).reshape(Ts, Hq * D)
        x = x_ref[:].astype(jnp.float32)                    # [Ts, H]
        proj = _mmw(attn, w,
                    "proj_w" if meta.fused_qkv else "o_w", meta)
        if meta.bias:
            proj = proj + w["proj_b"][:][None, :]
        x2 = x + proj
        y2 = _norm_rows(x2, w["ln2_w"][:],
                        w["ln2_b"][:] if meta.fused_qkv else None, meta)
        if meta.activation == "swiglu":
            f = jax.nn.silu(_mmw(y2, w, "gate_w", meta)) \
                * _mmw(y2, w, "up_w", meta)
            o = _mmw(f, w, "down_w", meta)
        else:
            h = jax.nn.gelu(_mmw(y2, w, "fc1_w", meta)
                            + w["fc1_b"][:][None, :], approximate=True)
            o = _mmw(h, w, "fc2_w", meta) + w["fc2_b"][:][None, :]
        x_out_ref[:] = (x2 + o).astype(x_out_ref.dtype)


# ---------------------------------------------------------------------------
# host wrapper + autotune
# ---------------------------------------------------------------------------
def _fitting_candidates(spec, chunk: int, mb: int, pool_itemsize: int,
                        wbytes: int, x_itemsize: int,
                        kv_quant: bool = False) -> Tuple[int, ...]:
    """Page-chunk candidates the cost model says can fit this chunk
    length — provably-overflowing ones never reach the tuner; the floor
    convention is the decode kernel's (``_floor_candidates``)."""
    cands = tuple(
        p for p in _PAGE_CANDIDATES
        if p <= max(mb, 1)
        and _vmem_total(spec, p, chunk, wbytes, pool_itemsize,
                        x_itemsize, kv_quant) <= VMEM_BUDGET_BYTES)
    return _floor_candidates(cands)


def _tuned_pages(spec, lp, pool_k, mb: int, chunk: int, args) -> int:
    from .autotune import FLAGS, lookup, pick
    keys = _param_keys(spec, lp)
    wbytes = sum(lp[n].size * lp[n].dtype.itemsize for n in keys)
    x_isz = lp[keys[0]].dtype.itemsize
    kvq = is_quantized_pool(pool_k)
    p_isz = _pool_itemsize(pool_k)
    pool_dt = ("int8+scale" if kvq else str(pool_k.dtype))
    cands = _fitting_candidates(spec, chunk, mb, p_isz, wbytes, x_isz,
                                kvq)
    default = max(p for p in cands if p <= DEFAULT_PAGES)
    key = (chunk, spec.hidden, spec.num_heads, spec.kv_heads,
           spec.head_dim, spec.block_size, mb, spec.activation, pool_dt,
           getattr(spec, "weight_dtype", None),
           getattr(spec, "group_size", -1))
    if not FLAGS.use_autotune:
        return default
    if isinstance(args[0], jax.core.Tracer):
        return lookup("prefill_block", key, default)

    def run(cand):
        return jax.jit(functools.partial(_call, spec=spec,
                                         pages=int(cand)))

    return int(pick("prefill_block", key, cands, run, args, default,
                    valid=lambda p: _vmem_total(
                        spec, int(p), chunk, wbytes, p_isz, x_isz, kvq)
                    <= VMEM_BUDGET_BYTES))


def _call(x, lp, pool_k, pool_v, bt_row, start, cos, sin, *, spec,
          pages: int, scale: Optional[float] = None):
    """Build + invoke the pallas_call for a fixed page-chunk size;
    returns (x_out [Ts, H], k_new, v_new [Ts, Hkv, D]) — the pool
    scatter happens in :func:`prefill_block_pallas` so pool semantics
    match the per-op tier exactly."""
    _, Ts, H = x.shape
    Hq, Hkv, D = spec.num_heads, spec.kv_heads, spec.head_dim
    BS = spec.block_size
    mb = bt_row.shape[0]
    nt = -(-mb // pages)
    keys = _param_keys(spec, lp)
    kvq = is_quantized_pool(pool_k)
    meta = _Meta(hidden=H, num_heads=Hq, kv_heads=Hkv, head_dim=D,
                 block_size=BS, norm=spec.norm,
                 activation=spec.activation, eps=spec.eps,
                 rope=spec.rope, fused_qkv=spec.fused_qkv,
                 bias=spec.bias, pages=pages, nt=nt, mb=mb, chunk=Ts,
                 scale=(scale if scale is not None
                        else 1.0 / (D ** 0.5)),
                 weight_dtype=getattr(spec, "weight_dtype", None),
                 group_size=getattr(spec, "group_size", -1),
                 kv_quant=kvq, param_keys=keys)

    def wspec(arr):
        if arr.ndim == 1:
            return pl.BlockSpec((arr.shape[0],), lambda j: (0,))
        return pl.BlockSpec(arr.shape, lambda j: (0,) * arr.ndim)

    n_pool = 4 if kvq else 2
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),       # start (prefix len)
        pl.BlockSpec(memory_space=pltpu.SMEM),       # block-table row
        pl.BlockSpec((Ts, H), lambda j: (0, 0)),     # residual tile
        pl.BlockSpec((Ts, D), lambda j: (0, 0)),     # cos rows
        pl.BlockSpec((Ts, D), lambda j: (0, 0)),     # sin rows
        *[wspec(lp[n]) for n in keys],
        pl.BlockSpec(memory_space=pltpu.ANY),        # pool_k (codes)
        pl.BlockSpec(memory_space=pltpu.ANY),        # pool_v (codes)
        *[pl.BlockSpec(memory_space=pltpu.ANY)] * (n_pool - 2),
    ]
    # quantized pools output fp32 k/v tiles (the host scatter
    # re-quantizes them, so pool contents match the reference tier's)
    kv_dt = jnp.float32 if kvq else pool_k.dtype
    out_specs = [
        pl.BlockSpec((Ts, H), lambda j: (0, 0)),
        pl.BlockSpec((Ts, Hkv, D), lambda j: (0, 0, 0)),
        pl.BlockSpec((Ts, Hkv, D), lambda j: (0, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((Ts, H), x.dtype),
        jax.ShapeDtypeStruct((Ts, Hkv, D), kv_dt),
        jax.ShapeDtypeStruct((Ts, Hkv, D), kv_dt),
    ]
    pool_dt = pool_k.data.dtype if kvq else pool_k.dtype
    scratch = [
        pltpu.VMEM((Hq, Ts, D), jnp.float32),        # q tile
        pltpu.VMEM((Hkv, Ts, D), jnp.float32),       # in-chunk k
        pltpu.VMEM((Hkv, Ts, D), jnp.float32),       # in-chunk v
        pltpu.VMEM((Hq, Ts), jnp.float32),           # running max
        pltpu.VMEM((Hq, Ts), jnp.float32),           # running sum
        pltpu.VMEM((Hq, Ts, D), jnp.float32),        # attn accumulator
        # two revolving DMA slots (cost.DMA_STAGING_SLOTS)
        pltpu.VMEM((2, pages, BS, Hkv, D), pool_dt),
        pltpu.VMEM((2, pages, BS, Hkv, D), pool_dt),
    ]
    if kvq:
        scratch += [
            pltpu.VMEM((2, pages, BS, Hkv), jnp.float32),   # k scales
            pltpu.VMEM((2, pages, BS, Hkv), jnp.float32),   # v scales
        ]
    pools = ((pool_k.data, pool_v.data, pool_k.scale, pool_v.scale)
             if kvq else (pool_k, pool_v))
    cos2 = jnp.zeros((Ts, D), x.dtype) if cos is None else cos
    sin2 = jnp.zeros((Ts, D), x.dtype) if sin is None else sin
    return pl.pallas_call(
        functools.partial(_kernel, meta=meta),
        grid=(nt,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[*scratch,
                        pltpu.SemaphoreType.DMA((2, pages, n_pool))],
        interpret=use_interpret(),
    )(jnp.reshape(jnp.asarray(start, jnp.int32), (1,)),
      jnp.asarray(bt_row, jnp.int32), x[0], cos2, sin2,
      *[lp[n] for n in keys], *pools)


def prefill_block_pallas(x, lp, pool_k, pool_v, blk, off, bt_row, mask,
                         cos, sin, *, spec, start,
                         scale: Optional[float] = None,
                         pages: Optional[int] = None):
    """The megakernel tier of ``ops.decode_block.prefill_block`` —
    returns ``(x_out [1, Ts, H], pool_k, pool_v)`` with the tile's KV
    scattered at ``blk``/``off`` (the scatter runs host-side on the
    kernel's k/v outputs, so pool contents — including the dropped
    out-of-range writes of bucket-padded rows — are IDENTICAL to the
    per-op tier's ``.at[blk, off].set``).  ``mask`` is unused: the
    kernel derives causality from ``start`` and the tile positions."""
    del mask
    if pages is None:
        pages = _tuned_pages(spec, lp, pool_k, bt_row.shape[0],
                             x.shape[1],
                             (x, lp, pool_k, pool_v, bt_row, start,
                              cos, sin))
    x_out, k_new, v_new = _call(x, lp, pool_k, pool_v, bt_row, start,
                                cos, sin, spec=spec, pages=int(pages),
                                scale=scale)
    if is_quantized_pool(pool_k):
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        pool_k = QuantizedKVPool(data=pool_k.data.at[blk, off].set(kq),
                                 scale=pool_k.scale.at[blk, off].set(ks))
        pool_v = QuantizedKVPool(data=pool_v.data.at[blk, off].set(vq),
                                 scale=pool_v.scale.at[blk, off].set(vs))
    else:
        pool_k = pool_k.at[blk, off].set(k_new.astype(pool_k.dtype))
        pool_v = pool_v.at[blk, off].set(v_new.astype(pool_v.dtype))
    return x_out[None], pool_k, pool_v


def tune_prefill_block(x, lp, pool_k, pool_v, blk, off, bt_row, mask,
                       cos, sin, *, spec, start,
                       scale: Optional[float] = None):
    """Eagerly time the page-chunk candidates for this geometry and
    cache the winner under the ``"prefill_block"`` autotune key
    (FLAGS.use_autotune must be on) — run once at engine warmup; traced
    calls then read the cache."""
    return prefill_block_pallas(x, lp, pool_k, pool_v, blk, off, bt_row,
                                mask, cos, sin, spec=spec, start=start,
                                scale=scale)
