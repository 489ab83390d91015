"""Pallas TPU megakernel for the fused decode-step transformer block.

One kernel invocation runs ONE layer for one decode token per sequence:
norm → qkv projection → RoPE at the absolute position → paged-KV
attention over the engine's block table → out-projection + residual →
norm → FFN → residual.  The ``[1, H]`` residual stream, the projected
q/k/v, and the online-softmax state live in VMEM scratch for the whole
layer — the only HBM traffic is the weights (streamed once), the KV
pages the attention DMA-gathers through the block table, and the final
``[1, H]`` write-back.  Per-op decode pays ~2 reads + 2 writes of the
residual stream per fusion boundary on top of that; this kernel pays
zero (docs/performance.md has the per-token byte math).

Shape of the kernel:

* grid ``(B, nt)`` — one sequence per outer step, ``nt`` page-chunks of
  the sequence's block-table row inner; scratch accumulators carry the
  flash-style online softmax across chunks (same scheme as
  ``decode_attention.py``).
* the prologue (norm/qkv/rope) runs at chunk 0, writing q and the new
  token's k/v to scratch; pages DMA-copy from the ``ANY``-space pools
  into a revolving TWO-SLOT staging buffer — each grid step starts the
  NEXT chunk's copies into the other slot before waiting on its own, so
  the page DMA overlaps the flash accumulation (the cost model's 2x
  staging term, ``cost.DMA_STAGING_SLOTS``); the epilogue at the last
  chunk folds in the CURRENT token's k/v (the pool append happens
  host-side after the kernel, so the value math matches the per-op
  order append-then-attend), then runs out-proj, norm, FFN and both
  residual adds.
* pages per chunk is the autotuned knob (``"decode_block"`` key in
  ``ops/pallas/autotune``).

Limits (the dispatch in ``ops/decode_block.py`` falls back to the
reference tier outside them, or raises the typed error when the kernel
is forced): the layer's full weight set plus the page staging buffers
must fit :data:`VMEM_BUDGET_BYTES`, and ``head_dim`` is capped at
:data:`MAX_HEAD_DIM`.  Models past the budget (7B-class layers) need
the multi-core fusion of FlashFuser — single-kernel fusion is the
small/draft-model and distilled-serving tier.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...analysis.kernel import cost
from ..paged_kv import KV_SCALE_EPS, is_quantized_pool
from .common import NEG_INF, use_interpret

__all__ = ["decode_block_pallas", "tune_decode_block",
           "unsupported_reason", "VMEM_BUDGET_BYTES", "MAX_HEAD_DIM"]

# Both limits come from the shared cost model (ISSUE 10): the number
# the static analyzer (KL001) proves things about is the number this
# dispatch enforces.  Kept as module attrs so tests/operators can tune
# the budget without touching the global table.
VMEM_BUDGET_BYTES = cost.budget_bytes()
MAX_HEAD_DIM = cost.MAX_HEAD_DIM
DEFAULT_PAGES = 8
_PAGE_CANDIDATES = (1, 2, 4, 8, 16)


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
    nt: int              # number of chunks (grid inner length)
    mb: int              # block-table width
    scale: float
    weight_dtype: Optional[str] = None   # weight-only quant storage
    group_size: int = -1                 # scale grouping along K
    kv_quant: bool = False               # int8 pool + fp32 scale pages
    param_keys: Tuple[str, ...] = ()     # actual lp keys, ref order


# The matmul weights of both layouts — the leaves weight-only
# quantization replaces with ``__q``/``__s`` pairs (norm gains and
# biases always stream full width).
_MATMUL_NAMES = frozenset(("q_w", "k_w", "v_w", "o_w", "gate_w", "up_w",
                           "down_w", "qkv_w", "proj_w", "fc1_w", "fc2_w"))


def _weight_names(spec) -> Tuple[str, ...]:
    if spec.fused_qkv:
        return ("ln1_w", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
                "ln2_w", "ln2_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b")
    return ("ln1_w", "q_w", "k_w", "v_w", "o_w", "ln2_w", "gate_w",
            "up_w", "down_w")


def _param_keys(spec, lp=()) -> Tuple[str, ...]:
    """The keys of layer dict ``lp`` the kernel streams, in ref order:
    matmul weights expand to (codes, scales) pairs under weight-only
    quant; a full-width weight that ``lp`` stores ``[N, K]`` under
    ``name + "t"`` (``ops.decode_block.serving_layout``: a serving
    engine's q/k/v) streams under that key and is contracted as it
    lies (:func:`_mmw`)."""
    wdt = getattr(spec, "weight_dtype", None)
    keys = []
    for n in _weight_names(spec):
        if wdt is not None and n in _MATMUL_NAMES:
            keys.extend((n + "__q", n + "__s"))
        elif n not in lp and n + "t" in lp:
            keys.append(n + "t")
        else:
            keys.append(n)
    return tuple(keys)


def _vmem_total(spec, pages: int, wbytes: int, pool_itemsize: int,
                x_itemsize: int, kv_quant: bool = False) -> int:
    """One layer invocation's VMEM bytes — the shared cost model's
    number (analysis/kernel/cost.py), never a local formula."""
    return cost.decode_block_vmem(
        hidden=spec.hidden, num_heads=spec.num_heads,
        kv_heads=spec.kv_heads, head_dim=spec.head_dim,
        block_size=spec.block_size, pages=pages, weight_bytes=wbytes,
        pool_itemsize=pool_itemsize, x_itemsize=x_itemsize,
        kv_quant=kv_quant)["total"]


def _pool_itemsize(pool_k) -> int:
    return (pool_k.data.dtype.itemsize if is_quantized_pool(pool_k)
            else pool_k.dtype.itemsize)


def unsupported_reason(spec, lp, pool_k) -> Optional[str]:
    """None when this layer fits the kernel, else the reason (the
    ``ops/decode_block.py`` dispatch signal).  Layout checks (a dense
    layer dict) live here; every byte/cap limit is delegated to the
    shared cost model so the static KL001 analysis and this runtime
    gate cannot drift.

    Weight bytes are measured from the ACTUAL leaves — under
    weight-only quant the ``__q`` int8 codes (int4: packed nibbles,
    half the rows) plus fp32 ``__s`` scales, which is how int8/int4
    provably admits layer widths whose full-width weights overflow the
    budget (the fusion-envelope pin)."""
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
    return cost.decode_block_unsupported_reason(
        hidden=spec.hidden, num_heads=spec.num_heads,
        kv_heads=spec.kv_heads, head_dim=spec.head_dim,
        block_size=spec.block_size, rope=spec.rope, weight_bytes=wbytes,
        pool_itemsize=_pool_itemsize(pool_k),
        x_itemsize=lp[keys[0]].dtype.itemsize,
        kv_quant=is_quantized_pool(pool_k),
        budget=VMEM_BUDGET_BYTES)


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------
def _norm_rows(x, w, b, meta: _Meta):
    """fp32 row norm ([1, H]) matching the reference-tier closures."""
    if meta.norm == "rms":
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + meta.eps) * w[None, :]
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return xc * jax.lax.rsqrt(var + meta.eps) * w[None, :] + b[None, :]


def _mm(a32, w_ref, w_contract: int = 0):
    """[1, n] fp32 × weight ref [n, m] → [1, m] fp32 (MXU dot in the
    weight's storage dtype, fp32 accumulation — the per-op precision);
    ``w_contract=1``: the ref holds the weight ``[m, n]``."""
    w = w_ref[:]
    return jax.lax.dot_general(a32.astype(w.dtype), w,
                               (((1,), (w_contract,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot32(a32, w32):
    return jax.lax.dot_general(a32, w32, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _mm_quant(a32, q_ref, s_ref, meta: "_Meta"):
    """Dequant-in-kernel matmul over the ``quant_linear`` scale layout:
    per-channel scales post-multiply the int-code dot (fp32 accum),
    grouped scales dequantize the VMEM-resident tile first — the same
    split the reference tier's ``make_mm`` makes, so the two tiers share
    one numeric structure."""
    K = a32.shape[-1]
    wq = q_ref[:]
    if meta.weight_dtype == "int4":
        # halves packing: rows [0, K/2) in the low nibble, [K/2, K) in
        # the high nibble; arithmetic shifts sign-extend
        lo = (wq << 4).astype(jnp.int8) >> 4
        hi = wq >> 4
        wq = jnp.concatenate([lo, hi], axis=0)[:K]
    s = s_ref[:].astype(jnp.float32)
    if meta.group_size == -1:
        return _dot32(a32, wq.astype(jnp.float32)) * s[None, :]
    srow = jnp.repeat(s, meta.group_size, axis=0)[:K]
    return _dot32(a32, wq.astype(jnp.float32) * srow)


def _mmw(a32, w, name, meta: "_Meta"):
    """Matmul against logical weight ``name`` — full width or the
    quantized (codes, scales) pair, decided by the spec."""
    if meta.weight_dtype is None:
        if name + "t" in w:
            return _mm(a32, w[name + "t"], 1)
        return _mm(a32, w[name])
    return _mm_quant(a32, w[name + "__q"], w[name + "__s"], meta)


def _rot_half(x):
    d2 = x.shape[-1] // 2
    return jnp.concatenate([-x[..., d2:], x[..., :d2]], axis=-1)


def _kernel(*refs, meta: _Meta):
    nw = len(meta.param_keys)
    np_ = 4 if meta.kv_quant else 2
    bt_ref, len_ref, x_ref, cos_ref, sin_ref = refs[:5]
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

    b = pl.program_id(0)
    jt = pl.program_id(1)
    Hq, Hkv, D = meta.num_heads, meta.kv_heads, meta.head_dim
    G = Hq // Hkv
    P, BS = meta.pages, meta.block_size
    length = len_ref[b]

    # ---- prologue: norm1 + qkv + rope, once per sequence -------------
    @pl.when(jt == 0)
    def _pro():
        x = x_ref[:].astype(jnp.float32)                    # [1, H]
        y = _norm_rows(x, w["ln1_w"][:],
                       w["ln1_b"][:] if meta.fused_qkv else None, meta)
        if meta.fused_qkv:
            z = _mmw(y, w, "qkv_w", meta) + w["qkv_b"][:][None, :]
            z = z.reshape(Hq, 3 * D)
            q, k, v = z[:, :D], z[:, D:2 * D], z[:, 2 * D:]
        else:
            q = _mmw(y, w, "q_w", meta).reshape(Hq, D)
            k = _mmw(y, w, "k_w", meta).reshape(Hkv, D)
            v = _mmw(y, w, "v_w", meta).reshape(Hkv, D)
        if meta.rope:
            cos = cos_ref[:].astype(jnp.float32)            # [1, D]
            sin = sin_ref[:].astype(jnp.float32)
            q = q * cos + _rot_half(q) * sin
            k = k * cos + _rot_half(k) * sin
        q_scr[:] = q
        if meta.kv_quant:
            # fold the int8-ROUND-TRIPPED new-token k/v: the host-side
            # append quantizes these rows into the pool, so attending
            # the stored value (not the full-precision one) keeps this
            # step bit-consistent with the XLA tier and with what every
            # future step reads back
            ks = jnp.maximum(jnp.max(jnp.abs(k), axis=-1,
                                     keepdims=True),
                             KV_SCALE_EPS) / 127.0
            vs = jnp.maximum(jnp.max(jnp.abs(v), axis=-1,
                                     keepdims=True),
                             KV_SCALE_EPS) / 127.0
            kn_scr[:] = jnp.clip(jnp.round(k / ks), -127, 127) * ks
            vn_scr[:] = jnp.clip(jnp.round(v / vs), -127, 127) * vs
        else:
            kn_scr[:] = k
            vn_scr[:] = v
        kn_ref[:] = k.reshape(1, Hkv, D).astype(kn_ref.dtype)
        vn_ref[:] = v.reshape(1, Hkv, D).astype(vn_ref.dtype)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # ---- attention chunk: double-buffered page DMA — chunk jt's copies
    # were started one grid step earlier (chunk 0's in the prologue
    # step); start chunk jt+1's into the OTHER slot before waiting, so
    # the next pages stream while this chunk's flash accumulation runs -
    def _page_copies(ct, slot):
        copies = []
        for p in range(P):
            idx = jnp.minimum(ct * P + p, meta.mb - 1)
            phys = jnp.maximum(bt_ref[b, idx], 0)
            copies += [pltpu.make_async_copy(pool_k_ref.at[phys],
                                             kbuf.at[slot, p],
                                             sem.at[slot, p, 0]),
                       pltpu.make_async_copy(pool_v_ref.at[phys],
                                             vbuf.at[slot, p],
                                             sem.at[slot, p, 1])]
            if meta.kv_quant:
                # per-(token, head) fp32 scale rows ride the page walk
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
        jnp.int32, (1, P * BS), 1)                          # [1, T]
    valid = t_pos < length
    for kv in range(Hkv):
        sl = slice(kv * G, (kv + 1) * G)
        qh = q_scr[sl]                                      # [G, D]
        s = jax.lax.dot_general(qh, k_all[:, kv, :],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(valid, s * meta.scale, NEG_INF)       # [G, T]
        m_prev = m_scr[sl]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        pw = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[sl] = alpha * l_scr[sl] + jnp.sum(pw, axis=1,
                                                keepdims=True)
        acc_scr[sl] = acc_scr[sl] * alpha + jax.lax.dot_general(
            pw, v_all[:, kv, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[sl] = m_new

    # ---- epilogue: fold the CURRENT token, then proj/norm/FFN --------
    @pl.when(jt == meta.nt - 1)
    def _epi():
        heads = []
        for kv in range(Hkv):
            sl = slice(kv * G, (kv + 1) * G)
            qh = q_scr[sl]
            s_new = jnp.sum(qh * kn_scr[kv][None, :], axis=1,
                            keepdims=True) * meta.scale     # [G, 1]
            m_prev = m_scr[sl]
            m_f = jnp.maximum(m_prev, s_new)
            alpha = jnp.exp(m_prev - m_f)
            p_new = jnp.exp(s_new - m_f)
            l_f = alpha * l_scr[sl] + p_new
            acc_f = acc_scr[sl] * alpha \
                + p_new * vn_scr[kv][None, :]
            heads.append(acc_f / jnp.maximum(l_f, 1e-30))
        attn = jnp.concatenate(heads, axis=0)               # [Hq, D]
        x = x_ref[:].astype(jnp.float32)                    # [1, H]
        proj = _mmw(attn.reshape(1, Hq * D), w,
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
def _floor_candidates(cands) -> Tuple[int, ...]:
    """The ONE candidate-floor convention both block kernels share
    (decode_block here, prefill_block in its twin module): when the fit
    filter rejects every page-chunk size, degrade to single-page
    staging rather than returning an empty tuple — whether the kernel
    runs at all is the ``unsupported_reason`` gate's decision, never an
    empty candidate list's."""
    return tuple(cands) or (1,)


def _fitting_candidates(spec, mb: int, pool_itemsize: int, wbytes: int,
                        x_itemsize: int,
                        kv_quant: bool = False) -> Tuple[int, ...]:
    """Page-chunk candidates the cost model says can fit — the
    provably-overflowing ones never reach the tuner (KL005's runtime
    half).  Quantized candidates (int8/int4 weights, int8 KV) filter
    through the dtype-aware model the same way."""
    cands = tuple(
        p for p in _PAGE_CANDIDATES
        if p <= max(mb, 1)
        and _vmem_total(spec, p, wbytes, pool_itemsize, x_itemsize,
                        kv_quant) <= VMEM_BUDGET_BYTES)
    return _floor_candidates(cands)


def _tuned_pages(spec, lp, pool_k, mb: int, args) -> int:
    from .autotune import FLAGS, lookup, pick
    keys = _param_keys(spec, lp)
    wbytes = sum(lp[n].size * lp[n].dtype.itemsize for n in keys)
    x_isz = lp[keys[0]].dtype.itemsize
    kvq = is_quantized_pool(pool_k)
    p_isz = _pool_itemsize(pool_k)
    pool_dt = ("int8+scale" if kvq else str(pool_k.dtype))
    cands = _fitting_candidates(spec, mb, p_isz, wbytes, x_isz, kvq)
    default = max(p for p in cands if p <= DEFAULT_PAGES)
    key = (spec.hidden, spec.num_heads, spec.kv_heads, spec.head_dim,
           spec.block_size, mb, spec.activation, pool_dt,
           getattr(spec, "weight_dtype", None),
           getattr(spec, "group_size", -1))
    if not FLAGS.use_autotune:
        return default
    if isinstance(args[0], jax.core.Tracer):
        return lookup("decode_block", key, default)

    def run(cand):
        return jax.jit(functools.partial(_call, spec=spec,
                                         pages=int(cand)))

    return int(pick("decode_block", key, cands, run, args, default,
                    valid=lambda p: _vmem_total(
                        spec, int(p), wbytes, p_isz, x_isz, kvq)
                    <= VMEM_BUDGET_BYTES))


def _call(x, lp, pool_k, pool_v, block_table, lengths, cos, sin, *,
          spec, pages: int):
    """Build + invoke the pallas_call for a fixed page-chunk size;
    returns (x_out, k_new, v_new) — the pool append happens in
    :func:`decode_block_pallas` so pool semantics match the per-op
    tier exactly."""
    B, H = x.shape
    Hq, Hkv, D = spec.num_heads, spec.kv_heads, spec.head_dim
    BS = spec.block_size
    mb = block_table.shape[1]
    nt = -(-mb // pages)
    keys = _param_keys(spec, lp)
    kvq = is_quantized_pool(pool_k)
    meta = _Meta(hidden=H, num_heads=Hq, kv_heads=Hkv, head_dim=D,
                 block_size=BS, norm=spec.norm,
                 activation=spec.activation, eps=spec.eps,
                 rope=spec.rope, fused_qkv=spec.fused_qkv,
                 bias=spec.bias, pages=pages, nt=nt, mb=mb,
                 scale=1.0 / (D ** 0.5),
                 weight_dtype=getattr(spec, "weight_dtype", None),
                 group_size=getattr(spec, "group_size", -1),
                 kv_quant=kvq, param_keys=keys)

    def wspec(arr):
        if arr.ndim == 1:
            return pl.BlockSpec((arr.shape[0],), lambda b, j: (0,))
        return pl.BlockSpec(arr.shape, lambda b, j: (0,) * arr.ndim)

    n_pool = 4 if kvq else 2
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),       # block table
        pl.BlockSpec(memory_space=pltpu.SMEM),       # lengths
        pl.BlockSpec((None, 1, H), lambda b, j: (b, 0, 0)),   # x row
        pl.BlockSpec((None, 1, D), lambda b, j: (b, 0, 0)),   # cos row
        pl.BlockSpec((None, 1, D), lambda b, j: (b, 0, 0)),   # sin row
        *[wspec(lp[n]) for n in keys],
        pl.BlockSpec(memory_space=pltpu.ANY),        # pool_k (codes)
        pl.BlockSpec(memory_space=pltpu.ANY),        # pool_v (codes)
        *[pl.BlockSpec(memory_space=pltpu.ANY)] * (n_pool - 2),  # kv scales
    ]
    # quantized pools output fp32 k/v rows (the host paged_append
    # re-quantizes them, so pool contents match the reference tier's)
    kv_dt = jnp.float32 if kvq else pool_k.dtype
    out_specs = [
        pl.BlockSpec((None, 1, H), lambda b, j: (b, 0, 0)),
        pl.BlockSpec((1, Hkv, D), lambda b, j: (b, 0, 0)),
        pl.BlockSpec((1, Hkv, D), lambda b, j: (b, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, 1, H), x.dtype),
        jax.ShapeDtypeStruct((B, Hkv, D), kv_dt),
        jax.ShapeDtypeStruct((B, Hkv, D), kv_dt),
    ]
    pool_dt = pool_k.data.dtype if kvq else pool_k.dtype
    scratch = [
        pltpu.VMEM((Hq, D), jnp.float32),            # q
        pltpu.VMEM((Hkv, D), jnp.float32),           # new k
        pltpu.VMEM((Hkv, D), jnp.float32),           # new v
        pltpu.VMEM((Hq, 1), jnp.float32),            # running max
        pltpu.VMEM((Hq, 1), jnp.float32),            # running sum
        pltpu.VMEM((Hq, D), jnp.float32),            # attn accumulator
        # two revolving DMA slots (cost.DMA_STAGING_SLOTS): chunk jt
        # accumulates out of slot jt % 2 while jt+1 streams into the
        # other
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
    cos2 = jnp.zeros((B, D), x.dtype) if cos is None else cos
    sin2 = jnp.zeros((B, D), x.dtype) if sin is None else sin
    x_out, k_new, v_new = pl.pallas_call(
        functools.partial(_kernel, meta=meta),
        grid=(B, nt),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[*scratch,
                        pltpu.SemaphoreType.DMA((2, pages, n_pool))],
        interpret=use_interpret(),
    )(jnp.asarray(block_table, jnp.int32),
      jnp.asarray(lengths, jnp.int32), x[:, None], cos2[:, None],
      sin2[:, None], *[lp[n] for n in keys], *pools)
    return x_out[:, 0], k_new, v_new


def decode_block_pallas(x, lp, pool_k, pool_v, block_table, lengths, cos,
                        sin, *, spec, pages: Optional[int] = None):
    """The megakernel tier of ``ops.decode_block.decode_block`` —
    returns ``(x_out, pool_k, pool_v)`` with the new token's KV
    appended (append runs host-side on the kernel's k/v outputs, so the
    pool contents are IDENTICAL to the per-op tier's
    ``paged_append``)."""
    from ..paged_kv import paged_append
    if pages is None:
        pages = _tuned_pages(spec, lp, pool_k, block_table.shape[1],
                             (x, lp, pool_k, pool_v, block_table,
                              lengths, cos, sin))
    x_out, k_new, v_new = _call(x, lp, pool_k, pool_v, block_table,
                                lengths, cos, sin, spec=spec,
                                pages=int(pages))
    pool_k, pool_v = paged_append(pool_k, pool_v, k_new, v_new,
                                  block_table, lengths, spec.block_size)
    return x_out, pool_k, pool_v


def tune_decode_block(x, lp, pool_k, pool_v, block_table, lengths, cos,
                      sin, *, spec):
    """Eagerly time the page-chunk candidates for this geometry and
    cache the winner under the ``"decode_block"`` autotune key
    (FLAGS.use_autotune must be on) — run once at engine warmup; traced
    calls then read the cache."""
    return decode_block_pallas(x, lp, pool_k, pool_v, block_table,
                               lengths, cos, sin, spec=spec)
