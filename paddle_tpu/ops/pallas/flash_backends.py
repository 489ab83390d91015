"""Flash-attention backend selection: in-tree kernel vs platform-tuned.

The reference does NOT hand-roll its production flash kernel — it dynloads
an external tuned library (/root/reference/paddle/phi/kernels/gpu/
flash_attn_kernel.cu:536 via backends/dynload/flashattn.h:19) and keeps a
per-shape dispatch layer in front of it.  The TPU analog of that tuned
library is the Pallas kernel suite that ships inside JAX itself
(``jax.experimental.pallas.ops.tpu.flash_attention`` and
``splash_attention``).  Those kernels ship UNTUNED: the splash kernel's
default tiles are a 128 x 128 placeholder under the library's own
"TODO: Select better parameters based on a heuristic", and at that
default the train cell's attention ran at 4% of its roofline until PR 33.
The tiles it runs at here are this module's (:func:`splash_block_sizes`),
chosen from the call's shapes at caps read on a TPU v5e with JAX 0.9.0
(2026-10-03; the table is in PERF.md, section 6, PR 33).  This module is
also the dispatch layer: it exposes :func:`tuned_flash` which picks, per
shape signature, one of

* ``ours``      — the first-party kernel (flash_attention.py): full feature
                  set (GQA-native, segment ids, bias, lse out) and the only
                  backend that runs in interpret mode on CPU;
* ``jax_flash`` — the platform flash kernel (equal-head MHA; GQA served by
                  repeating KV heads), at its library defaults;
* ``splash``    — the platform splash kernel (causal/full masks, segment
                  ids, native grouped-KV via its MQA form), at this
                  module's tiles.

Selection is by a static order (``available_backends``) unless
``FLAGS.use_autotune`` is on (ops/pallas/autotune.py: timed fwd+bwd once
per unseen shape, winners persisted; its table holds no TPU entry),
mirroring the reference's per-shape flash/mem-efficient/math dispatch
(python/paddle/nn/functional/flash_attention.py:976).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .common import use_interpret

__all__ = ["tuned_flash", "available_backends", "run_backend"]


# ---------------------------------------------------------------------------
# backend wrappers — all take/return the paddle [B, S, H, D] layout and are
# differentiable end to end (each underlying kernel defines its own VJP)
# ---------------------------------------------------------------------------

def _ours(q, k, v, scale, causal, seg_q=None, seg_k=None, bias=None):
    from .flash_attention import flash_attention
    return flash_attention(q, k, v, scale, causal, segment_ids=seg_q,
                           kv_segment_ids=seg_k, bias=bias)


def _jax_flash(q, k, v, scale, causal, seg_q=None, seg_k=None, bias=None):
    from jax.experimental.pallas.ops.tpu import flash_attention as _fa
    Hq, Hkv = q.shape[2], k.shape[2]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if Hq != Hkv:                       # GQA: the platform kernel is
        g = Hq // Hkv                   # equal-heads only — repeat KV
        kt = jnp.repeat(kt, g, axis=1)
        vt = jnp.repeat(vt, g, axis=1)
    seg = None
    if seg_q is not None:
        seg = _fa.SegmentIds(q=seg_q.astype(jnp.int32),
                             kv=seg_k.astype(jnp.int32))
    ab = None
    if bias is not None:
        ab = jnp.broadcast_to(
            bias, (q.shape[0], Hq, q.shape[1], k.shape[1])).astype(q.dtype)
    out = _fa.flash_attention(qt, kt, vt, ab=ab, segment_ids=seg,
                              causal=causal, sm_scale=float(scale))
    return jnp.swapaxes(out, 1, 2)


# The splash kernel's tiles.  The library ships none: without
# ``block_sizes`` it runs ``BlockSizes.get_default()``, 128 for all eight
# under its own "TODO: Select better parameters", and at 128 x 128 the
# kernel is bound by stepping its grid (~0.75 us a step), not by the MXU.
# The caps below were read on one TPU v5e with JAX 0.9.0 (PR 33,
# 2026-10-03; the table is in PERF.md, section 6): a kernel's ms a call
# in the profiler's trace of ``jax.grad`` of ``run_backend("splash", ...)``,
# bf16, causal, at the train cell's [4, 2048, 32/8, 128]; the same caps
# were best, or within 2.5% of the best call, at [2, 4096, 32/8, 128],
# [8, 1024, 32/8, 128], [16, 512, 32/8, 128], [2, 2048, 16/16, 64] and
# [4, 1024, 16/16, 64].  A tile is the largest power of two up to its cap
# that divides its length.
_SPLASH_BLOCK_Q = 1024           # fwd 11.42 ms at 128 -> 1.56 at 1024 x 1024
_SPLASH_BLOCK_KV = 1024          # (512: 1.84, 2048: 1.85; q 2048: 1.92, and
#                                  the compiler refuses 2048 x 2048 for VMEM)
_SPLASH_BLOCK_KV_COMPUTE = 512   # 256: 1.563, 512: 1.557, 1024: 1.720
_SPLASH_BLOCK_Q_DKV = 1024       # fused dkv+dq 2.98 ms at 1024 x 1024 / 512
_SPLASH_BLOCK_KV_DKV = 1024      # (512 x 512: 3.19, 1024 x 2048: 3.70)
_SPLASH_BLOCK_KV_DKV_COMPUTE = 512   # 256: 3.07, 512: 2.98, 1024: 2.95
_SPLASH_BLOCK_Q_DQ = 1024        # the dq kernel alone: 11.24 ms at 128 ->
_SPLASH_BLOCK_KV_DQ = 1024       # 2.00 at 1024 x 1024 (512: 2.24, 2048: 2.20)
# The fused backward (dq from the dkv kernel: 2.98 ms against 2.41 + 2.00
# at the same tiles) writes one partial dq per kv tile, Sk / block_kv_dkv
# times q's bytes, and sums them outside: time it gains at every length,
# memory it takes in proportion to Sk.  Past this many partials the two
# kernels run apart and dq stays one array.
_SPLASH_FUSED_BWD_MAX_PARTIALS = 4
# A tile holds at most the bytes of the 1024 x 128 bf16 rows the caps were
# read at, so wider rows get shorter tiles: at D = 256 (bf16) 512 x 512
# reads 2.59 ms a call against 2.71 at 1024 x 1024, and 1024 float32 rows
# of 256 the compiler refuses outright (scoped VMEM, the dkv and dq kernels).
_SPLASH_TILE_BYTES = 1024 * 128 * 2


def splash_block_sizes(Sq: int, Sk: int, D: int, group: int,
                       itemsize: int = 2):
    """The ``BlockSizes`` the splash kernel is built with, from what the
    call can see: the two lengths and a row's bytes.  ``group`` (1: the
    MHA form, more: the MQA form over a group of q heads) wanted no other
    caps on the v5e."""
    from jax.experimental.pallas.ops.tpu import splash_attention as _sa
    del group
    rows = _SPLASH_TILE_BYTES // (D * itemsize)

    def tile(length, cap):
        # ``length`` is a multiple of 128 (``available_backends``)
        t = 128
        while t * 2 <= min(cap, rows) and length % (t * 2) == 0:
            t *= 2
        return t

    kv = tile(Sk, _SPLASH_BLOCK_KV)
    kv_dkv = tile(Sk, _SPLASH_BLOCK_KV_DKV)
    fused = Sk // kv_dkv <= _SPLASH_FUSED_BWD_MAX_PARTIALS
    dq = {} if fused else dict(block_q_dq=tile(Sq, _SPLASH_BLOCK_Q_DQ),
                               block_kv_dq=tile(Sk, _SPLASH_BLOCK_KV_DQ))
    return _sa.BlockSizes(
        block_q=tile(Sq, _SPLASH_BLOCK_Q),
        block_kv=kv,
        block_kv_compute=min(kv, _SPLASH_BLOCK_KV_COMPUTE),
        block_q_dkv=tile(Sq, _SPLASH_BLOCK_Q_DKV),
        block_kv_dkv=kv_dkv,
        block_kv_dkv_compute=min(kv_dkv, _SPLASH_BLOCK_KV_DKV_COMPUTE),
        use_fused_bwd_kernel=fused, **dq)


def _splash(q, k, v, scale, causal, seg_q=None, seg_k=None, bias=None):
    from jax.experimental.pallas.ops.tpu import splash_attention as _sa
    if bias is not None:
        raise NotImplementedError("splash backend has no bias input")
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    # splash has no sm_scale knob: fold the scale into q
    qt = (jnp.swapaxes(q, 1, 2) * jnp.asarray(scale, q.dtype))
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    mk = _sa.CausalMask((Sq, Sk)) if causal else _sa.FullMask((Sq, Sk))
    seg = None
    if seg_q is not None:
        seg = _sa.SegmentIds(q=seg_q.astype(jnp.int32),
                             kv=seg_k.astype(jnp.int32))
    g = Hq // Hkv
    make = (_sa.make_splash_mha_single_device if g == 1
            else _sa.make_splash_mqa_single_device)
    kernel = make(_sa.MultiHeadMask([mk] * (Hq if g == 1 else g)),
                  block_sizes=splash_block_sizes(Sq, Sk, D, g,
                                                 q.dtype.itemsize),
                  interpret=use_interpret())
    if g == 1:
        if seg is None:
            out = jax.vmap(lambda qq, kk, vv: kernel(qq, kk, vv))(qt, kt, vt)
        else:
            out = jax.vmap(kernel)(qt, kt, vt, seg)
    else:
        # GQA via the MQA form: group q heads per KV head and vmap the
        # (batch, kv-head) axes; the mask covers one group
        qg = qt.reshape(B, Hkv, g, Sq, D)
        if seg is None:
            out = jax.vmap(jax.vmap(lambda qq, kk, vv: kernel(qq, kk, vv)))(
                qg, kt, vt)
        else:
            out = jax.vmap(
                lambda qb, kb, vb, sb: jax.vmap(
                    lambda qq, kk, vv: kernel(qq, kk, vv, sb))(qb, kb, vb)
            )(qg, kt, vt, seg)
        out = out.reshape(B, Hq, Sq, D)
    return jnp.swapaxes(out, 1, 2)


_IMPLS = {"ours": _ours, "jax_flash": _jax_flash, "splash": _splash}


def available_backends(q_shape, k_shape, causal, has_seg, has_bias,
                       interpret: bool) -> tuple:
    """Statically-valid backends for this signature, best-guess first.

    The ordering IS the no-autotune heuristic: the platform kernels lead
    whenever their constraints hold (splash at this module's tiles, the
    one backend timed on the chip since PR 29); ``ours`` is always
    last-resort-valid (full feature set + interpret mode)."""
    B, Sq, Hq, D = q_shape
    Sk, Hkv = k_shape[1], k_shape[2]
    if interpret:
        # CPU test lane: splash honors interpret=, jax_flash does not
        return ("ours",)
    cands = []
    aligned = Sq % 128 == 0 and Sk % 128 == 0 and D in (64, 128, 256)
    if aligned and not has_bias and causal:
        cands.append("splash")
    if aligned and Sq >= 128:
        cands.append("jax_flash")
    cands.append("ours")
    return tuple(cands)


def run_backend(name, q, k, v, scale, causal, seg_q=None, seg_k=None,
                bias=None):
    return _IMPLS[name](q, k, v, scale, causal, seg_q, seg_k, bias)


def _pick_backend(q, k, v, scale, causal, seg_q, seg_k, bias) -> str:
    from .autotune import FLAGS, lookup, pick
    interp = use_interpret()
    cands = available_backends(q.shape, k.shape, causal,
                               seg_q is not None, bias is not None, interp)
    default = cands[0]
    if len(cands) == 1 or not FLAGS.use_autotune:
        return default
    key = (tuple(q.shape), tuple(k.shape), str(q.dtype), causal,
           seg_q is not None, bias is not None)
    if isinstance(q, jax.core.Tracer):
        return lookup("flash_backend", key, default)

    def run(cand):
        impl = functools.partial(run_backend, cand, scale=scale,
                                 causal=causal, seg_q=seg_q, seg_k=seg_k,
                                 bias=bias)

        # time fwd+bwd: the training step pays ~2/3 of attention FLOPs in
        # the backward, so a fwd-only ranking can pick the wrong kernel
        def loss(qq, kk, vv):
            return jnp.sum(impl(qq, kk, vv).astype(jnp.float32))

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    return pick("flash_backend", key, cands, run, (q, k, v), default)


def tuned_flash(q, k, v, scale: Optional[float] = None,
                causal: bool = False, segment_ids=None,
                kv_segment_ids=None, bias=None):
    """Drop-in for ``flash_attention`` that routes to the fastest backend
    for this shape signature ([B, S, H, D] layout, differentiable)."""
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if segment_ids is not None and kv_segment_ids is None:
        kv_segment_ids = segment_ids
    name = _pick_backend(q, k, v, s, causal, segment_ids, kv_segment_ids,
                         bias)
    try:
        return run_backend(name, q, k, v, s, causal, segment_ids,
                           kv_segment_ids, bias)
    except Exception:
        # traced path: the autotune timing never ran here (tracers can't
        # be timed), so a platform kernel that rejects this signature at
        # trace time must not kill the whole trace — fall back to the
        # in-tree kernel, matching the eager autotune path's
        # skip-on-failure behavior (ADVICE r5 #4)
        if name == "ours":
            raise
        return run_backend("ours", q, k, v, s, causal, segment_ids,
                           kv_segment_ids, bias)
