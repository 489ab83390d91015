"""Logits-free fused cross-entropy head.

Every training path used to materialize the full ``[B, S, V]`` fp32
logits tensor before taking the loss — at GPT-125M bench shape
(b8×s1024, V≈50k) that is ~1.6 GB of activations plus the same again for
the softmax backward.  :func:`linear_cross_entropy` fuses the LM-head
matmul with the softmax-CE reduction: it streams over vocab chunks
keeping only O(T) running accumulators (online max / logsumexp / label
logit), wrapped in a ``jax.custom_vjp`` whose backward *recomputes* the
chunked softmax rows and emits grads w.r.t. both the activations and the
(possibly tied) head weight — ``[T, V]`` is never stored.

Three tiers behind one API:

* pure-XLA ``lax.scan`` chunking (works everywhere, incl. the CPU tier-1
  lane) — the default off-TPU;
* a Pallas TPU kernel (``ops/pallas/linear_ce.py``) with block sizes
  selected through ``ops/pallas/autotune`` — the default on TPU;
* a vocab-parallel variant (``axis_name=...``) for mp-sharded heads that
  fuses the two-pass ``parallel/manual.py:vocab_parallel_nll``
  all-reduces (max, then sum-exp + label pick) into the chunk loop: one
  ``pmax`` plus ONE ``psum`` of the stacked accumulators per call, and
  the backward's dx all-reduce replaces the ``mp_copy`` VJP psum.

:func:`softmax_nll_chunked` applies the same chunked reduction to
*already materialized* logits (the large-vocab 3-D ``F.cross_entropy``
case): the fp32 log-prob copy and its softmax residual are never built —
the backward recomputes probabilities chunk by chunk.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["linear_cross_entropy", "softmax_nll_chunked",
           "default_chunk", "naive_peak_bytes", "chunked_peak_bytes",
           "pallas_unsupported_reason", "LinearCEUnsupportedError"]

NEG = -1e30

# F.cross_entropy routes 3-D hard-label losses through the chunked path
# when the class dim is at least this wide (module attr so tests/users
# can tune it; small vocabs lose more to the scan than they save).
MIN_FUSED_VOCAB = 16384


def default_chunk(vocab: int) -> int:
    """Vocab-chunk width: full vocab when small, else 2048 — a [T, chunk]
    fp32 buffer per scan step (64 MB at the bench's T=8192) while keeping
    the per-chunk matmul MXU-shaped."""
    return vocab if vocab <= 2048 else 2048


def naive_peak_bytes(tokens: int, vocab: int) -> int:
    """Activation bytes of the naive head: fp32 logits + the softmax
    (log-prob) residual the backward keeps."""
    return 2 * tokens * vocab * 4


def chunked_peak_bytes(tokens: int, vocab: int, chunk: Optional[int] = None
                       ) -> int:
    """Activation bytes of the chunked head: two live [T, chunk] buffers
    (logits + exp) plus the four [T] running accumulators and saved lse."""
    c = chunk or default_chunk(vocab)
    return 2 * tokens * c * 4 + 5 * tokens * 4


class _Meta(NamedTuple):
    """Hashable static config for the custom_vjp (nondiff arg)."""
    chunk: int
    w_layout: str               # "vh" ([V, H]) or "hv" ([H, V])
    ignore_index: Optional[int]
    label_smoothing: float
    axis_name: Optional[str]    # vocab-parallel mesh axis (inside shard_map)
    vocab_global: int           # full vocab across all shards


def _slice_w(w, c0, width, meta: _Meta):
    axis = 0 if meta.w_layout == "vh" else 1
    return lax.dynamic_slice_in_dim(w, c0, width, axis=axis)


def _logits_chunk(x2, w_c, meta: _Meta):
    """[T, C] fp32 logits for one vocab chunk."""
    eq = "th,ch->tc" if meta.w_layout == "vh" else "th,hc->tc"
    return jnp.einsum(eq, x2, w_c, preferred_element_type=jnp.float32)


def _fwd_stats(carry, c0, w_c, x2, labels2, off, meta: _Meta):
    """Online-update the (m, s, zl, sz) accumulators with one chunk.

    m: running max; s: sum-exp rescaled to m; zl: raw label logit;
    sz: sum of raw logits (only tracked under label smoothing).
    """
    m, s, zl, sz = carry
    z = _logits_chunk(x2, w_c, meta)                       # [T, C]
    width = z.shape[1]
    cols = off + c0 + jnp.arange(width)                    # global class ids
    m_new = jnp.maximum(m, jnp.max(z, axis=-1))
    s = s * jnp.exp(m - m_new) + jnp.sum(jnp.exp(z - m_new[:, None]), -1)
    hit = labels2[:, None] == cols[None, :]
    zl = zl + jnp.sum(jnp.where(hit, z, 0.0), -1)
    if meta.label_smoothing > 0.0:
        sz = sz + jnp.sum(z, -1)
    return (m_new, s, zl, sz)


def _scan_chunks(step, carry, w, v_local, meta: _Meta):
    """Run ``step(carry, c0, w_chunk)`` over the whole local vocab:
    a lax.scan over the evenly divisible prefix plus one static epilogue
    chunk for the remainder (uneven V needs no padding or masking)."""
    chunk = min(meta.chunk, v_local)
    nc = v_local // chunk
    rem = v_local - nc * chunk

    if nc == 1 and rem == 0:
        return step(carry, 0, _slice_w(w, 0, v_local, meta))

    def body(c, i):
        c0 = i * chunk
        return step(c, c0, _slice_w(w, c0, chunk, meta)), None

    carry, _ = lax.scan(body, carry, jnp.arange(nc))
    if rem:
        carry = step(carry, nc * chunk, _slice_w(w, nc * chunk, rem, meta))
    return carry


def _rank_offset(w, meta: _Meta):
    v_local = w.shape[0] if meta.w_layout == "vh" else w.shape[1]
    if meta.axis_name is None:
        return v_local, jnp.zeros((), jnp.int32)
    return v_local, (lax.axis_index(meta.axis_name) * v_local).astype(
        jnp.int32)


def _lse_and_terms(x2, w, labels2, meta: _Meta):
    """Shared forward reduction: returns (lse, zl, sz) — all [T] fp32,
    globally reduced when vocab-parallel."""
    T = x2.shape[0]
    v_local, off = _rank_offset(w, meta)
    carry = (jnp.full((T,), NEG, jnp.float32), jnp.zeros((T,), jnp.float32),
             jnp.zeros((T,), jnp.float32), jnp.zeros((T,), jnp.float32))
    step = functools.partial(_fwd_stats, x2=x2, labels2=labels2, off=off,
                             meta=meta)
    m, s, zl, sz = _scan_chunks(step, carry, w, v_local, meta)
    if meta.axis_name is not None:
        # fuse the reference's two-pass all-reduces: one pmax for the
        # global max, then ONE psum carrying sum-exp, label logit and
        # (optionally) the smoothing sum together.
        m_g = lax.pmax(m, meta.axis_name)
        packed = jnp.stack([s * jnp.exp(m - m_g), zl, sz])
        packed = lax.psum(packed, meta.axis_name)
        s, zl, sz = packed[0], packed[1], packed[2]
        m = m_g
    return jnp.log(s) + m, zl, sz


def _nll_from_terms(lse, zl, sz, labels2, meta: _Meta):
    eps = meta.label_smoothing
    if eps > 0.0:
        nll = lse - (1.0 - eps) * zl - (eps / meta.vocab_global) * sz
    else:
        nll = lse - zl
    if meta.ignore_index is not None:
        nll = jnp.where(labels2 != meta.ignore_index, nll, 0.0)
    return nll


def _bwd_step(dx, c0, w_c, x2, labels2, g2, lse, off, meta: _Meta):
    """Recompute one chunk's softmax row; return (dx_acc, dw_chunk)."""
    z = _logits_chunk(x2, w_c, meta)                       # [T, C]
    width = z.shape[1]
    cols = off + c0 + jnp.arange(width)
    p = jnp.exp(z - lse[:, None])                          # softmax chunk
    eps = meta.label_smoothing
    y = (labels2[:, None] == cols[None, :]).astype(jnp.float32)
    if eps > 0.0:
        y = (1.0 - eps) * y + eps / meta.vocab_global
    dz = g2[:, None] * (p - y)                             # [T, C] fp32
    if meta.w_layout == "vh":
        dx = dx + jnp.einsum("tc,ch->th", dz, w_c,
                             preferred_element_type=jnp.float32)
        dw_c = jnp.einsum("tc,th->ch", dz, x2,
                          preferred_element_type=jnp.float32)
    else:
        dx = dx + jnp.einsum("tc,hc->th", dz, w_c,
                             preferred_element_type=jnp.float32)
        dw_c = jnp.einsum("th,tc->hc", x2, dz,
                          preferred_element_type=jnp.float32)
    return dx, dw_c


def _bwd_sweep(step, dx, w, v_local, meta: _Meta):
    """dx via the scan carry; dw chunks as STACKED scan outputs (each
    slot written once — carrying the full [V, H] buffer and
    dynamic-update-slicing it would re-copy it every iteration)."""
    chunk = min(meta.chunk, v_local)
    nc = v_local // chunk
    rem = v_local - nc * chunk
    vocab_axis = 0 if meta.w_layout == "vh" else 1

    if nc == 1 and rem == 0:
        dx, dw = step(dx, 0, _slice_w(w, 0, v_local, meta))
        return dx, dw

    def body(c, i):
        c0 = i * chunk
        return step(c, c0, _slice_w(w, c0, chunk, meta))

    dx, dw_stack = lax.scan(body, dx, jnp.arange(nc))
    if meta.w_layout == "vh":
        dw = dw_stack.reshape(nc * chunk, dw_stack.shape[-1])
    else:
        dw = jnp.moveaxis(dw_stack, 0, 1).reshape(w.shape[0], nc * chunk)
    if rem:
        dx, dw_rem = step(dx, nc * chunk,
                          _slice_w(w, nc * chunk, rem, meta))
        dw = jnp.concatenate([dw, dw_rem], axis=vocab_axis)
    return dx, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _lce(meta: _Meta, x, w, labels):
    nll, _ = _lce_fwd(meta, x, w, labels)
    return nll


def _lce_fwd(meta: _Meta, x, w, labels):
    x2 = x.reshape(-1, x.shape[-1])
    labels2 = labels.reshape(-1)
    lse, zl, sz = _lse_and_terms(x2, w, labels2, meta)
    nll = _nll_from_terms(lse, zl, sz, labels2, meta)
    return nll.reshape(labels.shape), (x, w, labels, lse)


def _lce_bwd(meta: _Meta, res, g):
    x, w, labels, lse = res
    x2 = x.reshape(-1, x.shape[-1])
    labels2 = labels.reshape(-1)
    g2 = g.reshape(-1).astype(jnp.float32)
    if meta.ignore_index is not None:
        g2 = jnp.where(labels2 != meta.ignore_index, g2, 0.0)
    v_local, off = _rank_offset(w, meta)
    step = functools.partial(_bwd_step, x2=x2, labels2=labels2, g2=g2,
                             lse=lse, off=off, meta=meta)
    dx, dw = _bwd_sweep(step, jnp.zeros(x2.shape, jnp.float32), w,
                        v_local, meta)
    if meta.axis_name is not None:
        # each rank saw only its vocab shard of the head matmul: the
        # activation grad is partial over mp (this psum replaces the
        # mp_copy VJP all-reduce of the unfused head); dw stays local.
        dx = lax.psum(dx, meta.axis_name)
    return (dx.astype(x.dtype).reshape(x.shape), dw.astype(w.dtype),
            np.zeros(labels.shape, jax.dtypes.float0))


_lce.defvjp(_lce_fwd, _lce_bwd)


def linear_cross_entropy(x, w, labels, *, w_layout: str = "vh",
                         chunk: Optional[int] = None,
                         ignore_index: Optional[int] = None,
                         label_smoothing: float = 0.0,
                         axis_name: Optional[str] = None,
                         backend: Optional[str] = None):
    """Per-token NLL of ``softmax(x @ head)`` without materializing logits.

    ``x``: [..., H] activations; ``w``: the (tied) head weight — [V, H]
    with ``w_layout="vh"`` (embedding layout) or [H, V] with ``"hv"``
    (Linear layout); ``labels``: [...] int global class ids.  Returns
    fp32 NLL shaped like ``labels`` (``ignore_index`` rows are 0).

    ``axis_name``: set to the mp mesh axis when ``w`` is the LOCAL vocab
    shard inside an all-manual ``shard_map`` — collectives (one pmax, one
    psum forward; one dx psum backward) are fused into the chunk loop.

    ``backend``: "xla" (lax.scan chunking), "pallas" (TPU kernel,
    dense-only; raises :class:`LinearCEUnsupportedError` at a hidden
    size its kernels cannot fit), or None = pallas on TPU when
    :func:`pallas_unsupported_reason` is None, else xla.
    """
    if w_layout not in ("vh", "hv"):
        raise ValueError(f"w_layout must be 'vh' or 'hv', got {w_layout!r}")
    v_local = w.shape[0] if w_layout == "vh" else w.shape[1]
    reason = pallas_unsupported_reason(x, w, axis_name)
    if backend is None:
        backend = "pallas" if (reason is None and _pallas_auto()) \
            else "xla"
    if backend == "pallas":
        if reason is not None:
            raise LinearCEUnsupportedError(f"linear_cross_entropy: {reason}")
        from .pallas.linear_ce import linear_cross_entropy_pallas
        w_vh = w if w_layout == "vh" else jnp.swapaxes(w, 0, 1)
        return linear_cross_entropy_pallas(
            x, w_vh, labels, chunk=chunk, ignore_index=ignore_index,
            label_smoothing=label_smoothing)
    if backend != "xla":
        raise ValueError(f"unknown backend {backend!r}")
    n_shards = 1
    if axis_name is not None:
        n_shards = lax.axis_size(axis_name)
    meta = _Meta(chunk=int(chunk or default_chunk(v_local)),
                 w_layout=w_layout, ignore_index=ignore_index,
                 label_smoothing=float(label_smoothing),
                 axis_name=axis_name, vocab_global=v_local * n_shards)
    return _lce(meta, x, w, labels.astype(jnp.int32))


class LinearCEUnsupportedError(ValueError):
    """``backend="pallas"`` was forced where the kernel cannot run."""


def pallas_unsupported_reason(x, w, axis_name: Optional[str] = None
                              ) -> Optional[str]:
    """None when the Pallas tier can serve this head, else the reason
    (the typed-fallback signal): the kernel is dense-only, and its backward must fit VMEM at ``x``'s hidden size
    (``analysis/kernel/cost.linear_ce_unsupported_reason``)."""
    if axis_name is not None:
        return ("the Pallas tier is dense-only; the vocab-parallel tier "
                "runs the XLA chunk loop")
    from ..analysis.kernel import cost
    return cost.linear_ce_unsupported_reason(
        x.shape[-1], x.dtype.itemsize, w.dtype.itemsize)


def _pallas_auto() -> bool:
    """Default to the Pallas tier only on real TPU hardware — interpret
    mode off-TPU is a correctness lane, not a perf one (tests opt in
    explicitly via backend="pallas")."""
    from ..core.device import on_tpu
    return on_tpu()


# ---------------------------------------------------------------------------
# chunked softmax-CE over ALREADY materialized logits (the 3-D large-vocab
# F.cross_entropy case): saves the fp32 log-prob copy + softmax residual.
# ---------------------------------------------------------------------------
class _SoftmaxMeta(NamedTuple):
    chunk: int
    ignore_index: Optional[int]
    label_smoothing: float


def _logits_terms(z2, labels2, meta: _SoftmaxMeta):
    """(lse, zl, sz) from [T, V] logits via static chunk slices."""
    T, V = z2.shape
    chunk = min(meta.chunk, V)
    m = jnp.full((T,), NEG, jnp.float32)
    s = jnp.zeros((T,), jnp.float32)
    zl = jnp.zeros((T,), jnp.float32)
    sz = jnp.zeros((T,), jnp.float32)
    for c0 in range(0, V, chunk):
        z = z2[:, c0:c0 + chunk].astype(jnp.float32)
        cols = c0 + jnp.arange(z.shape[1])
        m_new = jnp.maximum(m, jnp.max(z, -1))
        s = s * jnp.exp(m - m_new) + jnp.sum(jnp.exp(z - m_new[:, None]), -1)
        m = m_new
        zl = zl + jnp.sum(
            jnp.where(labels2[:, None] == cols[None, :], z, 0.0), -1)
        if meta.label_smoothing > 0.0:
            sz = sz + jnp.sum(z, -1)
    return jnp.log(s) + m, zl, sz


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _softmax_nll(meta: _SoftmaxMeta, logits, labels):
    nll, _ = _softmax_nll_fwd(meta, logits, labels)
    return nll


def _softmax_nll_fwd(meta: _SoftmaxMeta, logits, labels):
    V = logits.shape[-1]
    z2 = logits.reshape(-1, V)
    labels2 = labels.reshape(-1)
    lse, zl, sz = _logits_terms(z2, labels2, meta)
    lmeta = _Meta(meta.chunk, "vh", meta.ignore_index, meta.label_smoothing,
                  None, V)
    nll = _nll_from_terms(lse, zl, sz, labels2, lmeta)
    return nll.reshape(labels.shape), (logits, labels, lse)


def _softmax_nll_bwd(meta: _SoftmaxMeta, res, g):
    logits, labels, lse = res
    V = logits.shape[-1]
    z2 = logits.reshape(-1, V)
    labels2 = labels.reshape(-1)
    g2 = g.reshape(-1).astype(jnp.float32)
    if meta.ignore_index is not None:
        g2 = jnp.where(labels2 != meta.ignore_index, g2, 0.0)
    chunk = min(meta.chunk, V)
    eps = meta.label_smoothing
    parts = []
    # the cotangent itself is [T, V] (unavoidable — logits are an input),
    # but the softmax is recomputed per chunk instead of stored.
    for c0 in range(0, V, chunk):
        z = z2[:, c0:c0 + chunk].astype(jnp.float32)
        cols = c0 + jnp.arange(z.shape[1])
        p = jnp.exp(z - lse[:, None])
        y = (labels2[:, None] == cols[None, :]).astype(jnp.float32)
        if eps > 0.0:
            y = (1.0 - eps) * y + eps / V
        parts.append((g2[:, None] * (p - y)).astype(logits.dtype))
    dz = jnp.concatenate(parts, axis=-1) if len(parts) > 1 else parts[0]
    return (dz.reshape(logits.shape),
            np.zeros(labels.shape, jax.dtypes.float0))


_softmax_nll.defvjp(_softmax_nll_fwd, _softmax_nll_bwd)


def softmax_nll_chunked(logits, labels, *, chunk: Optional[int] = None,
                        ignore_index: Optional[int] = None,
                        label_smoothing: float = 0.0):
    """Per-token NLL over materialized logits via the chunked reduction:
    forward keeps O(T) accumulators (no fp32 log-prob copy), backward
    recomputes softmax chunks from the saved lse."""
    V = logits.shape[-1]
    meta = _SoftmaxMeta(chunk=int(chunk or default_chunk(V)),
                        ignore_index=ignore_index,
                        label_smoothing=float(label_smoothing))
    return _softmax_nll(meta, logits, labels.astype(jnp.int32))
