"""Pallas TPU kernel for the logits-free fused linear + softmax-CE head.

Flash-attention-style online softmax over VOCAB blocks: grid
``(rows, vocab_chunks)`` with the chunk dim innermost, so the VMEM
scratch accumulators (running max / sum-exp / label logit) sweep the
whole vocab for one row block and the ``[T, V]`` logits never exist —
each grid step holds one ``[block_rows, chunk]`` tile.

Backward is the standard two-kernel recompute scheme: ``dx`` re-walks
the vocab chunks accumulating ``dz @ W_c`` per row block; ``dw`` flips
the grid (rows innermost) so each weight chunk's gradient block stays
resident in VMEM while all row blocks stream through.

Block sizes (block_rows, chunk) are selected through
``ops/pallas/autotune`` (timed once per shape signature, cached); the
backward shrinks them until its larger working set fits VMEM
(``analysis/kernel/cost.linear_ce_bwd_blocks``).
Weight layout is [V, H] (embedding layout); ``ops/fused_cross_entropy``
transposes Linear-layout heads before dispatching here.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import numpy as np

from .common import NEG_INF, use_interpret

__all__ = ["linear_cross_entropy_pallas", "tune_linear_ce"]

DEFAULT_BLOCKS = (256, 512)          # (block_rows, vocab chunk)
_BLOCK_CANDIDATES = ((128, 512), (256, 512), (512, 512), (128, 1024),
                     (256, 1024), (256, 2048), (512, 1024))


class _Meta(NamedTuple):
    block_rows: int
    chunk: int
    ignore_index: Optional[int]
    label_smoothing: float


def _compiler_params(outer: str):
    cls = getattr(pltpu, "CompilerParams", None) or pltpu.TPUCompilerParams
    return cls(dimension_semantics=(outer, "arbitrary"))


def _pow2_ceil(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _pad_rows(a, br):
    pad = (-a.shape[0]) % br
    if pad:
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    return a


def _tuned_blocks(x2, w, labels2, meta: _Meta) -> Tuple[int, int]:
    """(block_rows, chunk) via the autotune cache; explicit sizes win.

    Candidates are filtered through the shared VMEM cost model
    (``analysis/kernel/cost.py``) before timing: a (block_rows, chunk)
    whose per-grid-step working set cannot fit the budget at this
    hidden size never reaches the tuner (KL005's runtime half)."""
    from ...analysis.kernel import cost
    from .autotune import FLAGS, lookup, pick
    T, H = x2.shape
    V = w.shape[0]
    key = (T, H, V, str(x2.dtype))
    if not FLAGS.use_autotune:
        return DEFAULT_BLOCKS
    if isinstance(x2, jax.core.Tracer):
        return lookup("linear_ce", key, DEFAULT_BLOCKS)

    def run(cand):
        br, c = cand
        m = meta._replace(block_rows=br, chunk=c)
        return jax.jit(lambda a, b, l: _fwd(a, b, l, m)[0])

    def fits(cand):
        br, c = cand
        return cost.linear_ce_fits(br, c, H, x2.dtype.itemsize,
                                   w.dtype.itemsize)

    return pick("linear_ce", key, _BLOCK_CANDIDATES, run,
                (x2, w, labels2), DEFAULT_BLOCKS, valid=fits)


# ---------------------------------------------------------------------------
# forward: grid (nr, nv), chunk dim innermost
# ---------------------------------------------------------------------------
def _fwd_kernel(x_ref, w_ref, lab_ref, nll_ref, lse_ref,
                m_scr, s_scr, zl_scr, sz_scr, *, C, V, nv, meta: _Meta):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        s_scr[:] = jnp.zeros_like(s_scr)
        zl_scr[:] = jnp.zeros_like(zl_scr)
        sz_scr[:] = jnp.zeros_like(sz_scr)

    x = x_ref[:]                                          # [br, H]
    z = jax.lax.dot_general(x, w_ref[:], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [br, C]
    cols = j * C + jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    valid = cols < V
    z = jnp.where(valid, z, NEG_INF)
    m_prev = m_scr[:]                                     # [br, 1]
    m_new = jnp.maximum(m_prev, jnp.max(z, axis=1, keepdims=True))
    s_scr[:] = s_scr[:] * jnp.exp(m_prev - m_new) \
        + jnp.sum(jnp.exp(z - m_new), axis=1, keepdims=True)
    m_scr[:] = m_new
    hit = cols == lab_ref[:]                              # [br, C]
    zl_scr[:] = zl_scr[:] + jnp.sum(jnp.where(hit, z, 0.0), axis=1,
                                    keepdims=True)
    if meta.label_smoothing > 0.0:
        sz_scr[:] = sz_scr[:] + jnp.sum(jnp.where(valid, z, 0.0), axis=1,
                                        keepdims=True)

    @pl.when(j == nv - 1)
    def _final():
        lse = m_scr[:] + jnp.log(s_scr[:])
        eps = meta.label_smoothing
        if eps > 0.0:
            nll = lse - (1.0 - eps) * zl_scr[:] - (eps / V) * sz_scr[:]
        else:
            nll = lse - zl_scr[:]
        if meta.ignore_index is not None:
            nll = jnp.where(lab_ref[:] != meta.ignore_index, nll, 0.0)
        nll_ref[:] = nll
        lse_ref[:] = lse


def _fwd(x2, w, labels2, meta: _Meta):
    T, H = x2.shape
    V = w.shape[0]
    br = min(meta.block_rows, _pow2_ceil(T))
    C = min(meta.chunk, _pow2_ceil(V))
    xp = _pad_rows(x2, br)
    lab = _pad_rows(labels2.reshape(-1, 1).astype(jnp.int32), br)
    Tp = xp.shape[0]
    nr, nv = Tp // br, pl.cdiv(V, C)
    nll, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, C=C, V=V, nv=nv, meta=meta),
        grid=(nr, nv),
        in_specs=[
            pl.BlockSpec((br, H), lambda i, j: (i, 0)),
            pl.BlockSpec((C, H), lambda i, j: (j, 0)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Tp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Tp, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((br, 1), jnp.float32)] * 4,
        compiler_params=_compiler_params("parallel"),
        interpret=use_interpret(),
    )(xp, w, lab)
    return nll[:T, 0], lse[:T, 0]


# ---------------------------------------------------------------------------
# backward: dz = g * (softmax - target), recomputed per chunk
# ---------------------------------------------------------------------------
def _dz_chunk(x, w_c, lab, lse, g, j, C, V, eps):
    z = jax.lax.dot_general(x, w_c, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    cols = j * C + jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    valid = cols < V
    p = jnp.exp(jnp.where(valid, z, NEG_INF) - lse)       # 0 at invalid cols
    y = (cols == lab).astype(jnp.float32)
    if eps > 0.0:
        y = jnp.where(valid, (1.0 - eps) * y + eps / V, 0.0)
    return g * (p - y)                                    # [br, C]


def _dx_kernel(x_ref, w_ref, lab_ref, lse_ref, g_ref, dx_ref, acc_scr,
               *, C, V, nv, meta: _Meta):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    dz = _dz_chunk(x_ref[:], w_ref[:], lab_ref[:], lse_ref[:], g_ref[:],
                   j, C, V, meta.label_smoothing)
    # rows of the last w block past V are uninitialized padding; dz is 0
    # there but 0 * garbage is NaN-unsafe in the matmul — zero them.
    wrow = j * C + jax.lax.broadcasted_iota(jnp.int32, w_ref.shape, 0)
    w_c = jnp.where(wrow < V, w_ref[:], jnp.zeros((), w_ref.dtype))
    acc_scr[:] = acc_scr[:] + jax.lax.dot_general(
        dz.astype(w_c.dtype), w_c, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == nv - 1)
    def _final():
        dx_ref[:] = acc_scr[:].astype(dx_ref.dtype)


def _dw_kernel(x_ref, w_ref, lab_ref, lse_ref, g_ref, dw_ref, acc_scr,
               *, C, V, nr, meta: _Meta):
    i = pl.program_id(1)          # row blocks innermost: dw block resident
    j = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    x = x_ref[:]
    dz = _dz_chunk(x, w_ref[:], lab_ref[:], lse_ref[:], g_ref[:],
                   j, C, V, meta.label_smoothing)
    # padded rows carry g == 0, so their dz rows are exactly zero
    acc_scr[:] = acc_scr[:] + jax.lax.dot_general(
        dz.astype(x.dtype), x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == nr - 1)
    def _final():
        dw_ref[:] = acc_scr[:].astype(dw_ref.dtype)


def _bwd(x2, w, labels2, lse, g2, meta: _Meta):
    from ...analysis.kernel import cost
    T, H = x2.shape
    V = w.shape[0]
    # the backward holds an fp32 accumulator and an output block beside
    # the forward's blocks: shrink the tile until both kernels fit
    br, C = cost.linear_ce_bwd_blocks(
        min(meta.block_rows, _pow2_ceil(T)), min(meta.chunk, _pow2_ceil(V)),
        H, x2.dtype.itemsize, w.dtype.itemsize)
    xp = _pad_rows(x2, br)
    lab = _pad_rows(labels2.reshape(-1, 1).astype(jnp.int32), br)
    lsep = _pad_rows(lse.reshape(-1, 1), br)
    gp = _pad_rows(g2.reshape(-1, 1).astype(jnp.float32), br)  # pad = 0
    Tp = xp.shape[0]
    nr, nv = Tp // br, pl.cdiv(V, C)
    row_specs = [
        pl.BlockSpec((br, H), lambda i, j: (i, 0)),
        pl.BlockSpec((C, H), lambda i, j: (j, 0)),
        pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
    ]
    dx = pl.pallas_call(
        functools.partial(_dx_kernel, C=C, V=V, nv=nv, meta=meta),
        grid=(nr, nv),
        in_specs=row_specs,
        out_specs=pl.BlockSpec((br, H), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Tp, H), x2.dtype),
        scratch_shapes=[pltpu.VMEM((br, H), jnp.float32)],
        compiler_params=_compiler_params("parallel"),
        interpret=use_interpret(),
    )(xp, w, lab, lsep, gp)
    chunk_specs = [
        pl.BlockSpec((br, H), lambda j, i: (i, 0)),
        pl.BlockSpec((C, H), lambda j, i: (j, 0)),
        pl.BlockSpec((br, 1), lambda j, i: (i, 0)),
        pl.BlockSpec((br, 1), lambda j, i: (i, 0)),
        pl.BlockSpec((br, 1), lambda j, i: (i, 0)),
    ]
    dw = pl.pallas_call(
        functools.partial(_dw_kernel, C=C, V=V, nr=nr, meta=meta),
        grid=(nv, nr),
        in_specs=chunk_specs,
        out_specs=pl.BlockSpec((C, H), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((V, H), w.dtype),
        scratch_shapes=[pltpu.VMEM((C, H), jnp.float32)],
        compiler_params=_compiler_params("parallel"),
        interpret=use_interpret(),
    )(xp, w, lab, lsep, gp)
    return dx[:T], dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _lce_pallas(meta: _Meta, x, w, labels):
    nll, _ = _lce_pallas_fwd(meta, x, w, labels)
    return nll


def _lce_pallas_fwd(meta: _Meta, x, w, labels):
    x2 = x.reshape(-1, x.shape[-1])
    labels2 = labels.reshape(-1)
    nll, lse = _fwd(x2, w, labels2, meta)
    return nll.reshape(labels.shape), (x, w, labels, lse)


def _lce_pallas_bwd(meta: _Meta, res, g):
    x, w, labels, lse = res
    x2 = x.reshape(-1, x.shape[-1])
    labels2 = labels.reshape(-1)
    g2 = g.reshape(-1).astype(jnp.float32)
    if meta.ignore_index is not None:
        g2 = jnp.where(labels2 != meta.ignore_index, g2, 0.0)
    dx, dw = _bwd(x2, w, labels2, lse, g2, meta)
    return (dx.reshape(x.shape), dw,
            np.zeros(labels.shape, jax.dtypes.float0))


_lce_pallas.defvjp(_lce_pallas_fwd, _lce_pallas_bwd)


def linear_cross_entropy_pallas(x, w, labels, *, chunk: Optional[int] = None,
                                block_rows: Optional[int] = None,
                                ignore_index: Optional[int] = None,
                                label_smoothing: float = 0.0):
    """Per-token NLL of ``softmax(x @ w.T)`` — Pallas TPU tier.

    ``x``: [..., H]; ``w``: [V, H]; ``labels``: [...] int.  Block sizes
    default to the autotune cache (``tune_linear_ce`` primes it)."""
    x2 = x.reshape(-1, x.shape[-1])
    labels2 = labels.reshape(-1)
    meta = _Meta(DEFAULT_BLOCKS[0], DEFAULT_BLOCKS[1], ignore_index,
                 float(label_smoothing))
    if chunk is None or block_rows is None:
        br, c = _tuned_blocks(x2, w, labels2, meta)
        block_rows = block_rows or br
        chunk = chunk or c
    meta = meta._replace(block_rows=int(block_rows), chunk=int(chunk))
    return _lce_pallas(meta, x, w, labels.astype(jnp.int32))


def tune_linear_ce(x, w, labels, **kw):
    """Eagerly time the block candidates for this shape and cache the
    winner (FLAGS.use_autotune must be on) — run once at warmup; traced
    calls then read the cache."""
    return linear_cross_entropy_pallas(x, w, labels, **kw)
