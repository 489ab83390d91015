"""Paged KV cache + block attention (reference
paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu +
python/paddle/incubate/nn/functional/block_multihead_attention.py).

TPU-first: the physical cache is one pooled array
``[num_blocks, block_size, H_kv, D]`` per k/v; sequences own logical pages
through an int32 ``block_table [B, max_blocks]``.  The decode step walks
the table a chunk of columns at a time, as far as the longest live
sequence reaches: each trip is one XLA gather of that chunk's pages
(rides HBM at full bandwidth; no pointer chasing like the CUDA kernel —
the gather IS the page walk) folded into the same online-softmax math
as the dense MMHA.  The host-side
:class:`BlockAllocator` mirrors the reference's block manager: free-list
allocate/extend/release so unrelated sequences share the pool.
"""

from __future__ import annotations

import math
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["BlockAllocator", "PagedKVCache", "PagedKVGeometryError",
           "QuantizedKVPool", "paged_decode_attention", "paged_append",
           "validate_paged_decode_geometry", "quantize_kv",
           "dequantize_kv", "kv_page_bytes", "zeros_kv_pool",
           "pool_geometry", "is_quantized_pool", "decode_walk",
           "layers_as_one_pool", "layer_pages"]

NEG_INF = -1e30

# Positions one trip of the decode page walk covers in a table wide
# enough to need several (16 pages of 16 tokens).
WALK_POSITIONS = 256

# Scale floor for int8 KV quantization (all-zero rows — fresh pool
# pages — must not divide by zero; codes stay 0 and dequantize to 0).
KV_SCALE_EPS = 1e-8


class QuantizedKVPool(NamedTuple):
    """An int8 paged-KV pool: ``data`` holds the codes with the SAME
    logical shape a full-width pool has (``[..., NB, BS, Hkv, D]``),
    ``scale`` one fp32 absmax/127 scale per (page, token, kv-head)
    (``[..., NB, BS, Hkv]``).

    Scales are per-TOKEN, not per-page: a page-wide absmax would have to
    grow monotonically as tokens append, and a rejected spec-decode
    draft that raised it would retroactively requantize every committed
    token in the page — breaking the greedy bit-identity pin.  Per-token
    scales are append-local: rollback overwrites both code row and scale
    row in place, so committed tokens never change representation.

    A NamedTuple is a JAX pytree, so quantized pools flow through
    ``jax.jit`` donation, ``lax.scan`` carries (spec-decode verify), and
    the engine's jitted step without any special casing.
    """
    data: jnp.ndarray
    scale: jnp.ndarray


def is_quantized_pool(pool) -> bool:
    return isinstance(pool, QuantizedKVPool)


def pool_geometry(pool):
    """(num_blocks, block_size, kv_heads, head_dim) of a [NB, BS, Hkv,
    D]-shaped pool, full-width or quantized."""
    arr = pool.data if isinstance(pool, QuantizedKVPool) else pool
    return tuple(arr.shape[-4:])


def layers_as_one_pool(pool, like=None):
    """The pools of all layers, stacked ``[L, NB, BS, ...]``, as ONE
    pool ``[L*NB, BS, ...]`` whose pages ``i*NB .. (i+1)*NB - 1`` are
    layer ``i``'s (every array of a :class:`QuantizedKVPool` alike);
    with ``like`` (the stacked pool it came from) the way back.  A
    reshape of the leading dimensions: no byte moves.  A layer scan
    that carries this pool whole and hands each layer its pages through
    :func:`layer_pages` appends in place; one that slices a layer's
    ``[NB, ...]`` out of the stack and puts it back moves the whole
    stack through memory every call."""
    if like is not None:
        return jax.tree.map(lambda a, b: a.reshape(b.shape), pool, like)
    return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), pool)


def layer_pages(table, layer, num_blocks: int):
    """A table of one layer's page numbers as page numbers of
    :func:`layers_as_one_pool`'s pool: a mapped entry moves into layer
    ``layer``'s range, an unmapped one (negative) stays negative, so it
    is still dropped by an append and masked by a walk (which reads the
    whole pool's page 0 for it: any finite page does)."""
    return jnp.where(table >= 0, table + layer * num_blocks, table)


def quantize_kv(kv):
    """[..., H, D] new-token rows -> (int8 codes, fp32 scale [..., H]),
    per-(token, head) absmax — THE quantization both the XLA tier's
    append and the engine's host-side restore/prefill scatters use, so
    pool contents are bit-identical no matter which path wrote them."""
    kf = jnp.asarray(kv).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(kf), axis=-1)
    scale = jnp.maximum(absmax, KV_SCALE_EPS) / 127.0
    codes = jnp.clip(jnp.round(kf / scale[..., None]), -127, 127
                     ).astype(jnp.int8)
    return codes, scale


def dequantize_kv(data, scale, dtype=jnp.float32):
    """int8 codes [..., H, D] + scale [..., H] -> dequantized [..., H,
    D] in ``dtype``."""
    return (jnp.asarray(data).astype(jnp.float32)
            * jnp.asarray(scale).astype(jnp.float32)[..., None]
            ).astype(dtype)


def kv_page_bytes(block_size: int, kv_heads: int, head_dim: int,
                  *, dtype_itemsize: int = 2,
                  kv_quant: bool = False) -> int:
    """Bytes ONE pool page (k or v, one layer) occupies — the capacity
    denominator of the bench's quant capacity row.  Quantized pages pay
    1 B/element codes plus a 4 B fp32 scale per (token, head)."""
    elems = block_size * kv_heads * head_dim
    if kv_quant:
        return elems + block_size * kv_heads * 4
    return elems * dtype_itemsize


def zeros_kv_pool(shape, dtype, *, kv_quant: bool = False):
    """A fresh OWNED pool (``jnp.array`` of host zeros — safe to donate;
    never hand ``device_put``/``asarray`` views to the engine's donated
    args).  ``shape`` is the full-width ``[..., NB, BS, Hkv, D]``."""
    if kv_quant:
        return QuantizedKVPool(
            data=jnp.array(np.zeros(shape, np.int8)),
            scale=jnp.array(np.zeros(shape[:-1], np.float32)))
    return jnp.array(np.zeros(shape, dtype))


class PagedKVGeometryError(ValueError):
    """A model/pool geometry the paged decode path cannot serve.

    Raised at TRACE time with the offending shapes spelled out, instead
    of the bare XLA shape-mismatch error that used to surface deep
    inside the attention einsum when (say) a config's head_dim drifted
    from the pool it was paired with.  The fused decode-block op's
    fallback tier keys off the same validation (ISSUE 9)."""


def validate_paged_decode_geometry(q, pool_k, pool_v, block_table,
                                   lengths, *, op: str =
                                   "paged_decode_attention") -> None:
    """Shape/dtype contract of one paged decode step.

    ``q`` may be the [B, Hq, D] query array or its shape tuple.  All
    checks are static (trace-safe); violations raise
    :class:`PagedKVGeometryError` naming the offending geometry."""
    q_shape = tuple(q if isinstance(q, (tuple, list)) else q.shape)
    if len(q_shape) != 3:
        raise PagedKVGeometryError(
            f"{op}: q must be [B, Hq, D] (one token per sequence), got "
            f"shape {q_shape}")
    B, Hq, D = q_shape
    kq, vq = is_quantized_pool(pool_k), is_quantized_pool(pool_v)
    if kq != vq:
        raise PagedKVGeometryError(
            f"{op}: k/v pools disagree on quantization — k is "
            f"{'int8' if kq else 'full-width'}, v is "
            f"{'int8' if vq else 'full-width'}")
    if kq:
        for name, p in (("k", pool_k), ("v", pool_v)):
            if p.data.dtype != jnp.int8:
                raise PagedKVGeometryError(
                    f"{op}: quantized {name} pool data must be int8, "
                    f"got {p.data.dtype}")
            if tuple(p.scale.shape) != tuple(p.data.shape[:-1]):
                raise PagedKVGeometryError(
                    f"{op}: quantized {name} pool scale must be per "
                    f"(page, token, head) {tuple(p.data.shape[:-1])}, "
                    f"got {tuple(p.scale.shape)}")
        pool_k, pool_v = pool_k.data, pool_v.data
    if pool_k.ndim != 4 or pool_v.ndim != 4:
        raise PagedKVGeometryError(
            f"{op}: pools must be [num_blocks, block_size, Hkv, D], got "
            f"k {tuple(pool_k.shape)} / v {tuple(pool_v.shape)}")
    if tuple(pool_k.shape) != tuple(pool_v.shape):
        raise PagedKVGeometryError(
            f"{op}: k/v pools disagree: {tuple(pool_k.shape)} vs "
            f"{tuple(pool_v.shape)}")
    NB, BS, Hkv, Dp = pool_k.shape
    if Dp != D:
        raise PagedKVGeometryError(
            f"{op}: head_dim mismatch — q has D={D}, the KV pool was "
            f"built with D={Dp} (pool {tuple(pool_k.shape)})")
    if BS < 1:
        raise PagedKVGeometryError(
            f"{op}: block_size must be >= 1, pool has {BS}")
    if Hkv < 1 or Hq % Hkv != 0:
        raise PagedKVGeometryError(
            f"{op}: q heads ({Hq}) must be a positive multiple of kv "
            f"heads ({Hkv}) — GQA groups must divide evenly")
    bt_shape = tuple(np.shape(block_table))
    if len(bt_shape) != 2 or bt_shape[0] != B:
        raise PagedKVGeometryError(
            f"{op}: block_table must be [B={B}, max_blocks], got "
            f"{bt_shape}")
    len_shape = tuple(np.shape(lengths))
    if len_shape != (B,):
        raise PagedKVGeometryError(
            f"{op}: lengths must be [B={B}], got {len_shape}")


class BlockAllocator:
    """Free-list page allocator (reference BlockManager semantics)."""

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._owned: Dict[int, List[int]] = {}

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def allocate(self, seq_id: int, n: int) -> List[int]:
        if n > len(self._free):
            raise RuntimeError(
                f"paged KV pool exhausted: need {n} blocks, "
                f"{len(self._free)} free")
        got = [self._free.pop() for _ in range(n)]
        self._owned.setdefault(seq_id, []).extend(got)
        return got

    def blocks_of(self, seq_id: int) -> List[int]:
        return list(self._owned.get(seq_id, []))

    def release(self, seq_id: int) -> None:
        self._free.extend(reversed(self._owned.pop(seq_id, [])))


class PagedKVCache:
    """Pooled paged cache for one attention layer set.

    ``k/v``: [L, num_blocks, block_size, H_kv, D]; ``block_table``
    [B, max_blocks] (-1 = unmapped); ``lengths`` [B].
    """

    def __init__(self, num_layers: int, num_blocks: int, block_size: int,
                 num_kv_heads: int, head_dim: int, max_batch: int,
                 dtype=jnp.float32):
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.max_blocks_per_seq = num_blocks  # upper bound
        self.k = jnp.zeros((num_layers, num_blocks, block_size,
                            num_kv_heads, head_dim), dtype)
        self.v = jnp.zeros_like(self.k)
        self.block_table = np.full((max_batch, num_blocks), -1, np.int32)
        self.lengths = np.zeros((max_batch,), np.int32)
        self.alloc = BlockAllocator(num_blocks)

    def ensure_capacity(self, seq_id: int, new_len: int) -> None:
        """Map enough pages for ``new_len`` tokens of ``seq_id``."""
        have = len(self.alloc.blocks_of(seq_id))
        need = -(-new_len // self.block_size)
        if need > have:
            fresh = self.alloc.allocate(seq_id, need - have)
            self.block_table[seq_id, have:need] = fresh

    def free(self, seq_id: int) -> None:
        self.alloc.release(seq_id)
        self.block_table[seq_id] = -1
        self.lengths[seq_id] = 0


def paged_append(pool_k, pool_v, k_new, v_new, block_table, lengths,
                 block_size: int):
    """Scatter this step's per-sequence k/v token into its current page.

    pool_k/pool_v: [NB, BS, H, D] (or :class:`QuantizedKVPool`);
    k_new/v_new: [B, H, D]; block_table: [B, MB] int32; lengths: [B]
    (tokens already stored).  Returns updated (pool_k, pool_v) of the
    same representation.

    Quantized pools quantize the incoming rows per (token, head)
    (:func:`quantize_kv`) and scatter code row + scale row to the same
    (page, offset) — both are overwritten together on rollback, so a
    token's representation is fixed the moment it commits.
    """
    lengths = jnp.asarray(lengths)
    bt = jnp.asarray(block_table)
    pos = lengths                              # write slot per sequence
    blk_idx = pos // block_size
    off = pos % block_size
    phys = jnp.take_along_axis(bt, blk_idx[:, None], axis=1)[:, 0]
    # unmapped page (-1) must not wrap to the pool's last block and
    # corrupt another sequence: route it out of bounds so the scatter
    # drops it (callers are expected to ensure_capacity first)
    nb = (pool_k.data if is_quantized_pool(pool_k) else pool_k).shape[0]
    phys = jnp.where(phys < 0, nb, phys)
    if is_quantized_pool(pool_k):
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        pool_k = QuantizedKVPool(
            data=pool_k.data.at[phys, off].set(kq, mode="drop"),
            scale=pool_k.scale.at[phys, off].set(ks, mode="drop"))
        pool_v = QuantizedKVPool(
            data=pool_v.data.at[phys, off].set(vq, mode="drop"),
            scale=pool_v.scale.at[phys, off].set(vs, mode="drop"))
        return pool_k, pool_v
    pool_k = pool_k.at[phys, off].set(k_new, mode="drop")
    pool_v = pool_v.at[phys, off].set(v_new, mode="drop")
    return pool_k, pool_v


def decode_walk(lengths, max_blocks: int, block_size: int,
                positions: int = WALK_POSITIONS):
    """``(trips, chunk_pages)`` of :func:`paged_decode_attention`'s page
    walk (and, at its own ``positions`` a trip, of
    ``ops.mla.paged_latent_attention``'s) over a ``[B, max_blocks]``
    table: each trip gathers
    ``chunk_pages`` columns of every row, and ``trips`` covers the
    longest of ``lengths`` (tokens valid per row, capped at the table's
    width).  ``chunk_pages`` is static: about :data:`WALK_POSITIONS`
    positions, evened out over the table so that the last chunk is not
    mostly padding, and the whole table when it is narrower than that.
    ``trips`` follows ``lengths``: a traced scalar inside a program, an
    ``int`` for the numpy array the engine keeps on the host — ONE
    arithmetic, so the program's walk and the engine's
    ``decode_pages_walked`` cannot drift."""
    per_chunk = max(1, positions // block_size)
    chunk_pages = -(-max_blocks // -(-max_blocks // per_chunk))
    xp = np if isinstance(lengths, np.ndarray) else jnp
    longest = xp.minimum(xp.max(lengths), max_blocks * block_size)
    trips = -(-longest // (chunk_pages * block_size))
    return (int(trips) if xp is np else trips), chunk_pages


def paged_decode_attention(q, pool_k, pool_v, block_table, lengths,
                           scale: Optional[float] = None):
    """One decode step over a paged cache (reference
    block_multi_head_attention decode phase).

    q: [B, Hq, D]; pool_k/pool_v: [NB, BS, Hkv, D];
    block_table: [B, MB]; lengths: [B] tokens valid (AFTER appending the
    current token).  Returns [B, Hq, D].

    The table is walked in chunks of columns (:func:`decode_walk`): each
    trip gathers that chunk's pages of every row ([B, chunk, BS, Hkv,
    D]), scores them, masks positions at or past ``lengths`` and folds
    them into a running maximum, sum and output in float32 (the
    recurrence of ``ops/pallas/decode_attention.py``).  The trip count
    is computed from ``lengths`` where the program runs, so the work
    follows the LONGEST LIVE SEQUENCE, not the table's width, inside one
    compiled program: columns past the last trip are never read.  An
    unmapped entry (-1) reads page 0 and is masked like any position
    past its row's length; a row of length 0 yields finite output.

    Raises :class:`PagedKVGeometryError` (trace time, offending shapes
    in the message) when the q/pool/table geometry is inconsistent —
    head_dim drift, non-dividing GQA groups, mis-sized tables.
    """
    validate_paged_decode_geometry(q, pool_k, pool_v, block_table,
                                   lengths)
    B, Hq, D = q.shape
    NB, BS, Hkv, _ = pool_geometry(pool_k)
    MB = block_table.shape[1]
    G = Hq // Hkv
    s = scale if scale is not None else 1.0 / math.sqrt(D)
    lengths = jnp.minimum(jnp.asarray(lengths), MB * BS)
    trips, C = decode_walk(lengths, MB, BS)
    T = C * BS                                  # positions per trip
    bt = jnp.maximum(jnp.asarray(block_table), 0)     # -1 -> page 0 (masked)
    bt = jnp.pad(bt, ((0, 0), (0, (-MB) % C)))  # whole chunks only
    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32)

    def gather(pool, cols):                     # -> [B, T, Hkv, D] fp32
        # mode="clip": the default ("fill") adds a select over every
        # gathered element to NaN out-of-range pages; a table holds
        # page numbers of its own pool
        if is_quantized_pool(pool):
            # codes + scales of this chunk only; the math after the
            # dequantize is EXACTLY the full-width path's
            x = dequantize_kv(
                jnp.take(pool.data, cols, axis=0, mode="clip"),
                jnp.take(pool.scale, cols, axis=0, mode="clip"))
        else:
            x = jnp.take(pool, cols, axis=0,
                         mode="clip").astype(jnp.float32)
        return x.reshape(B, T, Hkv, D)

    def fold(i, carry):
        m, l, acc = carry
        cols = jax.lax.dynamic_slice_in_dim(bt, i * C, C, axis=1)
        logits = jnp.einsum("bkgd,btkd->bkgt", qg,
                            gather(pool_k, cols)) * s
        live = (i * T + jnp.arange(T))[None, None, None, :] \
            < lengths[:, None, None, None]
        logits = jnp.where(live, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "bkgt,btkd->bkgd", p, gather(pool_v, cols))
        return m_new, l, acc

    _, l, acc = jax.lax.fori_loop(
        0, trips, fold,
        (jnp.full((B, Hkv, G), NEG_INF, jnp.float32),
         jnp.zeros((B, Hkv, G), jnp.float32),
         jnp.zeros((B, Hkv, G, D), jnp.float32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Hq, D).astype(q.dtype)
