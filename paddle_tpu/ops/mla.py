"""Multi-head latent attention (MLA; DeepSeek-V2/V3, GLM-4.7-Flash's
``glm4_moe_lite``) for the serving path: its projections, its two forms,
and the paged pool of ONE vector a token they read.

The equations (``rms`` an RMS norm with a gain; ``nh`` heads; ``d_n`` /
``d_r`` the un-rotated and rotated parts of a query or key head, ``d_v``
a value head; ``r_kv`` the latent's width)::

    c_q = rms(x W_qa);  q = c_q W_qb -> nh x [q_n (d_n) | q_r (d_r)]
                        (or q = x W_q: no low-rank step, ``q_lora_rank`` None)
    [c | k_r] = x W_kva;  c = rms(c)               (r_kv | d_r)
    per head  k_n = c W_uk (d_n),  v = c W_uv (d_v)
    score = (q_n . k_n + rope(q_r) . rope(k_r)) * scale
    o = sum softmax(score) v;  out = concat_heads(o) W_o

RoPE turns ``q_r`` of every head and the ONE ``k_r`` all heads share.
What a token leaves behind is ``[c | rope(k_r)]``: ``r_kv + d_r`` values
a layer (576 for GLM-4.7-Flash, against ``2 x 20 x 256`` for its
per-head keys and values), ``c`` AFTER its norm and ``k_r`` AFTER its
rotation, so a cached token is never normed or turned again.

Two forms, the same numbers:

* **expanded** (:func:`expanded_attention`): decompress the latent to
  per-head ``k_n`` and ``v`` and attend as usual.  ``2 r_kv nh (d_n +
  d_v)`` FLOPs to decompress a cached token ONCE, then ``2 nh (d_n + d_r
  + d_v)`` a query: what a chunk fill wants, whose hundreds of queries
  share the decompression.
* **absorbed** (:func:`absorb_query`, :func:`paged_latent_attention`,
  :func:`lift_output`): fold ``W_uk`` into the query (``q~ = q_n
  W_uk^T``, ``r_kv`` wide), attend over the latent itself, multi-query:
  ONE ``r_kv + d_r`` key a token for all heads, whose first ``r_kv``
  columns are also its value, and fold ``W_uv`` after (``o = (sum p c)
  W_uv``).  ``2 nh (2 r_kv + d_r)`` FLOPs a query and cached token and
  nothing to decompress: what a decode step wants, whose one query a
  sequence would pay the decompression alone.

The weights are held split per head, ``uk_w [nh, r_kv, d_n]`` and ``uv_w
[nh, r_kv, d_v]`` (the public ``kv_b_proj [r_kv, nh x (d_n + d_v)]`` cut
at ``d_n``), the layout both forms contract in place.

The paged pool is ``[NB, BS, W]``: one array, no value pool, ``W`` the
latent's ``r_kv + d_r`` values rounded up to whole lanes of 128
(``MlaSpec.pool_width``: 640 for 576; the tail is zeros, in the rows
and in the queries).  A row of 576 is what the TPU pads to 640 anyway
in the layout its matmuls read, and handed ``[.., 16, 576]`` as an
argument the compiler keeps it in another, unpadded layout and copies
the whole pool into the padded one and back in every program
(4.4 GB each way at the benchmark's size: PERF.md, PR 34).
:func:`paged_latent_attention` walks a ``[B, MB]`` block table as
``ops.paged_kv.paged_decode_attention`` does, with its arithmetic
(``decode_walk``: a trip gathers a chunk of columns of every row, the
trips reach the longest live row, so the engine's
``decode_pages_walked`` counts this walk too), as a sibling and not a
call of it: that function takes a key pool AND a value pool of one
geometry, gathers each, and turns both tiles to float32 — handed the
latent pool twice it reads every page twice (or leans on the compiler
to find the two gathers equal), multiplies the ``d_r`` columns no value
has, and doubles the tile's bytes.  Here a trip gathers the tile once,
in the served dtype, and the value product reads its first ``r_kv``
columns.  ``tests/test_glm_moe_lite.py`` holds the sibling to
``paged_decode_attention`` at that shape.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .paged_kv import NEG_INF, decode_walk

__all__ = ["MlaSpec", "rms_norm", "WALK_POSITIONS", "FILL_POSITIONS", "project",
           "rope_pairs", "rope_angles", "absorb_query", "lift_output", "gate_heads",
           "expanded_attention", "absorbed_attention", "latent_append",
           "paged_latent_attention", "paged_expanded_attention"]

#: positions one trip of the decode walk covers (64 pages of 16).  Four
#: times ``ops.paged_kv.WALK_POSITIONS``: a latent row is 1,152 bytes
#: against a Mistral token's 2 x 2,048, and a long context at a trip of
#: 256 positions spends its time stepping the loop (27 trips a layer at
#: 6,900 tokens against 7).
WALK_POSITIONS = 1024

#: positions one trip of a chunk fill's walk decompresses and scores
#: (its float32 scores are ``nh x Ts x`` this).
FILL_POSITIONS = 512


@dataclasses.dataclass(frozen=True)
class MlaSpec:
    """The sizes of one latent-attention layer."""
    hidden: int
    num_heads: int
    #: None: the query has no low-rank step (``q = x W_q``, the leaf
    #: ``q_w``; Ling-3.0's ``q_lora_rank: null``)
    q_lora_rank: Optional[int]
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 10000.0
    #: DeepseekV3's ``rope_interleave``: a head's rotated columns pair
    #: as ``(2i, 2i + 1)``; False: as ``(i, i + d_r / 2)``
    rope_interleave: bool = True
    #: eps of the two inner norms (``q_a_layernorm``,
    #: ``kv_a_layernorm``: the public implementation builds them with
    #: its RMS norm's default, not with ``rms_norm_eps``)
    latent_norm_eps: float = 1e-6
    block_size: int = 16

    @property
    def latent_width(self) -> int:
        """Values a token leaves in the pool, a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_width(self) -> int:
        """A pool row: the latent in whole lanes of 128."""
        return -(-self.latent_width // 128) * 128

    @property
    def scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5


def rms_norm(x, w, eps: float):
    """RMS norm with a gain: float32 statistics, the scale applied in
    ``x``'s dtype (the serving paths' convention,
    ``ops.decode_block.make_norm``)."""
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x * jax.lax.rsqrt(ms + eps).astype(x.dtype)) * w


def rope_angles(pos, spec: MlaSpec) -> Tuple[jax.Array, jax.Array]:
    """``(cos, sin)`` float32 ``[..., d_r / 2]`` at positions ``pos``,
    computed where they are used (a table over the published 202,752
    positions would be a constant of every program)."""
    half = spec.qk_rope_head_dim // 2
    inv = 1.0 / (spec.rope_theta ** (
        jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def rope_pairs(t, cos, sin, interleave: bool):
    """Turn the pairs of ``t [..., d_r]`` by ``cos`` / ``sin [..., d_r /
    2]`` (broadcast over heads by the caller), in float32.  The result
    is laid out halves-apart (pair ``i`` at ``i`` and ``i + d_r / 2``)
    whatever the pairing read: the layout the public implementation
    leaves, and a score needs only that query and key agree."""
    t = t.astype(jnp.float32)
    if interleave:
        a, b = t[..., 0::2], t[..., 1::2]
    else:
        a, b = jnp.split(t, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def project(y, lp, pos, spec: MlaSpec):
    """The projections of normed rows ``y [..., H]`` at positions ``pos
    [...]``: ``(q_n [..., nh, d_n], q_r [..., nh, d_r] rotated, latent
    [..., W])``, the latent as the pool stores it (``c`` normed, ``k_r``
    rotated, zeros up to the pool's width), all in ``y``'s dtype."""
    nh, dn, dr = (spec.num_heads, spec.qk_nope_head_dim,
                  spec.qk_rope_head_dim)
    eps = spec.latent_norm_eps
    if spec.q_lora_rank is None:
        q = y @ lp["q_w"]
    else:
        q = rms_norm(y @ lp["q_a_w"], lp["q_a_ln_w"], eps) @ lp["q_b_w"]
    q = q.reshape(*y.shape[:-1], nh, dn + dr)
    kv = y @ lp["kv_a_w"]
    c = rms_norm(kv[..., :spec.kv_lora_rank], lp["kv_a_ln_w"], eps)
    cos, sin = rope_angles(pos, spec)
    q_r = rope_pairs(q[..., dn:], cos[..., None, :], sin[..., None, :],
                     spec.rope_interleave).astype(y.dtype)
    k_r = rope_pairs(kv[..., spec.kv_lora_rank:], cos, sin,
                     spec.rope_interleave).astype(y.dtype)
    tail = jnp.zeros(c.shape[:-1] + (spec.pool_width - spec.latent_width,),
                     c.dtype)
    return q[..., :dn], q_r, jnp.concatenate([c, k_r, tail], -1)


def absorb_query(q_n, q_r, uk_w, width: Optional[int] = None):
    """``[q~ | q_r]``: ``q_n [..., nh, d_n]`` through ``W_uk^T`` into
    the latent's space, beside the rotated part: the query of
    multi-query attention over the latent, ``[..., nh, r_kv + d_r]``
    (zeros up to ``width``, a pool row's, when given)."""
    qt = jnp.einsum("...hn,hrn->...hr", q_n, uk_w,
                    preferred_element_type=jnp.float32).astype(q_n.dtype)
    parts = [qt, q_r]
    pad = (width or 0) - qt.shape[-1] - q_r.shape[-1]
    if pad > 0:
        parts.append(jnp.zeros(qt.shape[:-1] + (pad,), qt.dtype))
    return jnp.concatenate(parts, -1)


def lift_output(o_lat, uv_w):
    """``(sum p c) W_uv``: ``[..., nh, r_kv]`` -> ``[..., nh x d_v]``."""
    o = jnp.einsum("...hr,hrv->...hv", o_lat, uv_w,
                   preferred_element_type=jnp.float32).astype(o_lat.dtype)
    return o.reshape(*o.shape[:-2], -1)


def gate_heads(o, gate):
    """A mixer's output gated a HEAD: ``o [..., nh x d]`` times
    ``sigmoid(gate [..., nh])`` (float32 inside), in ``o``'s dtype —
    Ling-3.0's ``gated_attention_proj_granularity_type: head_wise``, on
    its latent and its linear-attention layers alike."""
    nh = gate.shape[-1]
    g = jax.nn.sigmoid(gate.astype(jnp.float32))[..., None]
    oh = o.reshape(*o.shape[:-1], nh, -1).astype(jnp.float32) * g
    return oh.reshape(o.shape).astype(o.dtype)


def expanded_attention(q_n, q_r, latent, uk_w, uv_w, mask, scale: float):
    """The expanded form on ONE sequence: queries ``q_n [Q, nh, d_n]``,
    ``q_r [Q, nh, d_r]`` over ``latent [T, r_kv + d_r]`` under ``mask
    [Q, T]``; float32 softmax.  Returns ``[Q, nh x d_v]``."""
    r, dr = uk_w.shape[1], q_r.shape[-1]
    c, k_r = latent[:, :r], latent[:, r:r + dr]
    k_n = jnp.einsum("tr,hrn->thn", c, uk_w)
    v = jnp.einsum("tr,hrv->thv", c, uv_w)
    s = (jnp.einsum("qhn,thn->hqt", q_n, k_n,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("qhr,tr->hqt", q_r, k_r,
                      preferred_element_type=jnp.float32)) * scale
    p = jax.nn.softmax(jnp.where(mask[None], s, NEG_INF), -1)
    o = jnp.einsum("hqt,thv->qhv", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.astype(q_n.dtype).reshape(q_n.shape[0], -1)


def absorbed_attention(q_n, q_r, latent, uk_w, uv_w, mask, scale: float):
    """The absorbed form on the same arguments as
    :func:`expanded_attention`, and the same numbers."""
    r = uk_w.shape[1]
    qa = absorb_query(q_n, q_r, uk_w, latent.shape[-1])
    s = jnp.einsum("qhd,td->hqt", qa, latent,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(mask[None], s, NEG_INF), -1)
    o = jnp.einsum("hqt,tr->qhr", p.astype(latent.dtype), latent[:, :r],
                   preferred_element_type=jnp.float32)
    return lift_output(o.astype(q_n.dtype), uv_w)


def latent_append(pool, latent, block_table, lengths, block_size: int):
    """Scatter one new row a sequence, ``latent [B, W]``, into ``pool
    [NB, BS, W]`` at position ``lengths[b]`` of row ``b``'s table; an
    unmapped page (negative) writes nowhere."""
    phys = jnp.take_along_axis(
        block_table, (lengths // block_size)[:, None], axis=1)[:, 0]
    phys = jnp.where(phys < 0, pool.shape[0], phys)
    return pool.at[phys, lengths % block_size].set(latent, mode="drop")


def paged_latent_attention(q, pool, block_table, lengths, r_kv: int,
                           scale: float):
    """One decode step of absorbed MLA over the paged latent.

    ``q [B, nh, W]`` (:func:`absorb_query`); ``pool [NB, BS, W]``;
    ``block_table [B, MB]``; ``lengths [B]`` tokens valid AFTER the
    append.  Returns ``sum p c``, ``[B, nh, r_kv]``.

    The table is walked a chunk of columns at a time
    (``decode_walk(..., positions=WALK_POSITIONS)``), as far as the
    longest row reaches and no further, inside one compiled program;
    each trip gathers its pages ONCE (``[B, T, W]``, the served dtype),
    scores all heads against them, masks positions at or past
    ``lengths`` and folds them into a running maximum, sum and output in
    float32.  An unmapped entry reads page 0 and is masked; a row of
    length 0 yields finite output."""
    B, nh, W = q.shape
    NB, BS, Wp = pool.shape
    if Wp != W or block_table.shape[0] != B or r_kv > W:
        raise ValueError(
            f"paged_latent_attention: q {q.shape} against a pool of "
            f"{pool.shape} rows and a table {block_table.shape}")
    MB = block_table.shape[1]
    lengths = jnp.minimum(jnp.asarray(lengths), MB * BS)
    trips, C = decode_walk(lengths, MB, BS, positions=WALK_POSITIONS)
    T = C * BS
    bt = jnp.maximum(jnp.asarray(block_table), 0)
    bt = jnp.pad(bt, ((0, 0), (0, (-MB) % C)))

    def fold(i, carry):
        m, l, acc = carry
        cols = jax.lax.dynamic_slice_in_dim(bt, i * C, C, axis=1)
        tile = jnp.take(pool, cols, axis=0,
                        mode="clip").reshape(B, T, W)
        s = jnp.einsum("bhd,btd->bht", q, tile,
                       preferred_element_type=jnp.float32) * scale
        live = (i * T + jnp.arange(T))[None, None, :] \
            < lengths[:, None, None]
        s = jnp.where(live, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "bht,btr->bhr", p.astype(pool.dtype), tile[..., :r_kv],
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    with jax.named_scope("mla_decode_attention"):
        _, l, acc = jax.lax.fori_loop(
            0, trips, fold,
            (jnp.full((B, nh), NEG_INF, jnp.float32),
             jnp.zeros((B, nh), jnp.float32),
             jnp.zeros((B, nh, r_kv), jnp.float32)))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def paged_expanded_attention(q_n, q_r, pool, bt_row, pos, last, uk_w,
                             uv_w, scale: float):
    """A chunk fill's attention, the expanded form over the paged
    latent of ONE sequence: queries ``q_n [Ts, nh, d_n]``, ``q_r [Ts,
    nh, d_r]`` at absolute positions ``pos [Ts]`` against every cached
    position ``<= pos`` of the row ``bt_row [MB]`` (the chunk's own
    latents are in the pool by then).  ``last``: the highest position a
    real query sits at; the walk decompresses ``FILL_POSITIONS`` cached
    tokens a trip and stops past ``last``, so a short prefix costs a
    short walk whatever the table's width.  Returns ``[Ts, nh x d_v]``."""
    Ts, nh, _ = q_n.shape
    NB, BS, W = pool.shape
    r, dr = uk_w.shape[1], q_r.shape[-1]
    dv = uv_w.shape[2]
    MB = bt_row.shape[0]
    C = min(MB, max(1, FILL_POSITIONS // BS))
    T = C * BS
    trips = last // T + 1
    bt = jnp.pad(jnp.maximum(bt_row, 0), (0, (-MB) % C))

    def fold(i, carry):
        m, l, acc = carry
        cols = jax.lax.dynamic_slice_in_dim(bt, i * C, C)
        tile = jnp.take(pool, cols, axis=0, mode="clip").reshape(T, W)
        c, k_r = tile[:, :r], tile[:, r:r + dr]
        k_n = jnp.einsum("tr,hrn->thn", c, uk_w)
        v = jnp.einsum("tr,hrv->thv", c, uv_w)
        s = (jnp.einsum("qhn,thn->hqt", q_n, k_n,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("qhr,tr->hqt", q_r, k_r,
                          preferred_element_type=jnp.float32)) * scale
        seen = (i * T + jnp.arange(T))[None, None, :] \
            <= pos[None, :, None]
        s = jnp.where(seen, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "hqt,thv->hqv", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    with jax.named_scope("mla_fill_attention"):
        _, l, acc = jax.lax.fori_loop(
            0, trips, fold,
            (jnp.full((nh, Ts), NEG_INF, jnp.float32),
             jnp.zeros((nh, Ts), jnp.float32),
             jnp.zeros((nh, Ts, dv), jnp.float32)))
    o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q_n.dtype)
    return o.transpose(1, 0, 2).reshape(Ts, nh * dv)
