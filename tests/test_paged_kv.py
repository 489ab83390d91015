"""Paged KV cache (reference block_multi_head_attention /
test_block_multihead_attention.py): paged decode must equal dense-cache
decode; the allocator must share and reclaim pages."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.paged_kv import (BlockAllocator, PagedKVCache,
                                     QuantizedKVPool, decode_walk,
                                     dequantize_kv, paged_append,
                                     paged_decode_attention, quantize_kv)
from paddle_tpu.ops.pallas.decode_attention import decode_attention_ref

rng = np.random.default_rng(0)


class TestAllocator:
    def test_allocate_release_reuse(self):
        a = BlockAllocator(4)
        b0 = a.allocate(0, 2)
        b1 = a.allocate(1, 2)
        assert len(set(b0) | set(b1)) == 4 and a.free_blocks == 0
        with pytest.raises(RuntimeError):
            a.allocate(2, 1)
        a.release(0)
        assert a.free_blocks == 2
        b2 = a.allocate(2, 2)
        assert set(b2) == set(b0)    # pages recycled


class TestPagedAttention:
    def test_matches_dense_decode(self):
        B, Hq, Hkv, D, BS, NB = 2, 4, 2, 16, 4, 8
        T = 10                         # tokens already cached per seq
        q = rng.normal(size=(B, Hq, D)).astype(np.float32)
        dense_k = rng.normal(size=(B, 16, Hkv, D)).astype(np.float32)
        dense_v = rng.normal(size=(B, 16, Hkv, D)).astype(np.float32)
        lengths = np.array([T, 7], np.int32)

        # build the paged pool holding the same tokens
        pool_k = jnp.zeros((NB, BS, Hkv, D), jnp.float32)
        pool_v = jnp.zeros((NB, BS, Hkv, D), jnp.float32)
        table = np.full((B, 4), -1, np.int32)
        alloc = BlockAllocator(NB)
        for b in range(B):
            n = -(-int(lengths[b]) // BS)
            table[b, :n] = alloc.allocate(b, n)
            for t in range(int(lengths[b])):
                phys, off = table[b, t // BS], t % BS
                pool_k = pool_k.at[phys, off].set(dense_k[b, t])
                pool_v = pool_v.at[phys, off].set(dense_v[b, t])

        got = paged_decode_attention(q, pool_k, pool_v, table, lengths)
        ref = decode_attention_ref(jnp.asarray(q), jnp.asarray(dense_k),
                                   jnp.asarray(dense_v),
                                   jnp.asarray(lengths))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_append_then_attend(self):
        B, Hq, Hkv, D, BS, NB = 1, 2, 2, 8, 2, 4
        pool_k = jnp.zeros((NB, BS, Hkv, D), jnp.float32)
        pool_v = jnp.zeros((NB, BS, Hkv, D), jnp.float32)
        table = np.array([[0, 1, -1, -1]], np.int32)
        toks_k = rng.normal(size=(3, Hkv, D)).astype(np.float32)
        toks_v = rng.normal(size=(3, Hkv, D)).astype(np.float32)
        for t in range(3):            # crosses a page boundary at t=2
            pool_k, pool_v = paged_append(
                pool_k, pool_v, toks_k[None, t], toks_v[None, t], table,
                np.array([t], np.int32), BS)
        # page 0 holds tokens 0..1, page 1 holds token 2
        np.testing.assert_allclose(np.asarray(pool_k[0, 1]), toks_k[1])
        np.testing.assert_allclose(np.asarray(pool_k[1, 0]), toks_k[2])
        q = rng.normal(size=(B, Hq, D)).astype(np.float32)
        got = paged_decode_attention(q, pool_k, pool_v, table,
                                     np.array([3], np.int32))
        ref = decode_attention_ref(
            jnp.asarray(q), jnp.asarray(toks_k)[None],
            jnp.asarray(toks_v)[None], jnp.asarray([3]))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_cache_manager_flow(self):
        c = PagedKVCache(num_layers=1, num_blocks=8, block_size=4,
                        num_kv_heads=2, head_dim=8, max_batch=2)
        c.ensure_capacity(0, 10)       # 3 pages
        assert (c.block_table[0] >= 0).sum() == 3
        c.ensure_capacity(0, 11)       # still 3
        assert (c.block_table[0] >= 0).sum() == 3
        c.ensure_capacity(1, 20)       # 5 pages
        assert c.alloc.free_blocks == 0
        c.free(0)
        assert c.alloc.free_blocks == 3
        assert (c.block_table[0] == -1).all()


# ---------------------------------------------------------------------
# the length-bounded page walk (ISSUE 28)
# ---------------------------------------------------------------------
W_BS, W_HQ, W_HKV, W_D = 4, 4, 2, 8
# block_size 4: one trip covers 64 pages = 256 positions
WALK_CASES = {
    "narrower_than_a_chunk": (8, [30, 7, 17]),
    "exactly_one_chunk": (64, [256, 100, 3]),
    "several_chunks_longest_ends_mid_chunk": (192, [300, 40, 257]),
    "one_row_at_full_width": (192, [768, 5, 200]),
    "length_0_beside_live_rows": (192, [0, 300, 10]),
    "unmapped_entries_inside_the_walk_of_a_short_row": (192, [600, 9, 1]),
    "chunk_does_not_divide_the_table": (65, [260, 30, 131]),
}


def _walk_setup(kind, mb, lengths, *, map_whole_table=False, seed=0):
    """Dense K/V of ``mb * BS`` positions a row and the paged pool
    holding each row's first ``lengths[b]`` tokens (the table -1 past
    them, or — ``map_whole_table`` — every column mapped to a page of
    its own).  ``kind`` "int8" stores codes + scales; its dense
    reference is the dequantized rows, so paged == dense still holds
    at the file's tolerance."""
    r = np.random.default_rng(seed)
    B, T = len(lengths), mb * W_BS
    q = r.normal(size=(B, W_HQ, W_D)).astype(np.float32)
    dk = r.normal(size=(B, T, W_HKV, W_D)).astype(np.float32)
    dv = r.normal(size=(B, T, W_HKV, W_D)).astype(np.float32)
    table = np.full((B, mb), -1, np.int32)
    nxt = 1                             # page 0 stays what -1 reads
    for b, n in enumerate(lengths):
        pages = mb if map_whole_table else -(-int(n) // W_BS)
        table[b, :pages] = np.arange(nxt, nxt + pages)
        nxt += pages

    def paged(dense):                   # [B, T, ...] -> [NB, BS, ...]
        pool = np.zeros((nxt, W_BS) + dense.shape[2:], dense.dtype)
        for b in range(B):
            for c in np.nonzero(table[b] >= 0)[0]:
                pool[table[b, c]] = dense[b, c * W_BS:(c + 1) * W_BS]
        return pool

    if kind == "int8":
        (kc, ks), (vc, vs) = quantize_kv(dk), quantize_kv(dv)
        dk, dv = np.asarray(dequantize_kv(kc, ks)), \
            np.asarray(dequantize_kv(vc, vs))
        pools = [QuantizedKVPool(jnp.asarray(paged(np.asarray(c))),
                                 jnp.asarray(paged(np.asarray(s))))
                 for c, s in ((kc, ks), (vc, vs))]
    else:
        pools = [jnp.asarray(paged(dk)), jnp.asarray(paged(dv))]
    return q, dk, dv, pools, table, np.asarray(lengths, np.int32)


def _assert_live_rows_match(got, q, dk, dv, lengths):
    got = np.asarray(got)
    assert np.isfinite(got).all()       # a length-0 row too
    ref = np.asarray(decode_attention_ref(
        jnp.asarray(q), jnp.asarray(dk), jnp.asarray(dv),
        jnp.asarray(lengths)))
    live = lengths > 0
    np.testing.assert_allclose(got[live], ref[live], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kind", ["fp32", "int8"])
@pytest.mark.parametrize("case", sorted(WALK_CASES))
def test_walk_matches_dense_decode(kind, case):
    mb, lengths = WALK_CASES[case]
    q, dk, dv, (pk, pv), table, lengths = _walk_setup(kind, mb, lengths)
    got = paged_decode_attention(q, pk, pv, table, lengths)
    assert got.dtype == q.dtype
    _assert_live_rows_match(got, q, dk, dv, lengths)


@pytest.mark.parametrize("kind", ["fp32", "int8"])
def test_walk_one_compilation_serves_every_length(kind):
    """``lengths`` is traced: the trip count is data, not a program."""
    mb = 192
    q, dk, dv, (pk, pv), table, _ = _walk_setup(
        kind, mb, [mb * W_BS] * 3, map_whole_table=True)
    # a function of its own: jit's cache is keyed by the function
    f = jax.jit(lambda *a: paged_decode_attention(*a))
    for lengths in ([5, 0, 31], [300, 2, 256], [768, 513, 40]):
        lengths = np.asarray(lengths, np.int32)
        _assert_live_rows_match(f(q, pk, pv, table, lengths),
                                q, dk, dv, lengths)
    assert f._cache_size() == 1


@pytest.mark.parametrize("kind", ["fp32", "int8"])
def test_walk_never_reads_past_the_longest_sequence(kind):
    """Every column is mapped; the pages of the columns past the last
    trip are poisoned.  A walk of the whole table would carry the NaNs
    through its masked probabilities (0 * NaN); the bounded walk never
    gathers them."""
    mb, lengths = 192, [300, 40, 0]
    q, dk, dv, (pk, pv), table, lengths = _walk_setup(
        kind, mb, lengths, map_whole_table=True)
    trips, chunk_pages = decode_walk(lengths, mb, W_BS)
    assert (trips, chunk_pages) == (2, 64)
    beyond = table[:, trips * chunk_pages:].ravel()

    def poison(pool):
        if kind == "int8":
            return QuantizedKVPool(
                pool.data, pool.scale.at[beyond].set(jnp.nan))
        return pool.at[beyond].set(jnp.nan)

    got = jax.jit(paged_decode_attention)(
        q, poison(pk), poison(pv), table, lengths)
    _assert_live_rows_match(got, q, dk, dv, lengths)


@pytest.mark.parametrize("mb,bs,lengths,want", [
    (256, 16, [1, 1], (1, 16)),         # the serve cell's table
    (256, 16, [257, 768], (3, 16)),
    (256, 16, [4096, 9000], (16, 16)),  # capped at the table's width
    (8, 4, [30], (1, 8)),               # narrower than one chunk
    (65, 4, [131], (1, 33)),            # evened out: 2 chunks of 33
    (65, 4, [133], (2, 33)),
    (4, 512, [0, 0], (0, 1)),           # a page wider than a chunk
])
def test_decode_walk_arithmetic(mb, bs, lengths, want):
    """The host's count and the program's trip count are one helper:
    an int from a numpy array, the same number from a traced one."""
    lengths = np.asarray(lengths, np.int32)
    assert decode_walk(lengths, mb, bs) == want
    traced = jax.jit(lambda l: decode_walk(l, mb, bs)[0])(lengths)
    assert int(traced) == want[0]


def test_block_multihead_attention_appends_then_walks():
    """The incubate entry point (reference block_multihead_attention,
    decode phase) runs the same append + walk, and like the reference
    kernel it is inference-only."""
    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn.functional import block_multihead_attention
    # each row's last page has room for the token this step appends
    mb, lengths = 192, [299, 39, 5]
    q, dk, dv, (pk, pv), table, lengths = _walk_setup("fp32", mb, lengths)
    B = len(lengths)
    qkv = np.stack([q[:, :W_HKV], dk[np.arange(B), lengths],
                    dv[np.arange(B), lengths]], axis=1)   # [B, 3, H, D]
    qkv_t = paddle.to_tensor(qkv, stop_gradient=False)
    out, kc, vc = block_multihead_attention(
        qkv_t, paddle.to_tensor(np.asarray(pk)),
        paddle.to_tensor(np.asarray(pv)), None,
        paddle.to_tensor(lengths), None, block_tables=paddle.to_tensor(table),
        block_size=W_BS)
    ref = decode_attention_ref(
        jnp.asarray(q[:, :W_HKV]), jnp.asarray(dk), jnp.asarray(dv),
        jnp.asarray(lengths + 1))
    np.testing.assert_allclose(out.numpy(), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # the new token landed in its row's current page
    np.testing.assert_allclose(
        kc.numpy()[table[0, 299 // W_BS], 299 % W_BS], dk[0, 299])
    assert out.stop_gradient
