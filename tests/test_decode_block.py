"""The serving layer bodies (``ops/decode_block.py``): decode_block and
prefill_block bit-identical to the per-op composition written out here
across GPT and Llama block variants, the chain against the train step's
``block_apply`` on the same weights, speculative decoding on against
off, the typed paged-KV geometry errors, and the engine's layer scan
over whole pools."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.decode_block import (DecodeBlockSpec, decode_block,
                                         decode_block_spec, make_norm_ffn,
                                         prefill_block, serving_layout)
from paddle_tpu.ops.paged_kv import (PagedKVGeometryError, paged_append,
                                     paged_decode_attention)

rng = np.random.default_rng(7)


def _w(*shape, dtype=np.float32, scale=0.1):
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                       * scale, dtype=dtype)


def _llama_layer(H, Hq, Hkv, D, F, dtype, tied_norms=False):
    ln1 = _w(H, dtype=dtype, scale=1.0) + 1.0
    lp = {"ln1_w": ln1, "q_w": _w(H, Hq * D, dtype=dtype),
          "k_w": _w(H, Hkv * D, dtype=dtype),
          "v_w": _w(H, Hkv * D, dtype=dtype),
          "o_w": _w(Hq * D, H, dtype=dtype),
          "ln2_w": ln1 if tied_norms else _w(H, dtype=dtype,
                                             scale=1.0) + 1.0,
          "gate_w": _w(H, F, dtype=dtype), "up_w": _w(H, F, dtype=dtype),
          "down_w": _w(F, H, dtype=dtype)}
    return lp


def _gpt_layer(H, Hq, D, F, dtype):
    return {"ln1_w": _w(H, dtype=dtype, scale=1.0) + 1.0,
            "ln1_b": _w(H, dtype=dtype),
            "qkv_w": _w(H, 3 * H, dtype=dtype),
            "qkv_b": _w(3 * H, dtype=dtype),
            "proj_w": _w(H, H, dtype=dtype), "proj_b": _w(H, dtype=dtype),
            "ln2_w": _w(H, dtype=dtype, scale=1.0) + 1.0,
            "ln2_b": _w(H, dtype=dtype),
            "fc1_w": _w(H, F, dtype=dtype), "fc1_b": _w(F, dtype=dtype),
            "fc2_w": _w(F, H, dtype=dtype), "fc2_b": _w(H, dtype=dtype)}


def _geometry(B=3, MB=6, NB=16, BS=4, Hkv=2, dtype=np.float32, D=8):
    pool_k = _w(NB, BS, Hkv, D, dtype=dtype)
    pool_v = _w(NB, BS, Hkv, D, dtype=dtype)
    bt = np.full((B, MB), -1, np.int32)
    bt[0, :3] = [2, 5, 7]
    bt[1, :2] = [1, 4]
    bt[2, 0] = 9
    lengths = np.array([9, 5, 0], np.int32)[:B]
    return pool_k, pool_v, jnp.asarray(bt), jnp.asarray(lengths)


def _per_op_reference(x, lp, pool_k, pool_v, bt, lengths, cos, sin, spec):
    """The pre-ISSUE-9 per-op chain, written out independently of the op
    module (norm/rope/FFN inline) — what decode_block must reproduce."""
    B = x.shape[0]
    Hq, Hkv, D = spec.num_heads, spec.kv_heads, spec.head_dim

    def norm(x_, w, b=None):
        if spec.norm == "rms":
            ms = jnp.mean(jnp.square(x_.astype(jnp.float32)), -1,
                          keepdims=True)
            return (x_ * jax.lax.rsqrt(ms + spec.eps).astype(x_.dtype)) * w
        x32 = x_.astype(jnp.float32)
        mu = jnp.mean(x32, -1, keepdims=True)
        var = jnp.var(x32, -1, keepdims=True)
        return ((x32 - mu) * jax.lax.rsqrt(var + spec.eps)
                ).astype(x_.dtype) * w + b

    y = norm(x, lp["ln1_w"], lp.get("ln1_b"))
    if spec.fused_qkv:
        qkv = (y @ lp["qkv_w"] + lp["qkv_b"]).reshape(B, Hq, 3 * D)
        q, k, v = jnp.split(qkv, 3, axis=-1)
    else:
        q = (y @ lp["q_w"]).reshape(B, Hq, D)
        k = (y @ lp["k_w"]).reshape(B, Hkv, D)
        v = (y @ lp["v_w"]).reshape(B, Hkv, D)
    if spec.rope:
        def rot(t):
            d2 = t.shape[-1] // 2
            return jnp.concatenate([-t[..., d2:], t[..., :d2]], -1)

        q = q * cos[:, None, :] + rot(q) * sin[:, None, :]
        k = k * cos[:, None, :] + rot(k) * sin[:, None, :]
    pk, pv = paged_append(pool_k, pool_v, k, v, bt, lengths,
                          spec.block_size)
    attn = paged_decode_attention(q, pk, pv, bt, lengths + 1)
    proj = attn.reshape(B, -1) @ (lp["proj_w"] if spec.fused_qkv
                                  else lp["o_w"])
    x = x + (proj + lp["proj_b"] if spec.bias else proj)
    y2 = norm(x, lp["ln2_w"], lp.get("ln2_b"))
    if spec.activation == "swiglu":
        f = (jax.nn.silu(y2 @ lp["gate_w"]) * (y2 @ lp["up_w"])) \
            @ lp["down_w"]
    else:
        f = jax.nn.gelu(y2 @ lp["fc1_w"] + lp["fc1_b"],
                        approximate=True) @ lp["fc2_w"] + lp["fc2_b"]
    return x + f, pk, pv


def _variant(kind, dtype):
    H, D, BS = 32, 8, 4
    if kind == "llama_gqa":
        Hq, Hkv, F = 4, 2, 48
        spec = DecodeBlockSpec(hidden=H, num_heads=Hq, kv_heads=Hkv,
                               head_dim=D, block_size=BS, norm="rms",
                               activation="swiglu", eps=1e-5, rope=True)
        lp = _llama_layer(H, Hq, Hkv, D, F, dtype)
    elif kind == "llama_mha_tied":
        Hq = Hkv = 4
        spec = DecodeBlockSpec(hidden=H, num_heads=Hq, kv_heads=Hkv,
                               head_dim=D, block_size=BS, norm="rms",
                               activation="swiglu", eps=1e-5, rope=True)
        lp = _llama_layer(H, Hq, Hkv, D, 48, dtype, tied_norms=True)
    else:                                        # gpt: ln + gelu + bias
        Hq = Hkv = 4
        spec = DecodeBlockSpec(hidden=H, num_heads=Hq, kv_heads=Hq,
                               head_dim=D, block_size=BS, norm="ln",
                               activation="gelu", eps=1e-5, rope=False,
                               fused_qkv=True, bias=True)
        lp = _gpt_layer(H, Hq, D, 48, dtype)
    pool_k, pool_v, bt, lengths = _geometry(Hkv=Hkv, dtype=dtype, D=D)
    x = _w(3, H, dtype=dtype, scale=0.5)
    cos = _w(3, D, dtype=dtype, scale=1.0) if spec.rope else None
    sin = _w(3, D, dtype=dtype, scale=1.0) if spec.rope else None
    return spec, lp, x, pool_k, pool_v, bt, lengths, cos, sin


VARIANTS = ("llama_gqa", "llama_mha_tied", "gpt")
DTYPES = (np.float32, jnp.bfloat16)


# ---------------------------------------------------------------------------
# the layer bodies against the per-op composition
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", VARIANTS)
@pytest.mark.parametrize("dtype", DTYPES, ids=("fp32", "bf16"))
def test_decode_block_bit_identical_to_per_op(kind, dtype):
    spec, lp, x, pk, pv, bt, ln, cos, sin = _variant(kind, dtype)
    ref = _per_op_reference(x, lp, pk, pv, bt, ln, cos, sin, spec)
    got = decode_block(x, lp, pk, pv, bt, ln, cos, sin, spec=spec)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r, np.float32),
                                      np.asarray(g, np.float32))


def _prefill_case(kind, dtype, Ts=7, start=5, MB=6, NB=16):
    """One sequence's chunk fill: ``Ts`` prompt tokens at absolute
    positions ``start + [0, Ts)`` against a pool holding ``start``
    committed tokens in the sequence's block-table row (plus unrelated
    junk everywhere else, which the fill must ignore)."""
    spec, lp, *_ = _variant(kind, dtype)
    H, Hkv, D, BS = spec.hidden, spec.kv_heads, spec.head_dim, \
        spec.block_size
    pool_k = _w(NB, BS, Hkv, D, dtype=dtype)
    pool_v = _w(NB, BS, Hkv, D, dtype=dtype)
    bt_row = np.full((MB,), -1, np.int32)
    nb = -(-(start + Ts) // BS)
    bt_row[:nb] = [2, 5, 7, 9, 11, 13][:nb]
    x = _w(1, Ts, H, dtype=dtype, scale=0.5)
    cos = _w(Ts, D, dtype=dtype, scale=1.0) if spec.rope else None
    sin = _w(Ts, D, dtype=dtype, scale=1.0) if spec.rope else None
    return (spec, lp, x, pool_k, pool_v,
            *_fill_targets(jnp.asarray(bt_row), start, Ts, BS), cos, sin)


def _fill_targets(bt_row, start, Ts, BS):
    """``(blk, off, bt_row, mask)`` of a chunk fill, as the engine's
    ``_build_chunk_fill`` derives them from the table row."""
    pos = start + jnp.arange(Ts)
    blk = jnp.take(jnp.maximum(bt_row, 0), pos // BS)
    jpos = jnp.arange(bt_row.shape[0] * BS)[None, None, None, :]
    return blk, pos % BS, bt_row, jpos <= pos[None, None, :, None]


def _prefill_per_op_reference(x, lp, pool_k, pool_v, blk, off, bt_row, mask, cos,
                              sin, spec):
    """The per-op chunk-fill chain, written out independently of the op
    module — what prefill_block must reproduce bit-for-bit."""
    _, Ts, _ = x.shape
    Hq, Hkv, D = spec.num_heads, spec.kv_heads, spec.head_dim

    def norm(x_, w, b=None):
        if spec.norm == "rms":
            ms = jnp.mean(jnp.square(x_.astype(jnp.float32)), -1,
                          keepdims=True)
            return (x_ * jax.lax.rsqrt(ms + spec.eps).astype(x_.dtype)) * w
        x32 = x_.astype(jnp.float32)
        mu = jnp.mean(x32, -1, keepdims=True)
        var = jnp.var(x32, -1, keepdims=True)
        return ((x32 - mu) * jax.lax.rsqrt(var + spec.eps)
                ).astype(x_.dtype) * w + b

    y = norm(x, lp["ln1_w"], lp.get("ln1_b"))
    if spec.fused_qkv:
        qkv = (y @ lp["qkv_w"] + lp["qkv_b"]).reshape(1, Ts, Hq, 3 * D)
        q, k, v = jnp.split(qkv, 3, axis=-1)
    else:
        q = (y @ lp["q_w"]).reshape(1, Ts, Hq, D)
        k = (y @ lp["k_w"]).reshape(1, Ts, Hkv, D)
        v = (y @ lp["v_w"]).reshape(1, Ts, Hkv, D)
    if spec.rope:
        def rot(t):
            d2 = t.shape[-1] // 2
            return jnp.concatenate([-t[..., d2:], t[..., :d2]], -1)

        q = q * cos[None, :, None, :] + rot(q) * sin[None, :, None, :]
        k = k * cos[None, :, None, :] + rot(k) * sin[None, :, None, :]
    pool_k = pool_k.at[blk, off].set(k[0])
    pool_v = pool_v.at[blk, off].set(v[0])
    k_all = jnp.take(pool_k, jnp.maximum(bt_row, 0),
                     axis=0).reshape(1, -1, Hkv, D)
    v_all = jnp.take(pool_v, jnp.maximum(bt_row, 0),
                     axis=0).reshape(1, -1, Hkv, D)
    rep = Hq // Hkv
    if rep > 1:
        k_all = jnp.repeat(k_all, rep, axis=2)
        v_all = jnp.repeat(v_all, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_all) * (1.0 / D ** 0.5)
    logits = jnp.where(mask, logits.astype(jnp.float32), -1e30)
    p = jax.nn.softmax(logits, -1).astype(q.dtype)
    attn = jnp.einsum("bhqk,bkhd->bqhd", p, v_all).reshape(1, Ts, -1)
    proj = attn @ (lp["proj_w"] if spec.fused_qkv else lp["o_w"])
    x = x + (proj + lp["proj_b"] if spec.bias else proj)
    y2 = norm(x, lp["ln2_w"], lp.get("ln2_b"))
    if spec.activation == "swiglu":
        f = (jax.nn.silu(y2 @ lp["gate_w"]) * (y2 @ lp["up_w"])) \
            @ lp["down_w"]
    else:
        f = jax.nn.gelu(y2 @ lp["fc1_w"] + lp["fc1_b"],
                        approximate=True) @ lp["fc2_w"] + lp["fc2_b"]
    return x + f, pool_k, pool_v


@pytest.mark.parametrize("kind", ("llama_gqa", "gpt"))
@pytest.mark.parametrize("dtype", DTYPES, ids=("fp32", "bf16"))
def test_prefill_block_bit_identical_to_per_op(kind, dtype):
    spec, lp, x, pk, pv, blk, off, bt, mask, cos, sin = _prefill_case(
        kind, dtype)
    ref = _prefill_per_op_reference(x, lp, pk, pv, blk, off, bt, mask,
                                    cos, sin, spec)
    got = prefill_block(x, lp, pk, pv, blk, off, bt, mask, cos, sin,
                        spec=spec)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r, np.float32),
                                      np.asarray(g, np.float32))


# ---------------------------------------------------------------------------
# the chain against the train step's block
# ---------------------------------------------------------------------------
# (kind, q/k/v layout, (start, valid)): a prompt's first ``start`` tokens
# committed, a chunk of ``valid`` filled after them (a cold fill, a
# single-token tail, a chunk across several pages), one token decoded
_CHAIN_CASES = [("llama_gqa", "kn", g) for g in ((0, 8), (3, 1), (11, 9))] \
    + [("llama_mha_tied", "kn", (11, 9)), ("llama_gqa", "nk", (11, 9)),
       ("llama_mha_tied", "nk", (11, 9))]


@pytest.mark.parametrize("dtype,tol", ((np.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)),
                         ids=("fp32", "bf16"))
@pytest.mark.parametrize(
    "kind,layout,geometry", _CHAIN_CASES,
    ids=[f"{k}-{lay}-{s}+{v}" for k, lay, (s, v) in _CHAIN_CASES])
def test_chain_matches_train_block(kind, layout, geometry, dtype, tol):
    """What serving computes for a sequence, position by position
    through the paged pool, is what the train step's ``block_apply``
    computes for it at once: the same weights (in the tree's ``[K, N]``
    q/k/v or the engine's ``[N, K]``), the same tokens, the same RoPE
    tables."""
    from paddle_tpu.models.llama import (LlamaConfig, _rope_cos_sin,
                                         block_apply)
    start, valid = geometry
    S, BS = start + valid + 1, 4
    spec, lp, *_ = _variant(kind, dtype)
    cfg = LlamaConfig(hidden_size=spec.hidden, num_heads=spec.num_heads,
                      num_kv_heads=spec.kv_heads, intermediate_size=48,
                      rms_norm_eps=spec.eps)
    cos, sin = _rope_cos_sin(S, spec.head_dim, cfg.rope_theta,
                             jnp.dtype(dtype))
    x = _w(1, S, spec.hidden, dtype=dtype, scale=0.5)
    want = np.asarray(block_apply(lp, x, cfg, cos, sin), np.float32)

    served = serving_layout(lp) if layout == "nk" else lp
    assert ("q_wt" in served) == (layout == "nk")
    pk = _w(16, BS, spec.kv_heads, spec.head_dim, dtype=dtype)
    pv = _w(16, BS, spec.kv_heads, spec.head_dim, dtype=dtype)
    bt_row = jnp.asarray([2, 5, 7, 9, 11, 13], jnp.int32)
    got = []
    for lo, n in ((0, start), (start, valid)):
        if n:
            out, pk, pv = prefill_block(
                x[:, lo:lo + n], served, pk, pv,
                *_fill_targets(bt_row, lo, n, BS), cos[lo:lo + n],
                sin[lo:lo + n], spec=spec)
            got.append(out)
    out, pk, pv = decode_block(
        x[:, S - 1], served, pk, pv, bt_row[None],
        jnp.asarray([S - 1], jnp.int32), cos[S - 1:], sin[S - 1:],
        spec=spec)
    got = np.concatenate([np.asarray(g, np.float32) for g in got]
                         + [np.asarray(out, np.float32)[None]], axis=1)
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_paged_geometry_typed_errors():
    """Satellite: paged_decode_attention raises the typed geometry error
    naming the offending shapes instead of an einsum shape mismatch."""
    pool_k, pool_v, bt, lengths = _geometry()
    q_bad_d = _w(3, 4, 16)                    # pool has D=8
    with pytest.raises(PagedKVGeometryError, match="head_dim mismatch"):
        paged_decode_attention(q_bad_d, pool_k, pool_v, bt, lengths)
    q_bad_g = _w(3, 3, 8)                     # 3 q heads on 2 kv heads
    with pytest.raises(PagedKVGeometryError, match="multiple"):
        paged_decode_attention(q_bad_g, pool_k, pool_v, bt, lengths)
    q = _w(3, 4, 8)
    with pytest.raises(PagedKVGeometryError, match="block_table"):
        paged_decode_attention(q, pool_k, pool_v, bt[:2], lengths)
    with pytest.raises(PagedKVGeometryError, match="lengths"):
        paged_decode_attention(q, pool_k, pool_v, bt, lengths[:2])
    with pytest.raises(PagedKVGeometryError, match="pools"):
        paged_decode_attention(q, pool_k, pool_v[:, :2], bt, lengths)


# ---------------------------------------------------------------------------
# engine / serve-path bit-identity
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_serving():
    from paddle_tpu import parallel as dist
    from paddle_tpu.models.llama import build_llama_train_step, llama_tiny
    from paddle_tpu.parallel.topology import HybridTopology, set_topology
    cfg = llama_tiny()
    topo = dist.init_topology(devices=jax.devices()[:1])
    _, init_fn = build_llama_train_step(cfg, topo, num_microbatches=1)
    params = init_fn(0)["params"]
    set_topology(HybridTopology())
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 9, 17)]
    return cfg, params, prompts


def _engine(cfg, params, spec=False, **kw):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    spec_config = None
    if spec:
        from paddle_tpu.spec_decode import SpecDecodeConfig
        spec_config = SpecDecodeConfig(draft_cfg=cfg, draft_params=params,
                                       k=2, window=8)
    return ContinuousBatchingEngine(
        cfg, params, max_batch=2, block_size=8, num_blocks=64,
        spec_config=spec_config, **kw)


def _drain(eng, prompts):
    for i, p in enumerate(prompts):
        eng.add_request(p, 6, seed=i)
    return eng.run_to_completion()


def test_spec_decode_verify_bit_identity(tiny_serving):
    """The verify program wraps the engine's step closure; greedy
    speculative output must stay bit-identical to baseline decode."""
    cfg, params, prompts = tiny_serving
    base = _drain(_engine(cfg, params), prompts)
    out = _drain(_engine(cfg, params, spec=True), prompts)
    assert set(out) == set(base)
    for k in base:
        np.testing.assert_array_equal(out[k], base[k])


def test_make_norm_ffn_matches_legacy_alias():
    """serving._make_rms_ffn must stay importable and be the op-module
    closure source (the draft program imports it)."""
    from paddle_tpu.inference.serving import _make_rms_ffn
    assert _make_rms_ffn is make_norm_ffn


def test_decode_block_spec_from_configs():
    from paddle_tpu.models.llama import llama_tiny
    s = decode_block_spec(llama_tiny(), 8)
    assert (s.norm, s.activation, s.rope, s.fused_qkv) == \
        ("rms", "swiglu", True, False)
    from paddle_tpu.models.gpt import GPTConfig
    g = decode_block_spec(GPTConfig(vocab_size=64, hidden_size=32,
                                    num_layers=1, num_heads=4,
                                    max_position_embeddings=32), 8)
    assert (g.norm, g.activation, g.rope, g.fused_qkv, g.bias) == \
        ("ln", "gelu", False, True, True)


# ---------------------------------------------------------------------------
# the engine's layer scan (ISSUE 31): the pools whole in the carry, all
# layers as one pool, and q/k/v contracted in the layout they are stored
# in — against the formulation the engine had before, kept HERE
# ---------------------------------------------------------------------------
def _scan_model(kv_quant, dtype="float32"):
    """A 3-layer engine (page 0 of every layer holds its own garbage,
    so a masked read of the wrong layer's page would show) and the tree
    it was built from."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models.llama import llama_tiny, stack_block_params
    from paddle_tpu.ops.paged_kv import QuantizedKVPool
    from paddle_tpu.quantization import ServeQuantConfig
    cfg = llama_tiny(num_layers=3, dtype=dtype)
    k1, k2, k3 = jax.random.split(jax.random.key(31), 3)
    dt = jnp.dtype(dtype)
    params = {
        "wte": jax.random.normal(k1, (cfg.vocab_size, cfg.hidden_size),
                                 dt) * 0.05,
        "head": jax.random.normal(k2, (cfg.hidden_size, cfg.vocab_size),
                                  dt) * 0.05,
        "lnf_w": jnp.ones(cfg.hidden_size, dt),
        "blocks": stack_block_params(cfg, k3, 1)}
    eng = ContinuousBatchingEngine(
        cfg, params, max_batch=3, block_size=4, num_blocks=8,
        max_blocks_per_seq=4, prefill_buckets=(8,),
        quant_config=ServeQuantConfig(kv_dtype="int8") if kv_quant
        else None)
    shape = (cfg.num_layers, 8, 4, cfg.kv_heads, cfg.head_dim)

    def pool():
        if kv_quant:
            return QuantizedKVPool(
                data=jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
                scale=jnp.asarray(rng.uniform(1e-3, 2e-2, shape[:-1]),
                                  jnp.float32))
        return _w(*shape, dtype=dt, scale=1.0)

    return cfg, params, eng, pool(), pool()


def _scan_as_it_was(body_of, params, pool_k, pool_v):
    """The scan of the engine's programs before ISSUE 31: the stacked
    pools as scan INPUTS and outputs, each layer handed its own
    ``[NB, ...]`` pool.  ``params``: the engine's tree for the bits of
    the scan alone, the tree it was built from (``y @ w``) for q/k/v
    too."""
    from paddle_tpu.models.generation import _collapse_blocks

    def body(x, inp):
        lp, pk, pv = inp
        x, pk, pv = body_of(x, lp, pk, pv)
        return x, (pk, pv)

    def run(x):
        return jax.lax.scan(
            body, x, (_collapse_blocks(params["blocks"]), pool_k, pool_v))
    return run


def _head(cfg, params, x):
    from paddle_tpu.inference.serving import _make_rms_ffn
    xf = _make_rms_ffn(cfg)[0](x, params["lnf_w"])
    return jnp.einsum("bh,hv->bv", xf, params["head"],
                      preferred_element_type=jnp.float32)


def _rope_tables(cfg):
    from paddle_tpu.models.llama import _rope_cos_sin
    return _rope_cos_sin(cfg.max_position_embeddings, cfg.head_dim,
                         cfg.rope_theta, jnp.dtype(cfg.dtype), None)


def _step_as_it_was(eng, params):
    cfg = eng.cfg
    cos_full, sin_full = _rope_tables(cfg)
    spec = decode_block_spec(cfg, eng.BS)

    def step(pool_k, pool_v, bt, lengths, tokens):
        cos = jnp.take(cos_full, lengths, axis=0)
        sin = jnp.take(sin_full, lengths, axis=0)
        x, (pk, pv) = _scan_as_it_was(
            lambda x, lp, pk, pv: decode_block(
                x, lp, pk, pv, bt, lengths, cos, sin, spec=spec),
            params, pool_k, pool_v)(jnp.take(params["wte"], tokens, axis=0))
        return pk, pv, _head(cfg, params, x)

    return step


def _fill_as_it_was(eng, params, Ts):
    cfg, BS = eng.cfg, eng.BS
    cos_full, sin_full = _rope_tables(cfg)
    spec = decode_block_spec(cfg, BS)

    def fill(pool_k, pool_v, bt_row, start, toks, valid=None):
        pos = start + jnp.arange(Ts)
        blk = jnp.take(jnp.maximum(bt_row, 0), pos // BS)
        if valid is not None:        # padded rows: one layer's page NB
            blk = jnp.where(jnp.arange(Ts) < valid, blk,
                            jax.tree.leaves(pool_k)[0].shape[1])
        mask = jnp.arange(bt_row.shape[0] * BS)[None, None, None, :] \
            <= pos[None, None, :, None]
        cos = jnp.take(cos_full, pos, axis=0)
        sin = jnp.take(sin_full, pos, axis=0)
        x, (pk, pv) = _scan_as_it_was(
            lambda x, lp, pk, pv: prefill_block(
                x, lp, pk, pv, blk, pos % BS, bt_row, mask, cos, sin,
                spec=spec, scale=1.0 / (cfg.head_dim ** 0.5)),
            params, pool_k, pool_v)(
                jnp.take(params["wte"], toks, axis=0)[None])
        last = x[:, -1] if valid is None \
            else jnp.take(x, valid - 1, axis=1)
        return pk, pv, _head(cfg, params, last)

    return fill


def _same_bits(got, want, what):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype, what
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32),
                                      err_msg=what)


# slot 0: 5 tokens on pages 2, 5, the rest of its row unmapped; slot 1:
# 3 tokens on pages 1, 4 (verify appends three more); slot 2: idle (nothing mapped, length 0)
_BT = np.array([[2, 5, -1, -1], [1, 4, -1, -1], [-1, -1, -1, -1]],
               np.int32)
_LEN = np.array([5, 3, 0], np.int32)
_LIVE = np.array([0, 1])


@pytest.mark.parametrize("dtype", ("float32", "bfloat16"))
@pytest.mark.parametrize("kv_quant", (False, True),
                         ids=("full_kv", "int8_kv"))
def test_step_scan_over_whole_pools_bit_identical(kv_quant, dtype):
    """Decode step: pools (every bit, every layer) and the live rows'
    logits equal the old formulation's, with an idle slot and unmapped
    table entries.  (An idle row reads page 0 of the WHOLE pool where
    it read its layer's: garbage in, garbage out, never read.)"""
    cfg, params, eng, pk, pv = _scan_model(kv_quant, dtype)
    assert "q_wt" in eng.params["blocks"] and "q_w" in params["blocks"]
    args = (jnp.asarray(_BT), jnp.asarray(_LEN),
            jnp.asarray([7, 11, 0], jnp.int32))
    want = jax.jit(_step_as_it_was(eng, eng.params))(pk, pv, *args)
    got = jax.jit(eng._build_step())(eng.params, pk, pv, *args)
    _same_bits(got[:2], want[:2], "pools")
    _same_bits(got[2][_LIVE], want[2][_LIVE], "live logits")
    # and from the tree's own layout: the same products, summed in the
    # order the backend chooses for each layout (one bit apart at most)
    tree = jax.jit(_step_as_it_was(eng, params))(pk, pv, *args)
    np.testing.assert_allclose(
        np.asarray(got[2][_LIVE]), np.asarray(tree[2][_LIVE]),
        **(dict(rtol=2e-2, atol=2e-2) if dtype == "bfloat16"
           else dict(rtol=1e-4, atol=1e-5)))
    # the rows landed, in every layer (not only where the old code put
    # them too): the pools changed at page 5 / 1, offset 1 / 3
    assert not np.array_equal(
        np.asarray(jax.tree.leaves(got[0])[0], np.float32),
        np.asarray(jax.tree.leaves(pk)[0], np.float32))


@pytest.mark.parametrize("valid", (None, 8, 5),
                         ids=("unpadded", "full_bucket", "padded"))
@pytest.mark.parametrize("kv_quant", (False, True),
                         ids=("full_kv", "int8_kv"))
def test_fill_scan_over_whole_pools_bit_identical(kv_quant, valid):
    """Chunk fill of 8 rows after a committed page: a padded bucket's
    rows (``valid`` 5) are dropped in EVERY layer — sent to one layer's
    page NB they would land in the next layer's page 0."""
    cfg, params, eng, pk, pv = _scan_model(kv_quant)
    bt_row = jnp.asarray([3, 6, 1, -1], jnp.int32)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, 8), jnp.int32)
    tail = () if valid is None else (jnp.int32(valid),)
    # the old scan compiles itself; the head after it runs per op (in
    # ONE program the CPU compiler fuses the old form's last row into
    # its loop and rounds the logits differently from the old form run
    # per op, with which the new program agrees to the bit)
    want = _fill_as_it_was(eng, eng.params, 8)(
        pk, pv, bt_row, jnp.int32(4), toks, *tail)
    got = jax.jit(eng._build_chunk_fill(8))(
        eng.params, pk, pv, bt_row, jnp.int32(4), toks, *tail)
    _same_bits(got, want, "pools and logits")
    if valid == 5:                   # page 0 of every layer is untouched
        for g, p in zip(jax.tree.leaves(got[:2]),
                        jax.tree.leaves((pk, pv))):
            np.testing.assert_array_equal(np.asarray(g[:, 0]),
                                          np.asarray(p[:, 0]))


@pytest.mark.parametrize("kv_quant", (False, True),
                         ids=("full_kv", "int8_kv"))
def test_verify_scan_inherits_the_whole_pool_step(kv_quant):
    """The spec-decode verify program scans the SAME step closure: three
    positions through it equal three through the old formulation."""
    from paddle_tpu.spec_decode.verify import build_verify_program
    cfg, params, eng, pk, pv = _scan_model(kv_quant)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (3, 3)), jnp.int32)
    old = _step_as_it_was(eng, eng.params)
    want = jax.jit(build_verify_program(
        lambda _, *a: old(*a)))(None, pk, pv, jnp.asarray(_BT),
                                jnp.asarray(_LEN), toks)
    got = jax.jit(build_verify_program(eng._build_step()))(
        eng.params, pk, pv, jnp.asarray(_BT), jnp.asarray(_LEN), toks)
    _same_bits(got[:2], want[:2], "pools")
    _same_bits(got[2][_LIVE], want[2][_LIVE], "live logits")


@pytest.mark.parametrize("dtype", DTYPES, ids=("fp32", "bf16"))
@pytest.mark.parametrize("lead", ((3,), (1, 8)), ids=("rows", "tile"))
def test_matmul_stored_contracts_either_layout(lead, dtype):
    """``[N, K]`` under ``name + "t"`` contracts to ``y @ w``: the same
    bits wherever every sum is exact (small whole numbers), and within
    rounding on random values, where the CPU backend sums the two
    layouts in different orders at some shapes; eager and compiled.
    The layout is made once: a tree that has it, or holds codes, comes
    back as it is."""
    from paddle_tpu.ops.decode_block import matmul_stored
    own = np.random.default_rng(31)
    w = _w(2, 64, 24, dtype=dtype)
    laid = serving_layout({"q_w": w, "o_w": w, "k_w__q": w})
    assert set(laid) == {"q_wt", "o_w", "k_w__q"}
    assert laid["q_wt"].shape == (2, 24, 64)
    assert serving_layout(laid).keys() == laid.keys() \
        and serving_layout(laid)["q_wt"] is laid["q_wt"]
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-6)
    for exact in (True, False):
        if exact:
            y = jnp.asarray(own.integers(-2, 3, (*lead, 64)), dtype)
            wk = jnp.asarray(own.integers(-2, 3, (64, 24)), dtype)
        else:
            y, wk = _w(*lead, 64, dtype=dtype), w[1]
        want = np.asarray(y @ wk, np.float32)
        for f in (matmul_stored, jax.jit(matmul_stored, static_argnums=1)):
            for lp in ({"q_wt": wk.T}, {"q_w": wk}):
                got = np.asarray(f(lp, "q_w", y), np.float32)
                if exact:
                    np.testing.assert_array_equal(got, want)
                else:
                    np.testing.assert_allclose(got, want, **tol)
