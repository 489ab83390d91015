"""Fused decode-step block op (ISSUE 9): value parity vs the per-op
composition across GPT and Llama block variants, the Pallas interpret
tier, autotune cache roundtrip, geometry fallback, engine greedy
bit-identity with fusion on/off (engine + ServingFrontend stream,
spec-decode enabled and disabled), and the typed paged-KV geometry
errors the fallback tier keys off."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.flags import FLAGS, set_flags
from paddle_tpu.ops.decode_block import (DecodeBlockSpec,
                                         DecodeBlockUnsupportedError,
                                         decode_block, decode_block_spec,
                                         decode_block_unsupported_reason,
                                         make_norm_ffn)
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
# tier parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", VARIANTS)
@pytest.mark.parametrize("dtype", DTYPES, ids=("fp32", "bf16"))
def test_xla_tier_bit_identical_to_per_op(kind, dtype):
    spec, lp, x, pk, pv, bt, ln, cos, sin = _variant(kind, dtype)
    ref = _per_op_reference(x, lp, pk, pv, bt, ln, cos, sin, spec)
    got = decode_block(x, lp, pk, pv, bt, ln, cos, sin, spec=spec,
                       backend="xla")
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r, np.float32),
                                      np.asarray(g, np.float32))


@pytest.mark.parametrize("kind", VARIANTS)
@pytest.mark.parametrize("dtype", DTYPES, ids=("fp32", "bf16"))
def test_pallas_tier_value_parity(kind, dtype):
    spec, lp, x, pk, pv, bt, ln, cos, sin = _variant(kind, dtype)
    ref = _per_op_reference(x, lp, pk, pv, bt, ln, cos, sin, spec)
    old = FLAGS.pallas_interpret
    set_flags({"pallas_interpret": True})
    try:
        got = decode_block(x, lp, pk, pv, bt, ln, cos, sin, spec=spec,
                           backend="pallas")
        # the traced path the engine's scan takes
        jit_got = jax.jit(lambda *a: decode_block(
            *a, spec=spec, backend="pallas"))(x, lp, pk, pv, bt, ln,
                                              cos, sin)
    finally:
        set_flags({"pallas_interpret": old})
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-5)
    for r, g, jg in zip(ref, got, jit_got):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(r, np.float32), **tol)
        np.testing.assert_allclose(np.asarray(jg, np.float32),
                                   np.asarray(r, np.float32), **tol)


def test_auto_dispatch_off_tpu_is_reference_tier():
    """With no TPU and no interpret flag, auto dispatch must take the
    per-op tier — the CPU tier-1 bit-identity story."""
    spec, lp, x, pk, pv, bt, ln, cos, sin = _variant("llama_gqa",
                                                     np.float32)
    ref = decode_block(x, lp, pk, pv, bt, ln, cos, sin, spec=spec,
                       backend="xla")
    got = decode_block(x, lp, pk, pv, bt, ln, cos, sin, spec=spec)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))


# ---------------------------------------------------------------------------
# geometry limits / typed fallback
# ---------------------------------------------------------------------------
def test_unsupported_head_dim_reason_and_raise():
    H, Hq, Hkv, D, F = 16, 2, 2, 512, 24     # D past the kernel cap
    spec = DecodeBlockSpec(hidden=H, num_heads=Hq, kv_heads=Hkv,
                           head_dim=D, block_size=4, norm="rms",
                           activation="swiglu", eps=1e-5, rope=True)
    lp = _llama_layer(H, Hq, Hkv, D, F, np.float32)
    pk, pv, bt, lengths = _geometry(Hkv=Hkv, D=D)
    x = _w(3, H)
    cos, sin = _w(3, D), _w(3, D)
    reason = decode_block_unsupported_reason(spec, lp, pk)
    assert reason is not None and "head_dim" in reason
    with pytest.raises(DecodeBlockUnsupportedError, match="head_dim"):
        decode_block(x, lp, pk, pv, bt, lengths, cos, sin, spec=spec,
                     backend="pallas")
    # auto dispatch silently takes the reference tier instead
    ref = decode_block(x, lp, pk, pv, bt, lengths, cos, sin, spec=spec,
                       backend="xla")
    old = FLAGS.pallas_interpret
    set_flags({"pallas_interpret": True})
    try:
        got = decode_block(x, lp, pk, pv, bt, lengths, cos, sin,
                           spec=spec)
    finally:
        set_flags({"pallas_interpret": old})
    np.testing.assert_array_equal(np.asarray(ref[0]), np.asarray(got[0]))


def test_unsupported_vmem_budget(monkeypatch):
    from paddle_tpu.ops.pallas import decode_block as pdb
    spec, lp, x, pk, pv, bt, ln, cos, sin = _variant("llama_gqa",
                                                     np.float32)
    assert decode_block_unsupported_reason(spec, lp, pk) is None
    monkeypatch.setattr(pdb, "VMEM_BUDGET_BYTES", 128)
    reason = decode_block_unsupported_reason(spec, lp, pk)
    assert reason is not None and "VMEM" in reason
    # auto dispatch silently falls back to the reference tier
    old = FLAGS.pallas_interpret
    set_flags({"pallas_interpret": True})
    try:
        got = decode_block(x, lp, pk, pv, bt, ln, cos, sin, spec=spec)
    finally:
        set_flags({"pallas_interpret": old})
    ref = decode_block(x, lp, pk, pv, bt, ln, cos, sin, spec=spec,
                       backend="xla")
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))


def test_moe_ffn_override_forces_reference_tier():
    spec, lp, x, pk, pv, bt, ln, cos, sin = _variant("llama_gqa",
                                                     np.float32)
    with pytest.raises(DecodeBlockUnsupportedError, match="FFN"):
        decode_block(x, lp, pk, pv, bt, ln, cos, sin, spec=spec,
                     ffn=lambda lp_, y: y, backend="pallas")


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
# autotune
# ---------------------------------------------------------------------------
def test_autotune_cache_roundtrip(tmp_path):
    from paddle_tpu.ops.pallas import autotune
    from paddle_tpu.ops.pallas.decode_block import tune_decode_block
    spec, lp, x, pk, pv, bt, ln, cos, sin = _variant("llama_gqa",
                                                     np.float32)
    path = tmp_path / "at.json"
    old = FLAGS.pallas_interpret
    set_flags({"use_autotune": True, "autotune_cache_file": str(path),
               "pallas_interpret": True})
    try:
        autotune.clear_cache()
        out = tune_decode_block(x, lp, pk, pv, bt, ln, cos, sin,
                                spec=spec)
        key = (spec.hidden, spec.num_heads, spec.kv_heads, spec.head_dim,
               spec.block_size, bt.shape[1], spec.activation,
               str(pk.dtype), None, -1)   # unquantized: weight_dtype/group
        won = autotune.lookup("decode_block", key, None)
        assert won is not None and int(won) >= 1
        # the winner persisted to disk for later processes
        import json
        with open(path) as f:
            on_disk = json.load(f)
        assert any(k.startswith("decode_block|") for k in on_disk), on_disk
        assert int(won) in [int(v) for k, v in on_disk.items()
                            if k.startswith("decode_block|")]
        ref = decode_block(x, lp, pk, pv, bt, ln, cos, sin, spec=spec,
                           backend="xla")
        np.testing.assert_allclose(np.asarray(out[0]),
                                   np.asarray(ref[0]), rtol=1e-5,
                                   atol=1e-5)
    finally:
        set_flags({"use_autotune": False, "autotune_cache_file": "",
                   "pallas_interpret": old})
        autotune.clear_cache()


# ---------------------------------------------------------------------------
# engine / serve-path bit-identity (the acceptance pins)
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


def _engine(cfg, params, fused, spec=False, **kw):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    spec_config = None
    if spec:
        from paddle_tpu.spec_decode import SpecDecodeConfig
        spec_config = SpecDecodeConfig(draft_cfg=cfg, draft_params=params,
                                       k=2, window=8)
    return ContinuousBatchingEngine(
        cfg, params, max_batch=2, block_size=8, num_blocks=64,
        fused_decode_block=fused, spec_config=spec_config, **kw)


def _drain(eng, prompts, sampled=False):
    for i, p in enumerate(prompts):
        eng.add_request(p, 6,
                        temperature=0.7 if (sampled and i == 1) else 0.0,
                        top_k=8 if (sampled and i == 1) else None,
                        seed=i)
    return eng.run_to_completion()


def test_engine_greedy_bit_identity_fused_on_off(tiny_serving):
    cfg, params, prompts = tiny_serving
    a = _drain(_engine(cfg, params, fused=True), prompts, sampled=True)
    b = _drain(_engine(cfg, params, fused=False), prompts, sampled=True)
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])


def test_frontend_stream_bit_identity_fused_on_off(tiny_serving):
    from paddle_tpu.serving import ServingFrontend
    cfg, params, prompts = tiny_serving

    def stream(fused):
        fe = ServingFrontend(_engine(cfg, params, fused=fused))
        handles = [fe.submit(p, max_new_tokens=6) for p in prompts]
        return [list(h) for h in handles]

    assert stream(True) == stream(False)


def test_spec_decode_verify_bit_identity_on_fused_path(tiny_serving):
    """The verify program wraps the engine's (now fused) step closure;
    greedy speculative output must stay bit-identical to baseline
    decode — fused on and off, spec on and off: all four agree."""
    cfg, params, prompts = tiny_serving
    runs = {(fused, spec): _drain(_engine(cfg, params, fused=fused,
                                          spec=spec), prompts)
            for fused in (True, False) for spec in (True, False)}
    base = runs[(False, False)]
    for key, out in runs.items():
        assert set(out) == set(base), key
        for k in base:
            np.testing.assert_array_equal(out[k], base[k], err_msg=str(key))


def test_aot_warm_start_covers_fusion_knob(tiny_serving, tmp_path):
    """The artifact config hash covers the knob: a fused export warm
    starts a fused engine bit-identically, and an UNFUSED engine
    pointed at the fused artifact falls back cleanly (no half-warm)."""
    from paddle_tpu.aot.serve import export_engine
    cfg, params, prompts = tiny_serving
    eng = _engine(cfg, params, fused=True, prefill_buckets=(8,))
    export_engine(eng, str(tmp_path))
    warm = _engine(cfg, params, fused=True, prefill_buckets=(8,),
                   aot_dir=str(tmp_path))
    assert warm.aot_loaded
    a = _drain(warm, prompts)
    b = _drain(_engine(cfg, params, fused=True, prefill_buckets=(8,)),
               prompts)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    cold = _engine(cfg, params, fused=False, prefill_buckets=(8,),
                   aot_dir=str(tmp_path))
    assert not cold.aot_loaded
    assert cold.aot_error is not None


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
    from paddle_tpu.ops.decode_block import prefill_block
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
                spec=spec, start=start,
                scale=1.0 / (cfg.head_dim ** 0.5)),
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
    from paddle_tpu.ops.decode_block import matmul_stored, serving_layout
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


@pytest.mark.parametrize("program", ("decode", "prefill"))
def test_pallas_tiers_read_qkv_as_stored(program):
    """Both megakernels stream ``q_wt``/``k_wt``/``v_wt`` and contract
    them as they lie: the same values as from the tree's layout."""
    from paddle_tpu.ops.decode_block import prefill_block, serving_layout
    spec, lp, x, pk, pv, bt, ln, cos, sin = _variant("llama_gqa",
                                                     np.float32)
    laid = serving_layout(lp)
    assert decode_block_unsupported_reason(spec, laid, pk) is None
    old = FLAGS.pallas_interpret
    set_flags({"pallas_interpret": True})
    try:
        if program == "decode":
            def run(w):
                return decode_block(x, w, pk, pv, bt, ln, cos, sin,
                                    spec=spec, backend="pallas")
        else:
            Ts, start = 4, 4
            pos = start + jnp.arange(Ts)
            xt = _w(1, Ts, spec.hidden, scale=0.5)
            c, s = _w(Ts, spec.head_dim, scale=1.0), \
                _w(Ts, spec.head_dim, scale=1.0)

            def run(w):
                return prefill_block(
                    xt, w, pk, pv, jnp.take(bt[0], pos // 4), pos % 4,
                    bt[0], None, c, s, spec=spec, start=jnp.int32(start),
                    backend="pallas")
        got, want = run(laid), run(lp)
    finally:
        set_flags({"pallas_interpret": old})
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-6)
