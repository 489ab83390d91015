"""Quantized serving end-to-end (ISSUE 16): PTQ export round-trip,
the int8/int4 weight-only decode path against its dequantized-weight
reference, greedy bit-identity WITHIN a quant config across every serve
surface (engine, frontend stream, HTTP wire, spec-decode, prefix-cache
hit), the int8-KV capacity win at fixed pool bytes, quantized spill round-trips
(preempt/restore, prefix offload, CRC bit-rot typed fallback,
cross-config mismatch guards), and the AOT config hash covering the
quant config.

Tolerance tiers: fp32 1e-5 and bf16 2e-2 follow test_decode_block; the
QUANTIZED tier is NOT a new numeric promise about the original weights
— int8 absmax rounding moves each weight by up to scale/2, so outputs
are compared against the DEQUANTIZED-weight reference at the fp32 tier
(the quantized path must compute exactly what its stored codes say)
and against the original weights only at the documented loose
``QUANT_TOL`` sanity bound.
"""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import parallel as dist
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models.llama import build_llama_train_step, llama_tiny
from paddle_tpu.ops.decode_block import DecodeBlockSpec, decode_block
from paddle_tpu.ops.paged_kv import (QuantizedKVPool, dequantize_kv,
                                     is_quantized_pool, kv_page_bytes,
                                     quantize_kv, zeros_kv_pool)
from paddle_tpu.parallel.topology import HybridTopology, set_topology
from paddle_tpu.quantization import (ServeQuantConfig,
                                     calibrate_weight_thresholds,
                                     dequantize_block_weight,
                                     quantize_params_for_serving)
from paddle_tpu.quantization.serve import _quantize_matrix
from paddle_tpu.serving.prefix_cache import PrefixCacheConfig
from paddle_tpu.serving.resilience import (SpillCorruptError,
                                           restore_into_slot,
                                           snapshot_slot)

rng = np.random.default_rng(16)

# absmax rounding perturbs each weight by <= scale/2 — absmax/254 at
# int8, absmax/14 at int4 — so the documented SANITY tier vs the
# ORIGINAL weights (not a parity claim) scales with the code width
QUANT_TOL = {"int8": dict(rtol=5e-2, atol=5e-2),
             "int4": dict(rtol=2e-1, atol=2e-1)}

CONFIGS = (
    ServeQuantConfig(weight_dtype="int8"),
    ServeQuantConfig(weight_dtype="int8", group_size=64),
    ServeQuantConfig(weight_dtype="int4", group_size=64),
    ServeQuantConfig(weight_dtype="int8", kv_dtype="int8"),
    ServeQuantConfig(kv_dtype="int8"),
)


@pytest.fixture(scope="module")
def model():
    cfg = llama_tiny()
    topo = dist.init_topology(devices=jax.devices()[:1])
    _, init_fn = build_llama_train_step(cfg, topo, num_microbatches=1)
    params = init_fn(0)["params"]
    set_topology(HybridTopology())
    return cfg, params


def _prompt(n):
    return rng.integers(0, 256, (n,)).astype(np.int32)


def _engine(model, qc=None, **kw):
    cfg, params = model
    kw.setdefault("max_batch", 2)
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 64)
    return ContinuousBatchingEngine(cfg, params, quant_config=qc, **kw)


def _drain(eng, prompts, max_new=6, sampled=False):
    rids = [eng.add_request(
        p, max_new,
        temperature=0.7 if (sampled and i == 1) else 0.0,
        top_k=8 if (sampled and i == 1) else None, seed=i)
        for i, p in enumerate(prompts)]
    res = eng.run_to_completion()
    rep = eng.kv_leak_report()
    assert rep["leaked"] == 0 and rep["unaccounted"] == 0, rep
    return [res[r] for r in rids]


# ---------------------------------------------------------------------
# PTQ export round-trip (satellite: observer-calibrated reference)
# ---------------------------------------------------------------------
@pytest.mark.parametrize("qc", [c for c in CONFIGS if c.quantized_weights],
                         ids=lambda c: f"{c.weight_dtype}/g{c.group_size}")
def test_ptq_round_trip_within_rounding_bound(model, qc):
    """Export llama_tiny, dequantize every exported weight, and check
    each element sits within scale/2 of the original — the absmax
    rounding bound, the tightest claim PTQ can make."""
    cfg, params = model
    out = quantize_params_for_serving(params, qc)
    checked = 0
    for name, v in params["blocks"].items():
        if name + "__q" not in out["blocks"]:
            assert name in out["blocks"]      # passed through untouched
            continue
        q = np.asarray(out["blocks"][name + "__q"])
        s = np.asarray(out["blocks"][name + "__s"])
        flat = np.asarray(v, np.float32).reshape((-1,) + v.shape[-2:])
        fq = q.reshape((-1,) + q.shape[-2:])
        fs = s.reshape((-1,) + s.shape[-2:]) if s.ndim > v.ndim - 1 \
            else s.reshape((-1,) + s.shape[-1:])
        for i in range(flat.shape[0]):
            K = flat[i].shape[0]
            deq = np.asarray(dequantize_block_weight(fq[i], fs[i], qc, K))
            gs = qc.group_size
            srow = np.repeat(fs[i], gs, axis=0)[:K] if gs != -1 else fs[i]
            np.testing.assert_array_less(
                np.abs(deq - flat[i]),
                np.broadcast_to(srow * 0.5 + 1e-7, deq.shape),
                err_msg=f"{name}[{i}] outside the rounding bound")
        checked += 1
    assert checked >= 7            # q/k/v/o/gate/up/down all quantized


def test_ptq_calibrated_thresholds_become_scales(model):
    """The observer-calibrated per-channel absmax IS the exported int8
    scale (x qmax): calibration-time statistics survive into the served
    tree byte-for-byte."""
    cfg, params = model
    qc = ServeQuantConfig(weight_dtype="int8")
    th = calibrate_weight_thresholds(params)
    out = quantize_params_for_serving(params, qc, thresholds=th)
    for name, t in th.items():
        s = np.asarray(out["blocks"][name + "__s"])
        flat = s.reshape((-1, s.shape[-1]))
        np.testing.assert_allclose(
            flat, np.maximum(t, 1e-8) / 127.0, rtol=1e-7,
            err_msg=f"{name} scales are not the calibrated thresholds")
        # and the weights themselves ARE the observer statistic, so the
        # calibrated export equals the raw-absmax export
    raw = quantize_params_for_serving(params, qc)
    for k in out["blocks"]:
        np.testing.assert_array_equal(np.asarray(out["blocks"][k]),
                                      np.asarray(raw["blocks"][k]), k)


# ---------------------------------------------------------------------
# the quantized decode path
# ---------------------------------------------------------------------
# the leaves the PTQ export replaces with ``__q``/``__s`` pairs (norm
# gains stay full width)
_MATMUL_NAMES = ("q_w", "k_w", "v_w", "o_w", "gate_w", "up_w", "down_w")


def _quant_layer(lp, qc):
    out = {}
    for n, v in lp.items():
        if n in _MATMUL_NAMES:
            q, s = _quantize_matrix(np.asarray(v, np.float32), qc)
            out[n + "__q"] = jnp.asarray(q)
            out[n + "__s"] = jnp.asarray(s)
        else:
            out[n] = v
    return out


def _decode_case(dtype, qc, kv_quant=False):
    H, Hq, Hkv, D, F = 32, 4, 2, 8, 48
    spec = DecodeBlockSpec(
        hidden=H, num_heads=Hq, kv_heads=Hkv, head_dim=D, block_size=4,
        norm="rms", activation="swiglu", eps=1e-5, rope=True,
        weight_dtype=qc.weight_dtype if qc else None,
        group_size=qc.group_size if qc else -1)

    def w(*shape, scale=0.1):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                           * scale, dtype)

    lp = {"ln1_w": w(H, scale=1.0) + 1.0, "q_w": w(H, Hq * D),
          "k_w": w(H, Hkv * D), "v_w": w(H, Hkv * D),
          "o_w": w(Hq * D, H), "ln2_w": w(H, scale=1.0) + 1.0,
          "gate_w": w(H, F), "up_w": w(H, F), "down_w": w(F, H)}
    pk, pv = w(16, 4, Hkv, D), w(16, 4, Hkv, D)
    if kv_quant:
        pk = QuantizedKVPool(*quantize_kv(pk))
        pv = QuantizedKVPool(*quantize_kv(pv))
    bt = np.full((2, 6), -1, np.int32)
    bt[0, :2], bt[1, :1] = [2, 5], [1]
    lengths = jnp.asarray(np.array([5, 3], np.int32))
    x = w(2, H, scale=0.5)
    cos, sin = w(2, D, scale=1.0), w(2, D, scale=1.0)
    return spec, lp, x, pk, pv, jnp.asarray(bt), lengths, cos, sin


@pytest.mark.parametrize("qc", [c for c in CONFIGS if c.quantized_weights],
                         ids=lambda c: f"{c.weight_dtype}/g{c.group_size}")
def test_quant_chain_matches_dequantized_reference(qc):
    """The quantized chain computes exactly what its stored codes
    say: output == the UNQUANTIZED op run on dequantized weights, at
    the fp32 tier (1e-5) — and stays within QUANT_TOL of the original
    weights."""
    spec, lp, x, pk, pv, bt, ln, cos, sin = _decode_case(
        np.float32, qc, kv_quant=qc.quantized_kv)
    qlp = _quant_layer(lp, qc)
    got, _, _ = decode_block(x, qlp, pk, pv, bt, ln, cos, sin,
                             spec=spec)
    deq = dict(lp)
    for n in lp:
        if n in _MATMUL_NAMES:
            deq[n] = dequantize_block_weight(
                qlp[n + "__q"], qlp[n + "__s"], qc, lp[n].shape[0])
    fp_spec = DecodeBlockSpec(
        hidden=spec.hidden, num_heads=spec.num_heads,
        kv_heads=spec.kv_heads, head_dim=spec.head_dim,
        block_size=spec.block_size, norm="rms", activation="swiglu",
        eps=1e-5, rope=True)
    ref, _, _ = decode_block(x, deq, pk, pv, bt, ln, cos, sin,
                             spec=fp_spec)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    orig, _, _ = decode_block(x, lp, pk, pv, bt, ln, cos, sin,
                              spec=fp_spec)
    np.testing.assert_allclose(np.asarray(got), np.asarray(orig),
                               **QUANT_TOL[qc.weight_dtype])


# ---------------------------------------------------------------------
# greedy bit-identity WITHIN a quant config, across every serve surface
# ---------------------------------------------------------------------
@pytest.mark.parametrize("qc", CONFIGS,
                         ids=lambda c: f"{c.weight_dtype}/g{c.group_size}"
                                       f"/kv{c.kv_dtype}")
def test_engine_deterministic_within_config(model, qc):
    prompts = [_prompt(5), _prompt(9), _prompt(17)]
    a = _drain(_engine(model, qc), prompts, sampled=True)
    b = _drain(_engine(model, qc), prompts, sampled=True)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_bit_identity_across_serve_surfaces(model):
    """One quant config (int8 weights + int8 KV), one answer: batch
    engine == frontend stream == HTTP/SSE wire == spec-decode engine ==
    prefix-cache hit, token for token."""
    from paddle_tpu.serving import HttpServingServer, ServingFrontend
    from paddle_tpu.serving.http import iter_sse
    from paddle_tpu.spec_decode import SpecDecodeConfig
    import http.client
    import json

    cfg, params = model
    qc = ServeQuantConfig(weight_dtype="int8", kv_dtype="int8")
    prompts = [_prompt(5), _prompt(9)]
    ref = _drain(_engine(model, qc), prompts)

    fe_streams = []
    fe = ServingFrontend(_engine(model, qc))
    for p in prompts:
        fe_streams.append(list(fe.submit(p, max_new_tokens=6)))
    for p, toks, full in zip(prompts, fe_streams, ref):
        np.testing.assert_array_equal(
            np.concatenate([p, np.asarray(toks, np.int32)]), full)

    srv = HttpServingServer(ServingFrontend(_engine(model, qc)))
    with srv:
        for p, full in zip(prompts, ref):
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=120)
            conn.request("POST", "/v1/generate",
                         json.dumps({"prompt_ids": p.tolist(),
                                     "max_new_tokens": 6}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            toks = {}
            for event, data in iter_sse(resp):
                if event == "token":
                    toks[data["i"]] = data["t"]
                else:
                    break
            conn.close()
            got = [toks[i] for i in sorted(toks)]
            np.testing.assert_array_equal(
                np.concatenate([p, np.asarray(got, np.int32)]), full)

    spec_eng = ContinuousBatchingEngine(
        cfg, params, max_batch=2, block_size=8, num_blocks=64,
        quant_config=qc,
        spec_config=SpecDecodeConfig(draft_cfg=cfg, draft_params=params,
                                     k=2, window=8))
    for x, y in zip(_drain(spec_eng, prompts), ref):
        np.testing.assert_array_equal(x, y)

    # prefix hit: same prompt twice through one engine; the second run
    # reuses committed quantized pages and must match the cold answer
    eng = _engine(model, qc)
    cold = _drain(eng, prompts)
    warm = _drain(eng, prompts)
    assert eng.prefix_stats()["hits"] >= 1
    for x, y, z in zip(cold, warm, ref):
        np.testing.assert_array_equal(x, z)
        np.testing.assert_array_equal(y, z)


# ---------------------------------------------------------------------
# int8 KV capacity at fixed pool bytes
# ---------------------------------------------------------------------
def test_int8_kv_capacity_at_fixed_pool_bytes():
    """At an identical pool byte budget and head_dim 64, int8 KV pages
    admit >= 1.8x the concurrent sequences of bf16 pages, draining at
    zero leaked blocks (the ISSUE 16 acceptance row)."""
    ccfg = llama_tiny(hidden_size=128, num_heads=2, num_kv_heads=2,
                      num_layers=2, dtype="bfloat16")
    topo = dist.init_topology(devices=jax.devices()[:1])
    _, init_fn = build_llama_train_step(ccfg, topo, num_microbatches=1)
    cparams = init_fn(0)["params"]
    set_topology(HybridTopology())
    page_bf16 = kv_page_bytes(16, ccfg.kv_heads, ccfg.head_dim,
                              dtype_itemsize=2)
    page_int8 = kv_page_bytes(16, ccfg.kv_heads, ccfg.head_dim,
                              dtype_itemsize=2, kv_quant=True)
    budget = 16 * page_bf16 * ccfg.num_layers * 2

    def capacity(kv_quant):
        page = page_int8 if kv_quant else page_bf16
        blocks = budget // (page * ccfg.num_layers * 2)
        eng = ContinuousBatchingEngine(
            ccfg, cparams, max_batch=16, block_size=16,
            num_blocks=int(blocks), prefill_buckets=(32,),
            quant_config=ServeQuantConfig(kv_dtype="int8")
            if kv_quant else None)
        r = np.random.default_rng(8)
        for _ in range(16):
            eng.add_request(
                r.integers(0, ccfg.vocab_size, (24,)).astype(np.int32),
                8)
        peak = 0
        while eng.queue or eng.finished \
                or any(s is not None for s in eng.slots):
            eng.step()
            peak = max(peak, eng.active_requests)
        rep = eng.kv_leak_report()
        assert rep["leaked"] == 0 and rep["unaccounted"] == 0, rep
        return peak

    base, quant = capacity(False), capacity(True)
    assert quant / base >= 1.8, (base, quant)


def test_quant_pool_allocation_matches_page_model():
    """zeros_kv_pool under kv_quant allocates exactly the bytes
    kv_page_bytes models — the capacity claim rests on this."""
    shape = (2, 8, 16, 2, 64)
    pool = zeros_kv_pool(shape, jnp.bfloat16, kv_quant=True)
    assert is_quantized_pool(pool)
    got = pool.data.nbytes + pool.scale.nbytes
    per_page = kv_page_bytes(16, 2, 64, dtype_itemsize=2, kv_quant=True)
    assert got == per_page * 2 * 8
    dense = zeros_kv_pool(shape, jnp.bfloat16)
    assert dense.nbytes == kv_page_bytes(16, 2, 64,
                                         dtype_itemsize=2) * 2 * 8


# ---------------------------------------------------------------------
# quantized spill tiers: preempt/restore, offload, bit-rot, mismatch
# ---------------------------------------------------------------------
def test_quant_preempt_restore_bit_identity(model):
    qc = ServeQuantConfig(weight_dtype="int8", kv_dtype="int8")
    prompts = [_prompt(9), _prompt(17)]
    want = _drain(_engine(model, qc), prompts)

    eng = _engine(model, qc)
    rids = [eng.add_request(p, 6) for p in prompts]
    eng.step()
    slot = next(s for s in range(eng.B) if eng.slots[s] is not None)
    eng.preempt(slot)
    res = eng.run_to_completion()
    assert eng.resilience["restores"] >= 1
    rep = eng.kv_leak_report()
    assert rep["leaked"] == 0 and rep["unaccounted"] == 0, rep
    for r, w in zip(rids, want):
        np.testing.assert_array_equal(res[r], w)


def test_quant_snapshot_crc_and_mismatch_guards(model):
    """KVSnapshot of a quantized slot carries codes + scales under a
    chained CRC: verify() catches bit-rot in EITHER array, and a
    cross-config restore (dense snapshot into a quant engine or vice
    versa) raises the typed SpillCorruptError instead of silently
    casting garbage."""
    qc = ServeQuantConfig(kv_dtype="int8")
    eng = _engine(model, qc)
    eng.add_request(_prompt(17), 4)
    eng.step()
    slot = next(s for s in range(eng.B) if eng.slots[s] is not None)
    snap = snapshot_slot(eng, slot)
    assert snap.k_scale is not None
    snap.verify()                         # clean: no raise
    snap.k_pages.view("uint8").reshape(-1)[0] ^= 0xFF
    with pytest.raises(SpillCorruptError, match="CRC"):
        snap.verify()                     # bit-rot in the CODES
    snap.k_pages.view("uint8").reshape(-1)[0] ^= 0xFF
    snap.verify()
    snap.k_scale.view("uint8").reshape(-1)[0] ^= 0xFF
    with pytest.raises(SpillCorruptError, match="CRC"):
        snap.verify()                     # bit-rot in the SCALES
    snap.k_scale.view("uint8").reshape(-1)[0] ^= 0xFF

    dense = _engine(model, None)
    dense.add_request(_prompt(17), 4)
    dense.step()
    dslot = next(s for s in range(dense.B)
                 if dense.slots[s] is not None)
    dsnap = snapshot_slot(dense, dslot)
    assert dsnap.k_scale is None
    with pytest.raises(SpillCorruptError, match="quantiz"):
        restore_into_slot(eng, slot, dsnap)
    with pytest.raises(SpillCorruptError, match="quantiz"):
        restore_into_slot(dense, dslot, snap)
    assert not eng.spill_compatible(dsnap)
    assert not dense.spill_compatible(snap)


def test_quant_prefix_offload_roundtrip_and_bitrot(model):
    """The prefix cache's host-RAM tier holds QUANTIZED pages (codes +
    scales): offload -> restore streams the cold answer bit-identically,
    and flipped host bytes fail the chained CRC typed, falling back to
    suffix recompute with zero leaks."""
    import faults
    qc = ServeQuantConfig(weight_dtype="int8", kv_dtype="int8")
    A = _prompt(21)
    cold_eng = _engine(model, qc, max_batch=1,
                       enable_prefix_caching=False)
    rid = cold_eng.add_request(A, 4)
    want = cold_eng.run_to_completion()[rid]

    eng = _engine(model, qc, max_batch=1,
                  prefix_cache_config=PrefixCacheConfig(
                      offload_capacity_bytes=1 << 24))
    a = eng.add_request(A, 4)
    res = eng.run_to_completion()
    stolen = eng.alloc.acquire(eng.alloc.free_blocks)
    try:
        eng.add_request(_prompt(9), 4)    # pressure -> evict -> offload
        res.update(eng.run_to_completion())
    finally:
        eng.alloc.release(stolen)
    ps = eng.prefix_stats()
    assert ps["offloaded_blocks"] >= 2, ps
    # offloaded nodes carry scales (quantized payloads)
    assert any(n.k_scale is not None
               for n in eng.prefix_cache._host_lru.values())
    c = eng.add_request(A, 4)
    res.update(eng.run_to_completion())
    assert eng.prefix_stats()["restores"] >= 2
    np.testing.assert_array_equal(res[a], want)
    np.testing.assert_array_equal(res[c], want)

    # round 2: corrupt the re-offloaded pages -> typed fallback
    stolen = eng.alloc.acquire(eng.alloc.free_blocks)
    try:
        eng.add_request(_prompt(9), 4)
        eng.run_to_completion()
    finally:
        eng.alloc.release(stolen)
    assert faults.corrupt_offloaded_prefix(eng, n=8) >= 2
    d = eng.add_request(A, 4)
    res = eng.run_to_completion()
    assert eng.prefix_stats()["restore_failures"] >= 1
    np.testing.assert_array_equal(res[d], want)
    rep = eng.kv_leak_report()
    assert rep["leaked"] == 0 and rep["unaccounted"] == 0, rep


# ---------------------------------------------------------------------
# AOT: the artifact hash covers the quant config
# ---------------------------------------------------------------------
def test_aot_hash_covers_quant_config(model, tmp_path):
    from paddle_tpu.aot.serve import export_engine
    qc = ServeQuantConfig(weight_dtype="int8", kv_dtype="int8")
    geom = dict(prefill_buckets=(8,))
    eng = _engine(model, qc, **geom)
    export_engine(eng, str(tmp_path))
    warm = _engine(model, qc, aot_dir=str(tmp_path), **geom)
    assert warm.aot_loaded, warm.aot_error
    prompts = [_prompt(5), _prompt(9)]
    a = _drain(warm, prompts)
    b = _drain(_engine(model, qc, **geom), prompts)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    # a DIFFERENT quant config must refuse the artifact, not half-load
    for other in (None, ServeQuantConfig(weight_dtype="int8"),
                  ServeQuantConfig(weight_dtype="int4", group_size=64,
                                   kv_dtype="int8")):
        cold = _engine(model, other, aot_dir=str(tmp_path), **geom)
        assert not cold.aot_loaded and cold.aot_error is not None, other


# ---------------------------------------------------------------------
# guards
# ---------------------------------------------------------------------
def test_kv_quant_round_trip_tolerance():
    """quantize_kv/dequantize_kv: per-(token, head) absmax keeps the
    round-trip within 1/127 of each head-row's absmax."""
    x = jnp.asarray(rng.standard_normal((4, 8, 2, 16)).astype(np.float32))
    codes, scale = quantize_kv(x)
    assert codes.dtype == jnp.int8
    back = np.asarray(dequantize_kv(codes, scale, jnp.float32))
    bound = np.max(np.abs(np.asarray(x)), axis=-1, keepdims=True) / 127.0
    assert (np.abs(back - np.asarray(x)) <= bound + 1e-7).all()


def test_moe_rejects_weight_quantization(model):
    cfg, params = model
    import dataclasses
    moe_cfg = dataclasses.replace(cfg, moe_num_experts=2)
    with pytest.raises(NotImplementedError, match="MoE"):
        ContinuousBatchingEngine(
            moe_cfg, params, max_batch=2, block_size=8, num_blocks=64,
            quant_config=ServeQuantConfig(weight_dtype="int8"))
