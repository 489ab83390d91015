"""Autoregressive decoding: KV-cache prefill + per-token decode + sampling.

Reference surface being matched:
* decode attention — masked_multihead_attention_kernel.cu (MMHA): one query
  token vs. a growing KV cache; here ops/pallas/decode_attention.py.
* generation loop — the reference serves generation through
  fused_multi_transformer + model-zoo ``generate()`` helpers; here a single
  jitted ``lax.scan`` over decode steps with STATIC shapes (prompt padded to
  its length, cache preallocated to ``max_len``) so XLA compiles one
  program for the whole rollout.
* sampling — greedy / temperature / top-k / top-p, matching
  ``paddle.tensor.search.top_p_sampling`` semantics.

Functions take the SAME pure param pytrees as the compiled train steps
(models/gpt.py / models/llama.py ``init_fn``), with stacked block leaves
``[S, per, ...]`` collapsed to ``[L, ...]`` — so a trained single-host
state plugs in directly.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.pallas.decode_attention import decode_attention

__all__ = ["sample_logits", "gpt_generate", "llama_generate",
           "llama_speculative_generate", "gpt_speculative_generate",
           "build_gpt_decoder", "build_llama_decoder"]


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------
def sample_logits(logits, key, *, temperature: float = 1.0,
                  top_k: Optional[int] = None, top_p: Optional[float] = None):
    """Sample token ids from [B, V] logits.  temperature<=0 → greedy."""
    if temperature is None or temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / temperature
    if top_k is not None and top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None and 0.0 < top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative prob >= top_p
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[:, None],
                                     axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def _collapse_blocks(blocks: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """[S, per, ...] (pipeline-stacked) -> [L, ...]."""
    return {k: v.reshape((-1,) + v.shape[2:]) for k, v in blocks.items()}


# ---------------------------------------------------------------------------
# GPT decoder
# ---------------------------------------------------------------------------
def build_gpt_decoder(cfg, max_len: int, use_pallas: Optional[bool] = None,
                      with_chunk: bool = False):
    """Returns (prefill, step).

    prefill(params, ids [B,T0]) -> (cache, logits_last [B,V])
    step(params, cache, token [B], pos scalar) -> (cache, logits [B,V])

    cache = {"k": [L,B,max_len,H,D], "v": ...} preallocated, static shape.
    """
    H, D, L = cfg.num_heads, cfg.head_dim, cfg.num_layers
    eps = cfg.layer_norm_eps
    moe = getattr(cfg, "moe_num_experts", 0)
    if moe and getattr(cfg, "moe_router", "topk") != "topk":
        raise NotImplementedError(
            "decode serves token-choice routing only (expert choice "
            "competes across the batch — non-causal at decode)")

    def ln(x, w, b):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * w + b

    def ffn(lp, y):
        """Dense GELU MLP or the dropless grouped-GEMM MoE bank."""
        if moe:
            from ..parallel.moe import moe_gelu_ffn_grouped
            return moe_gelu_ffn_grouped(
                y, lp["gate_w"], lp["e_w1"], lp["e_b1"], lp["e_w2"],
                lp["e_b2"], top_k=cfg.moe_top_k)
        return jax.nn.gelu(y @ lp["fc1_w"] + lp["fc1_b"],
                           approximate=True) @ lp["fc2_w"] + lp["fc2_b"]

    def final_logits(params, x):
        x = ln(x, params["lnf_w"], params["lnf_b"])
        return jnp.einsum("bh,vh->bv", x, params["wte"],
                          preferred_element_type=jnp.float32)

    def prefill(params, ids):
        """Run the full prompt through the (non-cached) forward, filling
        the cache from the per-layer K/V projections."""
        B, T0 = ids.shape
        blocks = _collapse_blocks(params["blocks"])
        pos = jnp.arange(T0)
        x = jnp.take(params["wte"], ids, axis=0) \
            + jnp.take(params["wpe"], pos, axis=0)[None]

        def body(x, lp):
            y = ln(x, lp["ln1_w"], lp["ln1_b"])
            qkv = y @ lp["qkv_w"] + lp["qkv_b"]
            qkv = qkv.reshape(B, T0, H, 3 * D)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            scale = 1.0 / math.sqrt(D)
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
            mask = jnp.tril(jnp.ones((T0, T0), bool))
            logits = jnp.where(mask, logits.astype(jnp.float32), -1e30)
            p = jax.nn.softmax(logits, -1).astype(x.dtype)
            attn = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, T0, -1)
            x = x + attn @ lp["proj_w"] + lp["proj_b"]
            x = x + ffn(lp, ln(x, lp["ln2_w"], lp["ln2_b"]))
            return x, (k, v)

        x, (ks, vs) = jax.lax.scan(body, x, blocks)
        # ks: [L, B, T0, H, D] -> preallocated cache
        pad = max_len - T0
        cache = {
            "k": jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
            "v": jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
        }
        return cache, final_logits(params, x[:, -1])

    def step(params, cache, token, pos):
        """One decode step at position ``pos`` — a 0-based global index,
        scalar (all rows aligned) or [B] vector (per-row positions, the
        batched-speculative case)."""
        B = token.shape[0]
        vec = jnp.ndim(pos) == 1
        blocks = _collapse_blocks(params["blocks"])
        wpe_t = jnp.take(params["wpe"], pos, axis=0) if vec else \
            jax.lax.dynamic_index_in_dim(params["wpe"], pos, 0,
                                         keepdims=False)[None]
        x = jnp.take(params["wte"], token, axis=0) + wpe_t
        lengths = (pos + 1).astype(jnp.int32) if vec else \
            jnp.full((B,), pos + 1, jnp.int32)

        def body(carry, inp):
            x = carry
            lp, k_l, v_l = inp
            y = ln(x, lp["ln1_w"], lp["ln1_b"])
            qkv = y @ lp["qkv_w"] + lp["qkv_b"]
            qkv = qkv.reshape(B, H, 3 * D)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            if vec:
                k_l = k_l.at[jnp.arange(B), pos].set(k)
                v_l = v_l.at[jnp.arange(B), pos].set(v)
            else:
                k_l = jax.lax.dynamic_update_slice(
                    k_l, k[:, None], (0, pos, 0, 0))
                v_l = jax.lax.dynamic_update_slice(
                    v_l, v[:, None], (0, pos, 0, 0))
            attn = decode_attention(q, k_l, v_l, lengths,
                                    use_pallas=use_pallas)
            x = x + attn.reshape(B, -1) @ lp["proj_w"] + lp["proj_b"]
            x = x + ffn(lp, ln(x, lp["ln2_w"], lp["ln2_b"]))
            return x, (k_l, v_l)

        x, (ks, vs) = jax.lax.scan(body, x, (blocks, cache["k"], cache["v"]))
        return {"k": ks, "v": vs}, final_logits(params, x)

    def chunk_step(params, cache, toks, pos):
        """Speculative verify: K1 consecutive tokens in one cached pass
        (see build_llama_decoder.chunk_step; GPT uses learned position
        embeddings instead of rope).  ``pos`` scalar or [B] vector."""
        B, K1 = toks.shape
        vec = jnp.ndim(pos) == 1
        blocks = _collapse_blocks(params["blocks"])
        if vec:
            pos_ids = pos[:, None] + jnp.arange(K1)[None, :]   # [B, K1]
            x = jnp.take(params["wte"], toks, axis=0) \
                + jnp.take(params["wpe"], pos_ids, axis=0)
            mask = jnp.arange(max_len)[None, None, None, :] \
                <= pos_ids[:, None, :, None]               # [B,1,K1,T]
        else:
            pos_ids = pos + jnp.arange(K1)
            x = jnp.take(params["wte"], toks, axis=0) \
                + jnp.take(params["wpe"], pos_ids, axis=0)[None]
            jpos = jnp.arange(max_len)[None, None, None, :]
            mask = jpos <= pos_ids[None, None, :, None]
        scale = 1.0 / math.sqrt(D)

        def body(carry, inp):
            x = carry
            lp, k_l, v_l = inp
            y = ln(x, lp["ln1_w"], lp["ln1_b"])
            qkv = y @ lp["qkv_w"] + lp["qkv_b"]
            qkv = qkv.reshape(B, K1, H, 3 * D)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            if vec:
                k_l = k_l.at[jnp.arange(B)[:, None], pos_ids].set(k)
                v_l = v_l.at[jnp.arange(B)[:, None], pos_ids].set(v)
            else:
                k_l = jax.lax.dynamic_update_slice(k_l, k, (0, pos, 0, 0))
                v_l = jax.lax.dynamic_update_slice(v_l, v, (0, pos, 0, 0))
            attn = _dense_masked_attention(
                q, k_l, v_l, mask, scale).reshape(B, K1, -1)
            x = x + attn @ lp["proj_w"] + lp["proj_b"]
            x = x + ffn(lp, ln(x, lp["ln2_w"], lp["ln2_b"]))
            return x, (k_l, v_l)

        x, (ks, vs) = jax.lax.scan(body, x, (blocks, cache["k"],
                                             cache["v"]))
        xf = ln(x, params["lnf_w"], params["lnf_b"])
        logits = jnp.einsum("bkh,vh->bkv", xf, params["wte"],
                            preferred_element_type=jnp.float32)
        return {"k": ks, "v": vs}, logits

    if with_chunk:
        return prefill, step, chunk_step
    return prefill, step


def _rope_rows(q, k, cos_bt, sin_bt):
    """Per-row RoPE: q,k [B, S, h, d]; cos/sin [B, S, d] gathered at each
    row's own positions (batched speculative decoding, where rows sit at
    divergent cache positions)."""
    from .llama import _rotate_half
    c = cos_bt[:, :, None, :]
    s = sin_bt[:, :, None, :]
    return q * c + _rotate_half(q) * s, k * c + _rotate_half(k) * s


def _dense_masked_attention(q, k, v, mask, scale):
    """q [B,Q,H,D] vs k/v [B,T,Hkv,D] (GQA-repeat inside) under a
    broadcastable boolean mask [.,.,Q,T]; fp32 softmax.  Shared by the
    llama prefill and the speculative chunk verify so masking/precision
    semantics cannot drift between them."""
    rep = q.shape[2] // k.shape[2]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    logits = jnp.where(mask, logits.astype(jnp.float32), -1e30)
    p = jax.nn.softmax(logits, -1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# ---------------------------------------------------------------------------
# Llama decoder
# ---------------------------------------------------------------------------
def quantize_llama_params(params, algo: str = "weight_only_int8"):
    """Quantize every block matmul weight of a Llama param pytree for
    weight-only decode (BASELINE config 5's fused weight-only path).
    Returns a params pytree whose block leaves ``<name>`` are replaced by
    ``<name>__q`` (int8/packed-int4) + ``<name>__s`` (scales)."""
    from ..nn.quant import weight_quantize
    blocks = params["blocks"]
    out = {k: v for k, v in params.items() if k != "blocks"}
    qblocks = {}
    for name, v in blocks.items():
        if name.endswith("_w") and v.ndim >= 3 and not name.startswith("ln"):
            flat = v.reshape((-1,) + v.shape[2:])   # [L, K, N]
            qs = [weight_quantize(flat[i], algo) for i in range(
                flat.shape[0])]
            qblocks[name + "__q"] = jnp.stack(
                [jnp.asarray(q[0]._value if hasattr(q[0], "_value")
                             else q[0]) for q in qs])[None]
            qblocks[name + "__s"] = jnp.stack(
                [jnp.asarray(q[1]._value if hasattr(q[1], "_value")
                             else q[1]) for q in qs])[None]
        else:
            qblocks[name] = v
    out["blocks"] = qblocks
    return out


def build_llama_decoder(cfg, max_len: int,
                        use_pallas: Optional[bool] = None,
                        quant: Optional[str] = None,
                        with_chunk: bool = False):
    """Same contract as :func:`build_gpt_decoder` for the Llama family
    (RMSNorm, RoPE, GQA cache [L,B,T,Hkv,D], SwiGLU, untied head).

    ``quant``: "weight_only_int8" / "weight_only_int4" — params must come
    from :func:`quantize_llama_params`; block matmuls then run through
    nn.quant.weight_only_linear (Pallas streaming-dequant on TPU)."""
    from .llama import _rope_cos_sin, apply_rope
    H, Hkv, D, L = (cfg.num_heads, cfg.kv_heads, cfg.head_dim,
                    cfg.num_layers)
    eps = cfg.rms_norm_eps
    moe = getattr(cfg, "moe_num_experts", 0)
    if moe and quant is not None:
        raise NotImplementedError(
            "weight-only quantization is not supported with "
            "moe_num_experts > 0 (expert banks are not wired into "
            "quantize_llama_params)")
    if moe and getattr(cfg, "moe_router", "topk") != "topk":
        raise NotImplementedError(
            "decode serves token-choice routing only; a model trained "
            "with moe_router='expert_choice' would be silently served a "
            "different forward (expert choice competes across the batch, "
            "which is non-causal at decode)")
    rs = getattr(cfg, "rope_scaling", None)
    if rs and rs.get("rope_type", rs.get("type")) == "dynamic":
        raise NotImplementedError(
            "dynamic-NTK rope depends on the current sequence length; "
            "the decoder bakes one table at max_len, which would "
            "mis-scale shorter prefixes — use 'linear' or 'llama3'")

    def ffn(lp, y):
        """Post-ln2 FFN: dense SwiGLU or Mixtral MoE.  The MoE branch is
        the DROPLESS grouped-GEMM serving path (sorted assignments +
        lax.ragged_dot, Mosaic grouped-matmul on TPU): top_k*T slot cost
        instead of E*C dispatch buffers, and no token is ever dropped
        (capacity truncation is a training regularizer, not a decode
        behavior)."""
        if moe:
            from ..parallel.moe import moe_swiglu_ffn_grouped
            out = moe_swiglu_ffn_grouped(
                y, lp["router_w"], lp["e_gate"], lp["e_up"], lp["e_down"],
                top_k=cfg.moe_top_k)
            if getattr(cfg, "moe_num_shared_experts", 0):
                out = out + (jax.nn.silu(y @ lp["s_gate"])
                             * (y @ lp["s_up"])) @ lp["s_down"]
            return out
        return mm(lp, "down_w", jax.nn.silu(mm(lp, "gate_w", y))
                  * mm(lp, "up_w", y))

    if quant is None:
        # a serving engine's tree stores q/k/v [N, K]: one definition
        from ..ops.decode_block import matmul_stored as mm
    else:
        wdt = "int4" if quant == "weight_only_int4" else "int8"

        def mm(lp, name, y):
            from ..nn.quant import weight_only_linear
            out = weight_only_linear(y, lp[name + "__q"],
                                     weight_scale=lp[name + "__s"],
                                     weight_dtype=wdt)
            return out._value if hasattr(out, "_value") else out

    def rms(x, w):
        ms = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        return (x * jax.lax.rsqrt(ms + eps).astype(x.dtype)) * w

    def final_logits(params, x):
        """Final RMSNorm + untied head for [B, h] or [B, K, h] — the
        chunk verify and single-token paths share ONE head so logits
        semantics cannot drift between them."""
        x = rms(x, params["lnf_w"])
        return jnp.einsum("...h,hv->...v", x, params["head"],
                          preferred_element_type=jnp.float32)

    cos_full, sin_full = _rope_cos_sin(max_len, D, cfg.rope_theta,
                                       jnp.dtype(cfg.dtype),
                                       getattr(cfg, "rope_scaling", None))

    def prefill(params, ids):
        B, T0 = ids.shape
        blocks = _collapse_blocks(params["blocks"])
        x = jnp.take(params["wte"], ids, axis=0)
        cos, sin = cos_full[:T0], sin_full[:T0]

        def body(x, lp):
            y = rms(x, lp["ln1_w"])
            q = mm(lp, "q_w", y).reshape(B, T0, H, D)
            k = mm(lp, "k_w", y).reshape(B, T0, Hkv, D)
            v = mm(lp, "v_w", y).reshape(B, T0, Hkv, D)
            q, k = apply_rope(q, k, cos, sin)
            mask = jnp.tril(jnp.ones((T0, T0), bool))
            attn = _dense_masked_attention(
                q, k, v, mask, 1.0 / math.sqrt(D)).reshape(B, T0, -1)
            x = x + mm(lp, "o_w", attn)
            x = x + ffn(lp, rms(x, lp["ln2_w"]))
            return x, (k, v)

        x, (ks, vs) = jax.lax.scan(body, x, blocks)
        pad = max_len - T0
        cache = {
            "k": jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
            "v": jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
        }
        return cache, final_logits(params, x[:, -1])

    def step(params, cache, token, pos):
        """``pos``: scalar (aligned rows) or [B] vector (per-row
        positions, the batched-speculative case)."""
        B = token.shape[0]
        vec = jnp.ndim(pos) == 1
        blocks = _collapse_blocks(params["blocks"])
        x = jnp.take(params["wte"], token, axis=0)
        if vec:
            cos_t = jnp.take(cos_full, pos, axis=0)[:, None]  # [B, 1, d]
            sin_t = jnp.take(sin_full, pos, axis=0)[:, None]
            lengths = (pos + 1).astype(jnp.int32)
        else:
            cos_t = jax.lax.dynamic_slice_in_dim(cos_full, pos, 1, 0)
            sin_t = jax.lax.dynamic_slice_in_dim(sin_full, pos, 1, 0)
            lengths = jnp.full((B,), pos + 1, jnp.int32)

        def body(carry, inp):
            x = carry
            lp, k_l, v_l = inp
            y = rms(x, lp["ln1_w"])
            q = mm(lp, "q_w", y).reshape(B, 1, H, D)
            k = mm(lp, "k_w", y).reshape(B, 1, Hkv, D)
            v = mm(lp, "v_w", y).reshape(B, 1, Hkv, D)
            if vec:
                q, k = _rope_rows(q, k, cos_t, sin_t)
                k_l = k_l.at[jnp.arange(B), pos].set(k[:, 0])
                v_l = v_l.at[jnp.arange(B), pos].set(v[:, 0])
            else:
                q, k = apply_rope(q, k, cos_t, sin_t)
                k_l = jax.lax.dynamic_update_slice(k_l, k, (0, pos, 0, 0))
                v_l = jax.lax.dynamic_update_slice(v_l, v, (0, pos, 0, 0))
            attn = decode_attention(q[:, 0], k_l, v_l, lengths,
                                    use_pallas=use_pallas)
            x = x + mm(lp, "o_w", attn.reshape(B, -1))
            x = x + ffn(lp, rms(x, lp["ln2_w"]))
            return x, (k_l, v_l)

        xin = x  # [B, h]
        x, (ks, vs) = jax.lax.scan(body, xin, (blocks, cache["k"],
                                               cache["v"]))
        return {"k": ks, "v": vs}, final_logits(params, x)

    def chunk_step(params, cache, toks, pos):
        """Verify step for speculative decoding: run ``K1`` consecutive
        tokens (``toks`` [B, K1] at positions pos..pos+K1-1) through the
        cached forward in ONE pass, returning per-position logits
        [B, K1, V].  Attention is dense q-vs-cache with a per-query
        length mask (query i sees cache[j] iff j <= pos+i), so the MXU
        sees a K1-row matmul instead of K1 vector passes — the
        arithmetic-intensity win speculative decoding banks on.
        ``pos`` scalar or [B] vector (per-row positions)."""
        B, K1 = toks.shape
        vec = jnp.ndim(pos) == 1
        blocks = _collapse_blocks(params["blocks"])
        x = jnp.take(params["wte"], toks, axis=0)          # [B, K1, h]
        if vec:
            pos_ids = pos[:, None] + jnp.arange(K1)[None, :]   # [B, K1]
            cos = jnp.take(cos_full, pos_ids, axis=0)      # [B, K1, d]
            sin = jnp.take(sin_full, pos_ids, axis=0)
            mask = jnp.arange(max_len)[None, None, None, :] \
                <= pos_ids[:, None, :, None]               # [B,1,K1,T]
        else:
            cos = jax.lax.dynamic_slice_in_dim(cos_full, pos, K1, 0)
            sin = jax.lax.dynamic_slice_in_dim(sin_full, pos, K1, 0)
            jpos = jnp.arange(max_len)[None, None, None, :]
            qpos = (pos + jnp.arange(K1))[None, None, :, None]
            mask = jpos <= qpos                            # [1,1,K1,T]
        scale = 1.0 / math.sqrt(D)

        def body(carry, inp):
            x = carry
            lp, k_l, v_l = inp
            y = rms(x, lp["ln1_w"])
            q = mm(lp, "q_w", y).reshape(B, K1, H, D)
            k = mm(lp, "k_w", y).reshape(B, K1, Hkv, D)
            v = mm(lp, "v_w", y).reshape(B, K1, Hkv, D)
            if vec:
                q, k = _rope_rows(q, k, cos, sin)
                k_l = k_l.at[jnp.arange(B)[:, None], pos_ids].set(k)
                v_l = v_l.at[jnp.arange(B)[:, None], pos_ids].set(v)
            else:
                q, k = apply_rope(q, k, cos, sin)
                k_l = jax.lax.dynamic_update_slice(k_l, k, (0, pos, 0, 0))
                v_l = jax.lax.dynamic_update_slice(v_l, v, (0, pos, 0, 0))
            attn = _dense_masked_attention(
                q, k_l, v_l, mask, scale).reshape(B, K1, -1)
            x = x + mm(lp, "o_w", attn)
            x = x + ffn(lp, rms(x, lp["ln2_w"]))
            return x, (k_l, v_l)

        x, (ks, vs) = jax.lax.scan(body, x, (blocks, cache["k"],
                                             cache["v"]))
        return {"k": ks, "v": vs}, final_logits(params, x)

    if with_chunk:
        return prefill, step, chunk_step
    return prefill, step


# ---------------------------------------------------------------------------
# generate loop (shared)
# ---------------------------------------------------------------------------
# bounded compiled-rollout cache (serving loops vary B/T0 freely; each
# entry pins a jitted closure + XLA executables)
from ..utils.lru import LRUCache as _LRUCache

_RUN_CACHE = _LRUCache(16)


def _generate(decoder_builder, cfg, params, input_ids, max_new_tokens,
              *, temperature=0.0, top_k=None, top_p=None, seed=0,
              eos_token_id=None, use_pallas=None):
    ids = jnp.asarray(input_ids)
    B, T0 = ids.shape
    if max_new_tokens <= 0:
        return ids
    max_len = T0 + max_new_tokens
    max_pos = getattr(cfg, "max_position_embeddings", None)
    if max_pos is not None and max_len > max_pos:
        raise ValueError(
            f"prompt ({T0}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_position_embeddings ({max_pos}); later positions would "
            f"silently clamp to the last learned position embedding")
    # the compiled rollout is cached per (model family, config, shapes,
    # sampling knobs) — repeated generate() calls must not recompile the
    # whole prefill + decode scan
    cache_key = (decoder_builder, repr(cfg), B, T0, max_new_tokens,
                 temperature, top_k, top_p, eos_token_id, use_pallas)
    cached = _RUN_CACHE.get(cache_key)
    if cached is not None:
        new = cached(params, ids, jax.random.key(seed))
        return jnp.concatenate([ids.astype(new.dtype), new], axis=1)

    prefill, step = decoder_builder(cfg, max_len, use_pallas=use_pallas)

    @jax.jit
    def run(params, ids, key):
        key0, key_loop = jax.random.split(key)
        cache, logits = prefill(params, ids)
        tok0 = sample_logits(logits, key0, temperature=temperature,
                             top_k=top_k, top_p=top_p)

        def scan_step(carry, i):
            cache, tok, key, done = carry
            key, sub = jax.random.split(key)
            cache, logits = step(params, cache, tok, T0 + i)
            nxt = sample_logits(logits, sub, temperature=temperature,
                                top_k=top_k, top_p=top_p)
            if eos_token_id is not None:
                done_now = done | (tok == eos_token_id)
                nxt = jnp.where(done_now, eos_token_id, nxt)
            else:
                done_now = done
            return (cache, nxt, key, done_now), tok

        done0 = jnp.zeros((B,), bool)
        (_, last, _, _), toks = jax.lax.scan(
            scan_step, (cache, tok0, key_loop, done0),
            jnp.arange(max_new_tokens - 1))
        toks = jnp.moveaxis(toks, 0, 1)          # [B, max_new-1]
        return jnp.concatenate([toks, last[:, None]], axis=1)

    _RUN_CACHE.put(cache_key, run)
    new = run(params, ids, jax.random.key(seed))
    return jnp.concatenate([ids.astype(new.dtype), new], axis=1)


def llama_speculative_generate(params, cfg, draft_params, draft_cfg,
                               input_ids, max_new_tokens: int, *,
                               num_draft: int = 4,
                               use_pallas: Optional[bool] = None):
    return _speculative_generate(
        build_llama_decoder, params, cfg, draft_params, draft_cfg,
        input_ids, max_new_tokens, num_draft=num_draft,
        use_pallas=use_pallas)


def gpt_speculative_generate(params, cfg, draft_params, draft_cfg,
                             input_ids, max_new_tokens: int, *,
                             num_draft: int = 4,
                             use_pallas: Optional[bool] = None):
    """GPT-family speculative decoding — same greedy-exact contract as
    :func:`llama_speculative_generate`."""
    return _speculative_generate(
        build_gpt_decoder, params, cfg, draft_params, draft_cfg,
        input_ids, max_new_tokens, num_draft=num_draft,
        use_pallas=use_pallas)


def _speculative_generate(builder, params, cfg, draft_params, draft_cfg,
                          input_ids, max_new_tokens: int, *,
                          num_draft: int = 4,
                          use_pallas: Optional[bool] = None):
    """Greedy speculative decoding (Leviathan et al. 2023, greedy case):
    a small DRAFT model proposes ``num_draft`` tokens per round; the
    target model scores all of them in ONE chunk_step (K+1-row matmuls
    instead of K+1 vector decodes) and accepts the longest matching
    prefix plus its own correction token.

    Greedy acceptance means every emitted token is an argmax of the
    TARGET's chunk logits, so the output equals a greedy rollout of the
    target evaluated with the chunked (dense-masked) attention — the
    draft changes speed, never content.  Agreement with llama_generate's
    single-token decode path additionally requires the two attention
    evaluations to agree at argmax, which holds except on floating-point
    near-ties (real models; random-init weights sit near ties often).

    Batched: per-row acceptance lengths diverge, so every draft/verify
    step runs at per-row cache positions ([B] pos vectors through the
    builders' vector-pos path); rows that finish early keep riding the
    batch with frozen positions until the slowest row completes.
    Returns ([B, T0 + max_new_tokens] ids, stats dict).
    """
    ids = jnp.asarray(input_ids)
    B, T0 = ids.shape
    if max_new_tokens <= 0:
        return ids, {"rounds": 0, "accepted_drafts": 0,
                     "proposed": 0, "accept_rate": 0.0}
    K = int(num_draft)
    max_len = T0 + max_new_tokens + K + 1   # slack for overshoot writes
    for c in (cfg, draft_cfg):
        mp = getattr(c, "max_position_embeddings", None)
        if mp is not None and max_len > mp:
            raise ValueError(
                f"speculative window needs {max_len} positions, config "
                f"allows {mp} (prompt {T0} + new {max_new_tokens} + "
                f"draft slack {K + 1})")

    # reuse jitted closures across calls (same keyed-cache policy as
    # _generate's _RUN_CACHE — a serving loop must not recompile four
    # decoder programs per request)
    ck = ("spec", builder, repr(cfg), repr(draft_cfg), max_len,
          use_pallas)
    cached = _RUN_CACHE.get(ck)
    if cached is None:
        prefill_t, _, chunk_t = builder(
            cfg, max_len, use_pallas=use_pallas, with_chunk=True)
        prefill_d, step_d = builder(draft_cfg, max_len,
                                    use_pallas=use_pallas)
        cached = (jax.jit(prefill_t), jax.jit(chunk_t),
                  jax.jit(prefill_d), jax.jit(step_d))
        _RUN_CACHE.put(ck, cached)
    jprefill_t, jchunk, jprefill_d, jstep_d = cached

    t_cache, t_logits = jprefill_t(params, ids)
    d_cache, _ = jprefill_d(draft_params, ids)
    last = jnp.argmax(t_logits, -1).astype(jnp.int32)     # [B]

    outs = [[int(t)] for t in np.asarray(last)]           # per-row tokens
    pos = np.full((B,), T0, np.int64)   # next unwritten cache position
    rounds = accepted = proposed = 0
    while any(len(o) < max_new_tokens for o in outs):
        pos_v = jnp.asarray(pos, jnp.int32)
        # draft proposes K tokens per row (positions pos_b .. pos_b+K-1)
        props = []
        dtok = last
        for i in range(K):
            d_cache, dl = jstep_d(draft_params, d_cache, dtok,
                                  pos_v + jnp.int32(i))
            dtok = jnp.argmax(dl, -1).astype(jnp.int32)
            props.append(dtok)
        # target verifies [last, d1..dK] in one pass at per-row positions
        # pos_b..pos_b+K; argmax[i] is the target's token AFTER chunk[i]
        chunk = jnp.stack([last] + props, axis=1)          # [B, K+1]
        t_cache, cl = jchunk(params, t_cache, chunk, pos_v)
        tgt = np.asarray(jnp.argmax(cl, -1))               # [B, K+1]
        props_np = np.asarray(chunk)[:, 1:]            # one host sync
        last_np = np.array(last)     # writable copy
        rounds += 1
        any_full = False
        for b in range(B):
            if len(outs[b]) >= max_new_tokens:
                continue       # finished row rides along, pos frozen
            n = 0
            while n < K and props_np[b, n] == tgt[b, n] \
                    and len(outs[b]) + n + 1 < max_new_tokens:
                n += 1
            if n == K:
                any_full = True
            new_toks = props_np[b, :n].tolist() + [int(tgt[b, n])]
            outs[b].extend(new_toks)
            accepted += n
            proposed += K
            pos[b] += n + 1
            last_np[b] = new_toks[-1]
        if any_full:
            # full acceptance on some row: d_K was proposed but never
            # PROCESSED by the draft (its inputs were last, d_1..d_{K-1});
            # feed it at old_pos+K or a permanent zero-KV hole forms
            # there.  Batched over every row is safe: rows with n < K
            # write a slot >= their new pos that the next round's
            # proposals overwrite before any read.
            d_cache, _ = jstep_d(draft_params, d_cache,
                                 jnp.asarray(props_np[:, K - 1], jnp.int32),
                                 pos_v + jnp.int32(K))
        last = jnp.asarray(last_np, jnp.int32)
        # draft cache now covers every position < pos; slots >= pos hold
        # rejected-token KV, masked until the next proposals overwrite

    toks = jnp.asarray([o[:max_new_tokens] for o in outs], ids.dtype)
    stats = {"rounds": rounds, "accepted_drafts": accepted,
             "proposed": proposed,
             "accept_rate": round(accepted / max(proposed, 1), 4)}
    return jnp.concatenate([ids, toks], axis=1), stats


def gpt_generate(params, cfg, input_ids, max_new_tokens: int, **kw):
    """Greedy/sampled generation for the GPT param pytree.  Returns
    [B, T0 + max_new_tokens] ids (prompt included)."""
    return _generate(build_gpt_decoder, cfg, params, input_ids,
                     max_new_tokens, **kw)


_QUANT_BUILDERS: Dict[str, Callable] = {}


def llama_generate(params, cfg, input_ids, max_new_tokens: int,
                   quant: Optional[str] = None, **kw):
    """``quant``: pass "weight_only_int8"/"weight_only_int4" with params
    from :func:`quantize_llama_params` (BASELINE config 5 weight-only
    decode)."""
    if quant is None:
        builder = build_llama_decoder
    else:
        # stable builder identity per algo so the compiled-rollout cache
        # in _generate keeps hitting
        builder = _QUANT_BUILDERS.setdefault(
            quant, functools.partial(build_llama_decoder, quant=quant))
    return _generate(builder, cfg, params, input_ids,
                     max_new_tokens, **kw)
