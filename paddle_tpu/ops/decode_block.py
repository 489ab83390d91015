"""One transformer layer of the serving path: the decode step's and the
chunk fill's layer bodies, and the closures they are made of.

Every compiled serve program reads its layer from this module: the
decode step and the speculative verify scan (:func:`decode_block`, one
token per sequence against the paged pool), the bucketed and suffix
chunk fills (:func:`prefill_block`, ``Ts`` prompt tokens of one
sequence), the spec-decode draft (:func:`make_norm_ffn`), and the
hybrid's attention layers (:func:`decode_attention_xla` /
:func:`prefill_attention_xla`, whose FFN half is the model's own).  So
there is ONE definition of the norm (:func:`make_norm`), the matmul
over a stored weight (:func:`make_mm`: full width in either layout, or
weight-only quantized), the FFN (:func:`make_ffn`) and the two
attention halves, and the programs cannot drift apart.  Each layer is a
chain of XLA ops (norm, projections, RoPE, paged append or positional
scatter, attention, out-projection, FFN) that the compiler fuses inside
the engine's layer scan.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .paged_kv import (QuantizedKVPool, dequantize_kv, is_quantized_pool,
                       paged_append, paged_decode_attention, quantize_kv,
                       validate_paged_decode_geometry)

__all__ = ["DecodeBlockSpec", "decode_attention_xla", "decode_block",
           "decode_block_spec", "make_norm", "make_ffn", "make_mm",
           "matmul_stored", "serving_layout", "make_norm_ffn",
           "prefill_attention_xla", "prefill_block", "rotate_half"]


@dataclasses.dataclass(frozen=True)
class DecodeBlockSpec:
    """Static shape/variant description of one transformer layer's
    decode step.  Covers the Llama family (RMSNorm, split q/k/v, RoPE,
    SwiGLU) and the GPT family (LayerNorm with bias, fused qkv, learned
    positions — no RoPE — and a GELU MLP)."""
    hidden: int
    num_heads: int
    kv_heads: int
    head_dim: int
    block_size: int                   # KV page size (pool geometry)
    norm: str = "rms"                 # "rms" | "ln"
    activation: str = "swiglu"        # "swiglu" | "gelu"
    eps: float = 1e-5
    rope: bool = True
    fused_qkv: bool = False           # GPT layout: qkv_w/qkv_b
    bias: bool = False                # GPT layout: proj/fc biases
    # weight-only quantization: matmul weights live in ``lp`` as
    # ``<name>__q`` int8 codes (int4: halves-packed nibbles) plus
    # ``<name>__s`` fp32 scales — the nn.quant/quantization.serve
    # export layout.  Norm gains and biases stay full width.
    weight_dtype: Optional[str] = None   # None | "int8" | "int4"
    group_size: int = -1                 # -1 | 64 | 128 (scale grouping)
    # a stated softmax scale (None: 1/sqrt(head_dim)) and a multiplier
    # on what the attention adds to the residual stream — the granite
    # family's ``attention_multiplier`` / ``residual_multiplier``
    attn_scale: Optional[float] = None
    residual_scale: float = 1.0

    def __post_init__(self):
        if self.norm not in ("rms", "ln"):
            raise ValueError(f"norm must be 'rms' or 'ln', got {self.norm!r}")
        if self.activation not in ("swiglu", "gelu"):
            raise ValueError("activation must be 'swiglu' or 'gelu', got "
                             f"{self.activation!r}")
        if self.fused_qkv and self.kv_heads != self.num_heads:
            raise ValueError(
                "fused_qkv implies MHA (one [H, 3*H] projection); got "
                f"num_heads={self.num_heads}, kv_heads={self.kv_heads}")
        if self.weight_dtype not in (None, "int8", "int4"):
            raise ValueError("weight_dtype must be None, 'int8' or "
                             f"'int4', got {self.weight_dtype!r}")
        if self.group_size not in (-1, 64, 128):
            raise ValueError(f"group_size must be -1/64/128, got "
                             f"{self.group_size}")
        if self.weight_dtype is None and self.group_size != -1:
            raise ValueError("group_size requires weight_dtype")


def decode_block_spec(cfg, block_size: int,
                      weight_dtype: Optional[str] = None,
                      group_size: int = -1) -> DecodeBlockSpec:
    """Spec for a model config: Llama-family configs (``rms_norm_eps``)
    map to rms/SwiGLU/RoPE, GPT-family (``layer_norm_eps``) to
    ln/GELU/fused-qkv.  ``weight_dtype``/``group_size`` select the
    weight-only quantized variant (params must carry ``__q``/``__s``
    leaves from ``quantization.serve.quantize_params_for_serving``)."""
    if hasattr(cfg, "rms_norm_eps"):
        return DecodeBlockSpec(
            hidden=cfg.hidden_size, num_heads=cfg.num_heads,
            kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
            block_size=block_size, norm="rms", activation="swiglu",
            eps=cfg.rms_norm_eps, rope=True,
            weight_dtype=weight_dtype, group_size=group_size)
    return DecodeBlockSpec(
        hidden=cfg.hidden_size, num_heads=cfg.num_heads,
        kv_heads=cfg.num_heads, head_dim=cfg.head_dim,
        block_size=block_size, norm="ln", activation="gelu",
        eps=cfg.layer_norm_eps, rope=False, fused_qkv=True, bias=True,
        weight_dtype=weight_dtype, group_size=group_size)


def rotate_half(x):
    """RoPE rotate-half convention ([-x2, x1]); identical math to the
    model-side helper so the serving and training paths cannot drift."""
    d2 = x.shape[-1] // 2
    return jnp.concatenate([-x[..., d2:], x[..., :d2]], axis=-1)


# ---------------------------------------------------------------------------
# shared closures: ONE source for the norm and FFN numerics of every
# compiled serve program (decode step, chunk fill, spec-decode draft)
# ---------------------------------------------------------------------------
def make_norm(spec: DecodeBlockSpec) -> Callable:
    """``norm(x, w, b=None)`` — fp32 statistics, scale applied in the
    input dtype (the convention every serving path has always used)."""
    eps = spec.eps
    if spec.norm == "rms":
        def norm(x, w, b=None):
            ms = jnp.mean(jnp.square(x.astype(jnp.float32)), -1,
                          keepdims=True)
            return (x * jax.lax.rsqrt(ms + eps).astype(x.dtype)) * w
        return norm

    def norm(x, w, b=None):
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, -1, keepdims=True)
        var = jnp.var(x32, -1, keepdims=True)
        out = (x32 - mu) * jax.lax.rsqrt(var + eps)
        return out.astype(x.dtype) * w + b
    return norm


def serving_layout(blocks):
    """The stacked blocks of a Llama-family tree as a serving engine
    holds them: the full-width ``q_w`` / ``k_w`` / ``v_w``
    ``[..., K, N]`` become ``q_wt`` / ``k_wt`` / ``v_wt``
    ``[..., N, K]``, every other leaf as it is.  For these three
    matmuls the TPU compiler assigns the weight the layout ``[N, K]``;
    given the tree's ``[K, N]`` it transposes the whole stacked leaf at
    the top of EVERY call of the decode step and of a chunk fill (1.6 GB
    of traffic a call at 16 layers of Mistral-7B's widths;
    ``tests/test_chip_compile.py`` holds the programs to it).  Laid out
    once, where the engine is built, they are read in place
    (:func:`matmul_stored`).  The tree that training, checkpoints and
    the PTQ export share keeps its layout: a tree that is already laid
    out, or holds ``q_w__q`` codes and no ``q_w``, comes back as it
    is."""
    out = dict(blocks)
    names = [n for n in ("q_w", "k_w", "v_w") if n in out]
    if names:          # ONE program for the three (COMPILE_BUDGET.md)
        laid = _swap_last_two([out.pop(n) for n in names])
        out.update((n + "t", w) for n, w in zip(names, laid))
    return out


@jax.jit
def _swap_last_two(ws):
    return [jnp.swapaxes(w, -1, -2) for w in ws]


def matmul_stored(lp, name, y):
    """``y @ W`` for the full-width weight ``name`` of layer dict
    ``lp``, contracted in the layout it is stored in: ``[K, N]`` under
    its own name, or ``[N, K]`` under ``name + "t"``
    (:func:`serving_layout`).  The same products either way; the
    order they are summed in is the backend's (the CPU tier's differs
    between the two layouts at some shapes, by a last bit in float32:
    tests/test_decode_block.py)."""
    wt = lp.get(name + "t")
    if wt is None:
        return y @ lp[name]
    return jnp.einsum("...k,nk->...n", y, wt)


def make_mm(spec: DecodeBlockSpec) -> Callable:
    """``mm(lp, name, y)`` — the ONE matmul closure of every serve
    program.  Full width: :func:`matmul_stored`.  Weight-only
    quantized: dequantizing matmul over the export layout — per-channel
    scales post-multiply the int-code matmul (fp32 accumulation), grouped
    scales dequantize the weight tile first (a per-channel post-multiply
    cannot represent per-K-group scales) — the same split
    ``ops/pallas/quant_linear._block_scale`` makes."""
    if spec.weight_dtype is None:
        return matmul_stored
    wdt, gs = spec.weight_dtype, spec.group_size

    def mm(lp, name, y):
        from ..nn.quant import _group_expand, _unpack_int4
        wq, s = lp[name + "__q"], lp[name + "__s"]
        K = y.shape[-1]
        if wdt == "int4":
            wq = _unpack_int4(wq, K)
        y32 = y.astype(jnp.float32)
        s32 = s.astype(jnp.float32)
        if gs == -1:
            out = (y32 @ wq.astype(jnp.float32)) * s32
        else:
            out = y32 @ (wq.astype(jnp.float32)
                         * _group_expand(s32, K, gs))
        return out.astype(y.dtype)
    return mm


def make_ffn(spec: DecodeBlockSpec) -> Callable:
    """``ffn(lp, y)`` for the dense FFN variants (MoE callers pass
    their own closure through ``decode_block(ffn=...)``)."""
    mm = make_mm(spec)
    if spec.activation == "swiglu":
        def ffn(lp, y):
            return mm(lp, "down_w", jax.nn.silu(mm(lp, "gate_w", y))
                      * mm(lp, "up_w", y))
        return ffn

    def ffn(lp, y):
        return mm(lp, "fc2_w", jax.nn.gelu(
            mm(lp, "fc1_w", y) + lp["fc1_b"],
            approximate=True)) + lp["fc2_b"]
    return ffn


def make_norm_ffn(cfg, weight_dtype: Optional[str] = None,
                  group_size: int = -1):
    """The Llama-engine (norm, ffn) closure pair, housed with the layer
    bodies so the decode step, the chunk fill, and the spec-decode draft
    all read one definition.  For an MoE config the FFN is the grouped
    expert layer (plus the shared experts where the config has them)."""
    moe = getattr(cfg, "moe_num_experts", 0)
    if moe and weight_dtype is not None:
        raise NotImplementedError(
            "weight-only quantization is not supported with MoE FFNs "
            "(expert banks are not wired into the PTQ export)")
    spec = DecodeBlockSpec(
        hidden=cfg.hidden_size, num_heads=cfg.num_heads,
        kv_heads=cfg.kv_heads, head_dim=cfg.head_dim, block_size=1,
        norm="rms", activation="swiglu", eps=cfg.rms_norm_eps,
        weight_dtype=weight_dtype, group_size=group_size)
    norm = make_norm(spec)
    if not moe:
        return norm, make_ffn(spec)

    def ffn(lp, y):
        from ..parallel.moe import moe_swiglu_ffn_grouped
        out = moe_swiglu_ffn_grouped(
            y, lp["router_w"], lp["e_gate"], lp["e_up"],
            lp["e_down"], top_k=cfg.moe_top_k)
        if getattr(cfg, "moe_num_shared_experts", 0):
            out = out + (jax.nn.silu(y @ lp["s_gate"])
                         * (y @ lp["s_up"])) @ lp["s_down"]
        return out

    return norm, ffn


# ---------------------------------------------------------------------------
# the layer bodies
# ---------------------------------------------------------------------------
def _qkv(y, lp, spec: DecodeBlockSpec, leading, mm=None):
    """Project the normed stream into per-head q/k/v."""
    H, Hkv, D = spec.num_heads, spec.kv_heads, spec.head_dim
    mm = mm or make_mm(spec)
    if spec.fused_qkv:
        qkv = mm(lp, "qkv_w", y) + lp["qkv_b"]
        qkv = qkv.reshape(*leading, H, 3 * D)
        return jnp.split(qkv, 3, axis=-1)
    q = mm(lp, "q_w", y).reshape(*leading, H, D)
    k = mm(lp, "k_w", y).reshape(*leading, Hkv, D)
    v = mm(lp, "v_w", y).reshape(*leading, Hkv, D)
    return q, k, v


def _proj(attn, lp, spec: DecodeBlockSpec, mm):
    return mm(lp, "proj_w" if spec.fused_qkv else "o_w", attn)


def decode_block(x, lp, pool_k, pool_v, block_table, lengths, cos, sin, *,
                 spec: DecodeBlockSpec, ffn=None):
    """One transformer layer for one decode token per sequence.

    ``x``: [B, H] residual stream; ``lp``: the layer's weight dict
    (Llama ``q_w/k_w/v_w/o_w/ln*_w/gate_w/up_w/down_w`` or GPT
    ``qkv_w/qkv_b/proj_w/proj_b/ln*_{w,b}/fc*_{w,b}``); ``pool_k/v``:
    [NB, BS, Hkv, D] paged KV pools; ``block_table``: [B, MB];
    ``lengths``: [B] tokens already stored; ``cos``/``sin``: [B, D]
    RoPE rows at each sequence's absolute position (ignored when
    ``spec.rope`` is off); ``ffn``: the FFN closure of a model whose
    FFN is not the spec's dense one (MoE).  Returns
    ``(x_out [B, H], pool_k, pool_v)`` with the new token's KV appended.

    A row whose CURRENT page (``block_table[b, lengths[b] // BS]``) is
    unmapped (-1) attends the clamped page-0 pool rows: garbage the
    engine never exposes (pages are mapped for a request's full budget
    at admission; inactive slots' outputs are never read).
    """
    validate_paged_decode_geometry(
        (x.shape[0], spec.num_heads, spec.head_dim), pool_k, pool_v,
        block_table, lengths, op="decode_block")
    norm = make_norm(spec)
    ffn = ffn or make_ffn(spec)
    x, pool_k, pool_v = decode_attention_xla(
        x, lp, pool_k, pool_v, block_table, lengths, cos, sin, spec=spec)
    x = x + ffn(lp, norm(x, lp["ln2_w"], lp.get("ln2_b")))
    return x, pool_k, pool_v


def _residual(x, proj, lp, spec: DecodeBlockSpec):
    """The attention's term added to the stream (the multiplier only
    where a spec states one, so the other families' arithmetic is the
    same operation for operation)."""
    proj = proj + lp["proj_b"] if spec.bias else proj
    if spec.residual_scale != 1.0:
        proj = proj * jnp.asarray(spec.residual_scale, proj.dtype)
    return x + proj


def decode_attention_xla(x, lp, pool_k, pool_v, block_table, lengths, cos,
                         sin, *, spec: DecodeBlockSpec):
    """The attention half of :func:`decode_block`: norm, q/k/v,
    RoPE (``cos``/``sin`` may be None when ``spec.rope`` is off), paged
    append, paged decode attention, out-projection, residual.  A model
    whose FFN half is its own (an expert layer that reports counts)
    calls this and adds the rest itself."""
    B = x.shape[0]
    norm = make_norm(spec)
    mm = make_mm(spec)
    y = norm(x, lp["ln1_w"], lp.get("ln1_b"))
    q, k, v = _qkv(y, lp, spec, (B,), mm)
    if spec.rope:
        def rope1(t):                                     # [B, h?, D]
            return t * cos[:, None, :] + rotate_half(t) * sin[:, None, :]
        q, k = rope1(q), rope1(k)
    pool_k, pool_v = paged_append(pool_k, pool_v, k, v, block_table,
                                  lengths, spec.block_size)
    attn = paged_decode_attention(q, pool_k, pool_v, block_table,
                                  lengths + 1, scale=spec.attn_scale)
    proj = _proj(attn.reshape(B, -1), lp, spec, mm)
    return _residual(x, proj, lp, spec), pool_k, pool_v


def prefill_block(x, lp, pool_k, pool_v, blk, off, bt_row, mask, cos,
                  sin, *, spec: DecodeBlockSpec, ffn=None,
                  scale: Optional[float] = None):
    """One transformer layer for ``Ts`` prompt tokens of ONE sequence
    against the paged pool — the chunk-fill twin of :func:`decode_block`:
    the same chain, with a dense masked attention over the sequence's
    gathered pages and a positional scatter instead of the single-token
    append.  Shares every numeric closure with the decode step so the
    two compiled paths cannot drift.

    ``x``: [1, Ts, H] residual tile; ``blk``/``off``: [Ts] positional
    scatter targets; ``bt_row``: [MB] block-table row; ``mask``:
    [1, 1, Ts, MB*BS] causal mask; ``cos``/``sin``: [Ts, D] RoPE rows at
    the tile's absolute positions; ``ffn``: as for :func:`decode_block`.
    Returns ``(x_out [1, Ts, H], pool_k, pool_v)`` with the tile's KV
    written."""
    norm = make_norm(spec)
    ffn = ffn or make_ffn(spec)
    x, pool_k, pool_v = prefill_attention_xla(
        x, lp, pool_k, pool_v, blk, off, bt_row, mask, cos, sin,
        spec=spec, scale=scale)
    x = x + ffn(lp, norm(x, lp["ln2_w"], lp.get("ln2_b")))
    return x, pool_k, pool_v


def prefill_attention_xla(x, lp, pool_k, pool_v, blk, off, bt_row, mask,
                          cos, sin, *, spec: DecodeBlockSpec,
                          scale: Optional[float] = None):
    """The attention half of :func:`prefill_block` (the twin of
    :func:`decode_attention_xla`); ``scale`` defaults to the spec's
    ``attn_scale``, then to ``1/sqrt(head_dim)``."""
    from ..models.generation import _dense_masked_attention
    Ts = x.shape[1]
    H, Hkv, D = spec.num_heads, spec.kv_heads, spec.head_dim
    norm = make_norm(spec)
    mm = make_mm(spec)
    if scale is None:
        scale = spec.attn_scale
    s = scale if scale is not None else 1.0 / (D ** 0.5)
    y = norm(x, lp["ln1_w"], lp.get("ln1_b"))
    q, k, v = _qkv(y, lp, spec, (1, Ts), mm)
    if spec.rope:
        def rope1(t):                                    # [1, Ts, *, D]
            return t * cos[None, :, None, :] \
                + rotate_half(t) * sin[None, :, None, :]
        q, k = rope1(q), rope1(k)
    if is_quantized_pool(pool_k):
        kq, ks = quantize_kv(k[0])
        vq, vs = quantize_kv(v[0])
        pool_k = QuantizedKVPool(data=pool_k.data.at[blk, off].set(kq),
                                 scale=pool_k.scale.at[blk, off].set(ks))
        pool_v = QuantizedKVPool(data=pool_v.data.at[blk, off].set(vq),
                                 scale=pool_v.scale.at[blk, off].set(vs))
        bt0 = jnp.maximum(bt_row, 0)
        k_all = dequantize_kv(jnp.take(pool_k.data, bt0, axis=0),
                              jnp.take(pool_k.scale, bt0, axis=0),
                              dtype=k.dtype)
        v_all = dequantize_kv(jnp.take(pool_v.data, bt0, axis=0),
                              jnp.take(pool_v.scale, bt0, axis=0),
                              dtype=v.dtype)
    else:
        pool_k = pool_k.at[blk, off].set(k[0])
        pool_v = pool_v.at[blk, off].set(v[0])
        k_all = jnp.take(pool_k, jnp.maximum(bt_row, 0), axis=0)
        v_all = jnp.take(pool_v, jnp.maximum(bt_row, 0), axis=0)
    k_all = k_all.reshape(1, -1, Hkv, D)
    v_all = v_all.reshape(1, -1, Hkv, D)
    attn = _dense_masked_attention(q, k_all, v_all, mask,
                                   s).reshape(1, Ts, -1)
    proj = _proj(attn, lp, spec, mm)
    return _residual(x, proj, lp, spec), pool_k, pool_v
