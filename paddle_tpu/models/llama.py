"""Llama model family — BASELINE configs 5/6 (Llama-2 7B/13B, sharding
stage2/3 + fused kernels).

Reference parity: PaddleNLP-style Llama built from the reference's
fleet.meta_parallel mp layers (mp_layers.py:47/334/541) and the incubate
fused ops it consumes (fused_rms_norm — incubate/nn/functional/fused_rms_norm.py,
fused_rotary_position_embedding — fused_rope_kernel.cu:27, swiglu —
phi/kernels/swiglu_kernel.h).  TPU-first design:

* :class:`LlamaForCausalLM` — imperative ``Layer`` graph (eager / hapi /
  DistributedEngine).  GQA (``num_kv_heads``), RoPE, RMSNorm, SwiGLU;
  optionally tensor-parallel via Column/RowParallelLinear.
* :func:`build_llama_train_step` — compiled hybrid dp×mp×pp×sp train step
  over the stacked pure-fn block (lax.scan over layers, shard_map pipeline
  over the pp axis), mirroring models/gpt.py's flagship path.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..nn import functional as F
from ..nn import initializer as I
from ..nn.attr import ParamAttr
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..parallel.mp_layers import (ColumnParallelLinear, RowParallelLinear,
                                  VocabParallelEmbedding)
from ..parallel.topology import (DP_AXIS, MP_AXIS, PP_AXIS, SEP_AXIS,
                                 SHARDING_AXIS, get_topology)

__all__ = ["LlamaConfig", "LlamaAttention", "LlamaMLP", "LlamaMoEMLP",
           "LlamaBlock",
           "LlamaModel", "LlamaForCausalLM", "llama_tiny", "llama_7b",
           "llama_13b", "llama_70b", "build_llama_train_step"]


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: Optional[int] = None  # None => MHA; < num_heads => GQA
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    use_mp: bool = False
    dtype: str = "float32"
    # Mixtral-style sparse MoE FFN (0 = dense): SwiGLU experts sharded
    # over the dp axis in the compiled step (parallel/moe.py)
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # expert_choice capacity is a DIFFERENT quantity (average experts per
    # token, not GShard slack); moe_ec_capacity names it explicitly and
    # falls back to moe_capacity_factor when unset (ADVICE r4)
    moe_ec_capacity: "Optional[float]" = None
    moe_aux_coef: float = 1e-2
    moe_router: str = "topk"   # "topk" | "expert_choice" (see gpt.py)
    # RoPE scaling for long-context extension (HF-compatible dict):
    #   {"rope_type": "linear", "factor": f}
    #   {"rope_type": "dynamic", "factor": f,
    #    "original_max_position_embeddings": n}
    #   {"rope_type": "llama3", "factor": f, "low_freq_factor": lo,
    #    "high_freq_factor": hi, "original_max_position_embeddings": n}
    rope_scaling: Optional[dict] = None
    moe_dropless: bool = False  # sorted ragged_dot experts (no drops;
    # local banks only — mutually exclusive with dp-EP / mp expert TP)
    # DeepSeek-style always-on shared experts: every token also runs a
    # dense SwiGLU of width moe_num_shared_experts * intermediate_size
    # (sum over shared experts == one wide block-diagonal SwiGLU), added
    # to the routed output; rides the dense TP/SP machinery
    moe_num_shared_experts: int = 0
    # logits-free fused cross-entropy head (ops/fused_cross_entropy) —
    # see GPTConfig.fused_head
    fused_head: bool = True

    def __post_init__(self):
        if self.moe_num_shared_experts and not self.moe_num_experts:
            raise ValueError(
                "moe_num_shared_experts requires moe_num_experts > 0 "
                "(shared experts augment a routed MoE FFN; for a plain "
                "dense FFN just widen intermediate_size)")


    def moe_capacity(self) -> float:
        if self.moe_router == "expert_choice" and \
                self.moe_ec_capacity is not None:
            return self.moe_ec_capacity
        return self.moe_capacity_factor

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads


def llama_tiny(**kw) -> LlamaConfig:
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("intermediate_size", 128)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 4)
    kw.setdefault("num_kv_heads", 2)
    kw.setdefault("max_position_embeddings", 128)
    return LlamaConfig(**kw)


def llama_7b(**kw) -> LlamaConfig:
    kw.setdefault("hidden_size", 4096)
    kw.setdefault("intermediate_size", 11008)
    kw.setdefault("num_layers", 32)
    kw.setdefault("num_heads", 32)
    return LlamaConfig(**kw)


def llama_13b(**kw) -> LlamaConfig:
    kw.setdefault("hidden_size", 5120)
    kw.setdefault("intermediate_size", 13824)
    kw.setdefault("num_layers", 40)
    kw.setdefault("num_heads", 40)
    return LlamaConfig(**kw)


def llama_70b(**kw) -> LlamaConfig:
    kw.setdefault("hidden_size", 8192)
    kw.setdefault("intermediate_size", 28672)
    kw.setdefault("num_layers", 80)
    kw.setdefault("num_heads", 64)
    kw.setdefault("num_kv_heads", 8)
    return LlamaConfig(**kw)


def _rope_cos_sin(seq_len: int, head_dim: int, theta: float, dtype,
                  scaling: Optional[dict] = None):
    """RoPE tables, optionally rescaled for long-context extension with
    HuggingFace-compatible semantics (transformers modeling_rope_utils):
    linear position interpolation, dynamic NTK theta adjustment, and
    llama3 per-frequency wavelength interpolation."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    if scaling:
        kind = scaling.get("rope_type", scaling.get("type"))
        if kind is None:
            raise ValueError(
                "rope_scaling needs a 'rope_type' (or legacy 'type') key "
                "— refusing to guess (a silently-applied default would "
                "mis-scale every position)")
        factor = float(scaling.get("factor", 1.0))
        if kind == "linear":
            t = t / factor
        elif kind == "dynamic":
            orig = int(scaling.get("original_max_position_embeddings")
                       or 0)
            if not orig:
                raise ValueError(
                    "dynamic rope_scaling needs "
                    "'original_max_position_embeddings' (HF derives it "
                    "from config.max_position_embeddings; set it "
                    "explicitly here)")
            if seq_len > orig:
                base = theta * (factor * seq_len / orig
                                - (factor - 1)) ** (head_dim /
                                                    (head_dim - 2))
                inv = 1.0 / (base ** (jnp.arange(0, head_dim, 2,
                                                 jnp.float32) / head_dim))
        elif kind == "llama3":
            orig = int(scaling.get("original_max_position_embeddings")
                       or 0)
            if not orig:
                raise ValueError(
                    "llama3 rope_scaling needs "
                    "'original_max_position_embeddings'")
            lo = float(scaling["low_freq_factor"])
            hi = float(scaling["high_freq_factor"])
            low_wl = orig / lo
            high_wl = orig / hi
            wl = 2.0 * math.pi / inv
            smooth = (orig / wl - lo) / (hi - lo)
            interp = (1 - smooth) * inv / factor + smooth * inv
            inv = jnp.where(wl > low_wl, inv / factor,
                            jnp.where(wl < high_wl, inv, interp))
        else:
            raise ValueError(f"unknown rope_type {kind!r}")
    freqs = jnp.outer(t, inv)                      # [s, d/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [s, d]
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


def _rotate_half(x):
    d = x.shape[-1] // 2
    return jnp.concatenate([-x[..., d:], x[..., :d]], axis=-1)


def apply_rope(q, k, cos, sin):
    """q,k: [b, s, h, d]; cos/sin: [s, d]."""
    cos = cos[None, :, None, :]
    sin = sin[None, :, None, :]
    return (q * cos + _rotate_half(q) * sin,
            k * cos + _rotate_half(k) * sin)


from ..core.dispatch import primitive


@primitive("llama_attention")
def _rope_gqa_attention(q, k, v, cos, sin):
    """Taped eager op: RoPE + grouped-query causal attention, pure jnp.
    q: [b,s,hq,d]; k,v: [b,s,hkv,d]; cos/sin: [s,d]."""
    q, k = apply_rope(q, k, cos, sin)
    return _gqa_attention(q, k, v, causal=True)


def _gqa_attention(q, k, v, causal=True):
    """q: [b, s, hq, d]; k,v: [b, s, hkv, d] with hq % hkv == 0."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    if hkv != hq:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask, logits.astype(jnp.float32), -1e30)
    else:
        logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class LlamaAttention(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        kvh = cfg.kv_heads
        if cfg.use_mp:
            self.q_proj = ColumnParallelLinear(h, cfg.num_heads * d,
                                               has_bias=False,
                                               gather_output=False)
            self.k_proj = ColumnParallelLinear(h, kvh * d, has_bias=False,
                                               gather_output=False)
            self.v_proj = ColumnParallelLinear(h, kvh * d, has_bias=False,
                                               gather_output=False)
            self.o_proj = RowParallelLinear(cfg.num_heads * d, h,
                                            has_bias=False,
                                            input_is_parallel=True)
        else:
            self.q_proj = Linear(h, cfg.num_heads * d, bias_attr=False)
            self.k_proj = Linear(h, kvh * d, bias_attr=False)
            self.v_proj = Linear(h, kvh * d, bias_attr=False)
            self.o_proj = Linear(cfg.num_heads * d, h, bias_attr=False)

    def forward(self, x, cos, sin):
        from ..ops import api as _api
        cfg = self.cfg
        b, s = x.shape[0], x.shape[1]
        q = _api.reshape(self.q_proj(x), [b, s, cfg.num_heads, cfg.head_dim])
        k = _api.reshape(self.k_proj(x), [b, s, cfg.kv_heads, cfg.head_dim])
        v = _api.reshape(self.v_proj(x), [b, s, cfg.kv_heads, cfg.head_dim])
        out = _rope_gqa_attention(q, k, v, cos, sin)
        out = _api.reshape(out, [b, s, cfg.num_heads * cfg.head_dim])
        return self.o_proj(out)


class LlamaMLP(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        h, f = cfg.hidden_size, cfg.intermediate_size
        if cfg.use_mp:
            self.gate_proj = ColumnParallelLinear(h, f, has_bias=False,
                                                  gather_output=False)
            self.up_proj = ColumnParallelLinear(h, f, has_bias=False,
                                                gather_output=False)
            self.down_proj = RowParallelLinear(f, h, has_bias=False,
                                               input_is_parallel=True)
        else:
            self.gate_proj = Linear(h, f, bias_attr=False)
            self.up_proj = Linear(h, f, bias_attr=False)
            self.down_proj = Linear(f, h, bias_attr=False)

    def forward(self, x):
        from ..incubate.nn.functional import swiglu
        return self.down_proj(swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaMoEMLP(Layer):
    """Eager Mixtral-style sparse FFN: SwiGLU expert bank + top-k router
    (compiled-path parity lives in parallel/moe.py:moe_swiglu_ffn_ep;
    expert parallelism belongs to build_llama_train_step)."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        E, h, f = cfg.moe_num_experts, cfg.hidden_size, cfg.intermediate_size
        self.router_w = self.create_parameter((h, E))
        self.e_gate = self.create_parameter((E, h, f))
        self.e_up = self.create_parameter((E, h, f))
        self.e_down = self.create_parameter((E, f, h))
        if cfg.moe_num_shared_experts:
            fs = cfg.moe_num_shared_experts * f
            self.s_gate = self.create_parameter((h, fs))
            self.s_up = self.create_parameter((h, fs))
            self.s_down = self.create_parameter((fs, h))

    def forward(self, x):
        from ..core.dispatch import run_op
        from ..parallel.moe import moe_swiglu_ffn_ep
        cfg = self.cfg

        def impl(x_, rw, wg, wu, wd):
            # eager semantics: loss += moe_aux_coef * aux per layer
            # (aux does not apply under the expert_choice router)
            return moe_swiglu_ffn_ep(
                x_, rw, wg, wu, wd, top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity(),
                aux_coef=cfg.moe_aux_coef, router=cfg.moe_router,
                dropless=cfg.moe_dropless)

        out = run_op("llama_moe_mlp", impl,
                     (x, self.router_w, self.e_gate, self.e_up,
                      self.e_down), {})
        if cfg.moe_num_shared_experts:
            def shared(x_, sg, su, sd):
                return (jax.nn.silu(x_ @ sg) * (x_ @ su)) @ sd

            out = out + run_op("llama_moe_shared", shared,
                               (x, self.s_gate, self.s_up, self.s_down),
                               {})
        return out


class LlamaBlock(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.input_layernorm = RMSNorm(cfg.hidden_size,
                                       epsilon=cfg.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(cfg.hidden_size,
                                                epsilon=cfg.rms_norm_eps)
        self.self_attn = LlamaAttention(cfg)
        self.mlp = LlamaMoEMLP(cfg) if cfg.moe_num_experts \
            else LlamaMLP(cfg)

    def forward(self, x, cos, sin):
        x = x + self.self_attn(self.input_layernorm(x), cos, sin)
        return x + self.mlp(self.post_attention_layernorm(x))


class LlamaModel(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        attr = ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range))
        if cfg.use_mp:
            self.embed_tokens = VocabParallelEmbedding(
                cfg.vocab_size, cfg.hidden_size, weight_attr=attr)
        else:
            self.embed_tokens = Embedding(cfg.vocab_size, cfg.hidden_size,
                                          weight_attr=attr)
        self.layers = LayerList([LlamaBlock(cfg)
                                 for _ in range(cfg.num_layers)])
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids):
        cfg = self.cfg
        s = input_ids.shape[1]
        cos, sin = _rope_cos_sin(s, cfg.head_dim, cfg.rope_theta,
                                 jnp.dtype(cfg.dtype),
                                 cfg.rope_scaling)
        x = self.embed_tokens(input_ids)
        for blk in self.layers:
            x = blk(x, cos, sin)
        return self.norm(x)


class LlamaForCausalLM(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.llama = LlamaModel(cfg)
        if not cfg.tie_word_embeddings:
            if cfg.use_mp:
                self.lm_head = ColumnParallelLinear(
                    cfg.hidden_size, cfg.vocab_size, has_bias=False)
            else:
                self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size,
                                      bias_attr=False)

    def forward(self, input_ids, labels=None):
        from ..ops import api as _api
        h = self.llama(input_ids)
        if labels is not None and self.cfg.fused_head \
                and not self.cfg.use_mp:
            # logits-free loss (ops/fused_cross_entropy): head matmul
            # fused into the chunked softmax-CE reduction
            w = self.llama.embed_tokens.weight \
                if self.cfg.tie_word_embeddings else self.lm_head.weight
            layout = "vh" if self.cfg.tie_word_embeddings else "hv"
            return F.fused_linear_cross_entropy(h, w, labels,
                                                w_layout=layout)
        if self.cfg.tie_word_embeddings:
            logits = _api.matmul(h, self.llama.embed_tokens.weight,
                                 transpose_y=True)
        else:
            logits = self.lm_head(h)
        if labels is not None:
            return F.cross_entropy(
                _api.reshape(logits, [-1, self.cfg.vocab_size]),
                _api.reshape(labels, [-1]))
        return logits


# ---------------------------------------------------------------------------
# Pipelined pure-function path (flagship compiled train step)
# ---------------------------------------------------------------------------
def init_block_params(cfg: LlamaConfig, key) -> Dict[str, jax.Array]:
    h, f, d = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    std = cfg.initializer_range
    ks = jax.random.split(key, 7)
    dt = jnp.dtype(cfg.dtype)
    kvd = cfg.kv_heads * d
    out = {
        "ln1_w": jnp.ones((h,), dt), "ln2_w": jnp.ones((h,), dt),
        "q_w": jax.random.normal(ks[0], (h, cfg.num_heads * d), dt) * std,
        "k_w": jax.random.normal(ks[1], (h, kvd), dt) * std,
        "v_w": jax.random.normal(ks[2], (h, kvd), dt) * std,
        "o_w": jax.random.normal(ks[3], (cfg.num_heads * d, h), dt) * std,
    }
    if cfg.moe_num_experts:
        E = cfg.moe_num_experts
        out.update({
            "router_w": jax.random.normal(jax.random.fold_in(key, 7),
                                          (h, E), dt) * std,
            "e_gate": jax.random.normal(ks[4], (E, h, f), dt) * std,
            "e_up": jax.random.normal(ks[5], (E, h, f), dt) * std,
            "e_down": jax.random.normal(ks[6], (E, f, h), dt) * std,
        })
        if cfg.moe_num_shared_experts:
            fs = cfg.moe_num_shared_experts * f
            k8, k9, k10 = jax.random.split(jax.random.fold_in(key, 8), 3)
            out.update({
                "s_gate": jax.random.normal(k8, (h, fs), dt) * std,
                "s_up": jax.random.normal(k9, (h, fs), dt) * std,
                "s_down": jax.random.normal(k10, (fs, h), dt) * std,
            })
    else:
        out.update({
            "gate_w": jax.random.normal(ks[4], (h, f), dt) * std,
            "up_w": jax.random.normal(ks[5], (h, f), dt) * std,
            "down_w": jax.random.normal(ks[6], (f, h), dt) * std,
        })
    return out


def block_param_specs(cfg: LlamaConfig, pipeline: bool) -> Dict[str, P]:
    base = {
        "ln1_w": P(), "ln2_w": P(),
        "q_w": P(None, MP_AXIS), "k_w": P(None, MP_AXIS),
        "v_w": P(None, MP_AXIS), "o_w": P(MP_AXIS, None),
    }
    if cfg.moe_num_experts:
        base.update({
            "router_w": P(),
            "e_gate": P(DP_AXIS, None, MP_AXIS),
            "e_up": P(DP_AXIS, None, MP_AXIS),
            "e_down": P(DP_AXIS, MP_AXIS, None),
        })
        if cfg.moe_num_shared_experts:
            base.update({
                "s_gate": P(None, MP_AXIS), "s_up": P(None, MP_AXIS),
                "s_down": P(MP_AXIS, None),
            })
    else:
        base.update({
            "gate_w": P(None, MP_AXIS), "up_w": P(None, MP_AXIS),
            "down_w": P(MP_AXIS, None),
        })
    if not pipeline:
        return base
    return {k: P(PP_AXIS, None, *list(v)) for k, v in base.items()}


def block_apply(params: Dict[str, jax.Array], x: jax.Array,
                cfg: LlamaConfig, cos, sin, attn_fn=None,
                mp_axis: Optional[str] = None,
                sequence_parallel: bool = False,
                tp_overlap: bool = False,
                ep_axis: Optional[str] = None,
                moe_aux_coef: Optional[float] = None) -> jax.Array:
    """One Llama block, pure jnp (stacked under lax.scan).

    ``mp_axis``: Megatron-style manual tensor parallelism — params are the
    LOCAL shards (q/k/v/gate/up column-split, o/down row-split), head
    counts derived from the local shard shapes; ``mp_copy`` before column
    matmuls, ``fwd_psum`` after row matmuls (see parallel/manual.py).

    ``sequence_parallel``: Megatron-SP — x's seq dim is sharded over mp;
    all-gather before column matmuls, reduce-scatter after row matmuls
    (parallel/sequence_parallel.py).

    ``tp_overlap`` (with sequence_parallel): ring-decompose each
    gather+matmul / matmul+reduce-scatter pair (parallel/overlap.py);
    sibling column weights (q/k/v, gate/up) are concatenated so each
    gather rides ONE ring regardless of how many matmuls consume it."""
    b = x.shape[0]

    def rms(v, w):
        ms = jnp.mean(jnp.square(v.astype(jnp.float32)), -1, keepdims=True)
        return (v * jax.lax.rsqrt(ms + cfg.rms_norm_eps)).astype(v.dtype) * w

    if mp_axis is not None and sequence_parallel:
        from ..parallel.sequence_parallel import (all_gather_op,
                                                 reduce_scatter_op)
        col_in = lambda y: all_gather_op(y, mp_axis)
        row_out = lambda z: reduce_scatter_op(z, mp_axis)
    elif mp_axis is not None:
        from ..parallel.manual import fwd_psum, mp_copy
        col_in = lambda y: mp_copy(y, mp_axis)
        row_out = lambda z: fwd_psum(z, mp_axis)
    else:
        col_in = row_out = lambda y: y

    from ..parallel.overlap import sp_matmul_helpers
    col_mm, row_mm = sp_matmul_helpers(mp_axis, sequence_parallel,
                                       tp_overlap, col_in, row_out)

    res = x
    qh, kh, vh = col_mm(rms(x, params["ln1_w"]),
                        params["q_w"], params["k_w"], params["v_w"])
    s = qh.shape[1]   # full (gathered) seq length under SP
    q = qh.reshape(b, s, -1, cfg.head_dim)
    k = kh.reshape(b, s, -1, cfg.head_dim)
    v = vh.reshape(b, s, -1, cfg.head_dim)
    q, k = apply_rope(q, k, cos, sin)
    if attn_fn is not None:
        # GQA is native in every attn_fn path (Pallas flash kernel, ring,
        # Ulysses) — k/v keep their grouped head count, no jnp.repeat.
        attn = attn_fn(q, k, v)
    else:
        attn = _gqa_attention(q, k, v, causal=True)
    attn = attn.reshape(b, s, attn.shape[2] * attn.shape[3])
    x = res + row_mm(attn, params["o_w"])
    res = x
    y_ln = rms(x, params["ln2_w"])   # pre-gather: shared by both paths
    y_in = y_ln
    if cfg.moe_num_experts:
        from ..parallel.moe import moe_swiglu_ffn_ep
        if mp_axis is not None and sequence_parallel:
            from ..parallel.sequence_parallel import (all_gather_op,
                                                      scatter_op)
            y_in = all_gather_op(y_ln, mp_axis)
        out = moe_swiglu_ffn_ep(
            y_in, params["router_w"], params["e_gate"], params["e_up"],
            params["e_down"], top_k=cfg.moe_top_k,
            capacity_factor=cfg.moe_capacity(), ep_axis=ep_axis,
            mp_axis=mp_axis, sequence_parallel=sequence_parallel,
            aux_coef=(cfg.moe_aux_coef if moe_aux_coef is None
                      else moe_aux_coef),
            router=cfg.moe_router, dropless=cfg.moe_dropless)
        if mp_axis is not None and sequence_parallel:
            out = scatter_op(out, mp_axis)
        if cfg.moe_num_shared_experts:
            # dense always-on experts ride the standard column/row TP
            # machinery (incl. SP gather/scatter and tp_overlap rings);
            # output sharding matches the routed 'out'; y_ln reuses the
            # single pre-gather RMSNorm
            sg, su = col_mm(y_ln, params["s_gate"], params["s_up"])
            out = out + row_mm(jax.nn.silu(sg) * su, params["s_down"])
        return res + out
    g, u = col_mm(y_in, params["gate_w"], params["up_w"])
    y = jax.nn.silu(g) * u
    return res + row_mm(y, params["down_w"])


def stack_block_params(cfg: LlamaConfig, key, num_stages: int
                      ) -> Dict[str, jax.Array]:
    """``[num_stages, per, ...]`` leaves, layer ``i`` seeded by the
    ``i``-th split of ``key``.  vmapped over the keys, so every leaf is
    born stacked: a per-layer list stacked afterwards holds the model
    twice at its peak and leaves the device heap in layer-sized holes,
    which at 7B width on a 16 GB chip is the difference between a
    decode step's temporaries fitting and not."""
    per = cfg.num_layers // num_stages
    keys = jax.random.split(key, cfg.num_layers).reshape(num_stages, per)
    return jax.vmap(jax.vmap(lambda k: init_block_params(cfg, k)))(keys)


def llama_param_specs(cfg: LlamaConfig, topo, num_model_chunks: int = 1):
    """``(param_specs, restack)`` of the stacked train/serve param tree
    on ``topo`` (``restack`` regroups blocks for virtual-pipeline
    chunks; see ``manual.vpp_block_layout``)."""
    from ..parallel import manual as man
    blk_specs, restack = man.vpp_block_layout(
        block_param_specs(cfg, pipeline=True),
        topo.get_pipe_parallel_world_size(), num_model_chunks,
        cfg.num_layers)
    return {"wte": P(MP_AXIS, None), "head": P(None, MP_AXIS),
            "lnf_w": P(), "blocks": blk_specs}, restack


def init_llama_params(cfg: LlamaConfig, topo, seed: int = 0,
                      num_model_chunks: int = 1):
    """Seeded params placed on ``topo`` — and nothing else.  The train
    step's ``init_fn`` adds the fp32 Adam moments on top of this; a
    server (``serving/http.py``, ``chip_smoke.py``, the bench serve
    rows) calls this directly, so it never allocates optimizer state it
    would throw away (two fp32 moment trees are 4x the bf16 weights)."""
    param_specs, restack = llama_param_specs(cfg, topo, num_model_chunks)
    S = topo.get_pipe_parallel_world_size()

    def sh(spec):
        return NamedSharding(topo.mesh, spec)

    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    dt = jnp.dtype(cfg.dtype)
    if num_model_chunks == 1:
        blocks = stack_block_params(cfg, k3, S)
    else:
        blocks = restack(stack_block_params(cfg, k3, S * num_model_chunks))
    return {
        "wte": jax.device_put(
            jax.random.normal(k1, (cfg.vocab_size, cfg.hidden_size), dt)
            * cfg.initializer_range, sh(param_specs["wte"])),
        "head": jax.device_put(
            jax.random.normal(k2, (cfg.hidden_size, cfg.vocab_size), dt)
            * cfg.initializer_range, sh(param_specs["head"])),
        "lnf_w": jax.device_put(jnp.ones(cfg.hidden_size, dt), sh(P())),
        "blocks": {n: jax.device_put(v, sh(param_specs["blocks"][n]))
                   for n, v in blocks.items()},
    }


def build_llama_train_step(cfg: LlamaConfig, topo=None,
                           num_microbatches: int = 4,
                           learning_rate: float = 1e-4,
                           cp_mode: str = None,
                           use_flash: Optional[bool] = None,
                           remat: bool = True,
                           remat_policy=None,
                           schedule: str = "1f1b",
                           sharding_stage: int = 2,
                           num_model_chunks: int = 1,
                           offload_optimizer: bool = False,
                           sequence_parallel: bool = False,
                           tp_overlap: bool = False,
                           fused_head: Optional[bool] = None,
                           head_chunk: Optional[int] = None):
    """Compiled hybrid dp×mp×pp×sharding×sep Llama train step.

    Fully-manual SPMD via parallel/manual.py:build_hybrid_train_step
    (same design as models/gpt.py:build_gpt_train_step — Megatron-style
    mp collectives, scan pipeline over pp, ring/Ulysses over sep, ZeRO
    stage-2 Adam over sharding).  Untied vocab-parallel head
    (column-split) + parallel cross-entropy.

    Returns (step_fn, init_fn)."""
    from ..parallel import manual as man
    topo = topo or get_topology()
    S = topo.get_pipe_parallel_world_size()
    mp = topo.get_model_parallel_world_size()
    sep = topo.get_sep_parallel_world_size()
    dp = topo.axis_size(DP_AXIS)
    shard = topo.axis_size(SHARDING_AXIS)
    if cfg.num_layers % S != 0:
        raise ValueError(
            f"num_layers {cfg.num_layers} not divisible by pp degree {S}")
    if cfg.moe_num_experts and cfg.moe_num_experts % dp != 0:
        raise ValueError(
            f"moe_num_experts={cfg.moe_num_experts} not divisible by the "
            f"expert-parallel (dp) degree {dp}")
    if cfg.moe_num_experts and cfg.moe_dropless:
        if cfg.moe_router != "topk":
            raise ValueError("moe_dropless applies to token-choice "
                             "routing only (moe_router='topk')")
        if dp > 1 or mp > 1:
            raise ValueError("moe_dropless needs local expert banks: "
                             "dp==1 and mp==1 (got dp=%d mp=%d)"
                             % (dp, mp))
    if mp > 1:
        for name, val in (("vocab_size", cfg.vocab_size),
                          ("num_heads", cfg.num_heads),
                          ("kv_heads", cfg.kv_heads),
                          ("intermediate_size", cfg.intermediate_size)):
            if val % mp != 0:
                raise ValueError(f"{name}={val} not divisible by mp={mp}")
    if cp_mode not in (None, "ring", "ulysses", "zigzag"):
        raise ValueError(f"unknown cp_mode {cp_mode!r}")
    if tp_overlap and not (sequence_parallel and mp > 1):
        raise ValueError("tp_overlap=True requires sequence_parallel=True "
                         "and mp>1")
    if sep > 1 and cp_mode is None:
        cp_mode = "ring"
    if cp_mode == "ulysses" and (cfg.num_heads // mp) % sep != 0:
        raise ValueError("ulysses needs (num_heads/mp) % sep == 0")

    if sep > 1:
        from ..parallel.context_parallel import (
            ring_flash_attention, ulysses_attention,
            zigzag_ring_flash_attention)
        if cp_mode == "ring":
            def cp_attn(q, k, v):
                return ring_flash_attention(q, k, v, SEP_AXIS, True)
        elif cp_mode == "zigzag":
            def cp_attn(q, k, v):
                return zigzag_ring_flash_attention(q, k, v, SEP_AXIS)
        else:
            def cp_attn(q, k, v):
                return ulysses_attention(q, k, v, SEP_AXIS, True)
    else:
        if use_flash is None and jax.default_backend() not in ("cpu",):
            # auto backend (ops/attention_policy): dense XLA attention
            # while its residuals fit HBM, the best tuned flash backend
            # once they don't — decided at trace time on the device-local
            # q/k shapes (ops/pallas/flash_backends)
            import functools
            from ..ops.attention_policy import make_auto_attn
            from ..ops.pallas.flash_backends import tuned_flash
            cp_attn = make_auto_attn(
                cfg.num_layers, S, num_microbatches, schedule, remat,
                remat_policy, functools.partial(tuned_flash, causal=True),
                functools.partial(_gqa_attention, causal=True),
                state_bytes=lambda: man.train_state_bytes(
                    jax.eval_shape(init_params_fn, 0), param_specs, topo))
        elif isinstance(use_flash, str):
            import math as _math
            from ..ops.pallas.flash_backends import run_backend

            def cp_attn(q, k, v, _b=use_flash):
                return run_backend(_b, q, k, v,
                                   1.0 / _math.sqrt(q.shape[-1]), True)
        elif use_flash:
            import functools
            from ..ops.pallas.flash_backends import tuned_flash
            cp_attn = functools.partial(tuned_flash, causal=True)
        else:
            cp_attn = None

    vpp = num_model_chunks if schedule == "interleave" else 1
    param_specs, _ = llama_param_specs(cfg, topo, vpp)

    def init_params_fn(seed: int = 0):
        return init_llama_params(cfg, topo, seed, vpp)

    sp = sequence_parallel and mp > 1
    if sp:
        from ..parallel.sequence_parallel import gather_op, scatter_op

    def embed_fn(params, ids):
        x = man.vocab_parallel_embedding(ids, params["wte"])
        if sp:
            x = scatter_op(x, MP_AXIS)
        return x

    def step_ctx_fn(s_l):
        # rope table for this sep shard's ORIGINAL global positions —
        # contiguous [sidx*s_l, (sidx+1)*s_l), or the two zigzag blocks
        # (i, 2R-1-i) — computed once per step, hoisted out of the
        # per-layer scan (and out of the remat backward) via step_ctx.
        cos, sin = _rope_cos_sin(s_l * sep, cfg.head_dim, cfg.rope_theta,
                                 jnp.dtype(cfg.dtype),
                                 cfg.rope_scaling)
        if cp_mode == "zigzag":
            from ..parallel.context_parallel import zigzag_positions
            pos = zigzag_positions(s_l, SEP_AXIS)
            return jnp.take(cos, pos, 0), jnp.take(sin, pos, 0)
        sidx = jax.lax.axis_index(SEP_AXIS)
        lcos = jax.lax.dynamic_slice_in_dim(cos, sidx * s_l, s_l, 0)
        lsin = jax.lax.dynamic_slice_in_dim(sin, sidx * s_l, s_l, 0)
        return lcos, lsin

    def _moe_coef(x, lcos):
        # lcos rows == the local seq length s_l
        if not cfg.moe_num_experts:
            return None
        from ..parallel.moe import schedule_aux_coef
        return schedule_aux_coef(
            cfg.moe_aux_coef, cfg.num_layers, schedule, S,
            num_microbatches, dp * shard * sep,
            x.shape[0] * lcos.shape[0])

    def block_fn(layer_params, x, ctx):
        lcos, lsin = ctx
        return block_apply(layer_params, x, cfg, lcos, lsin, cp_attn,
                           mp_axis=MP_AXIS, sequence_parallel=sp,
                           tp_overlap=tp_overlap,
                           ep_axis=DP_AXIS if cfg.moe_num_experts else None,
                           moe_aux_coef=_moe_coef(x, lcos))

    use_fused_head = cfg.fused_head if fused_head is None else fused_head

    def head_nll_fn(params, x, labels):
        if sp:
            x = gather_op(x, MP_AXIS)
        ms = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        x = (x * jax.lax.rsqrt(ms + cfg.rms_norm_eps)).astype(x.dtype) \
            * params["lnf_w"]
        if use_fused_head:
            # logits-free fused head: untied Linear-layout ([h, V/mp])
            # column-parallel shard streams through the chunk loop
            if mp > 1:
                return man.vocab_parallel_linear_nll(
                    x, params["head"], labels, w_layout="hv",
                    chunk=head_chunk)
            from ..ops.fused_cross_entropy import linear_cross_entropy
            return linear_cross_entropy(x, params["head"], labels,
                                        w_layout="hv", chunk=head_chunk)
        xf = man.mp_copy(x, MP_AXIS)   # column-parallel head
        logits = jnp.einsum("bsh,hv->bsv", xf, params["head"],
                            preferred_element_type=jnp.float32)
        return man.vocab_parallel_nll(logits, labels)

    return man.build_hybrid_train_step(
        topo=topo, param_specs=param_specs, init_params_fn=init_params_fn,
        embed_fn=embed_fn, block_fn=block_fn, head_nll_fn=head_nll_fn,
        step_ctx_fn=step_ctx_fn,
        num_microbatches=num_microbatches, learning_rate=learning_rate,
        remat=remat, remat_policy=remat_policy,
        schedule=schedule, sharding_stage=sharding_stage,
        num_model_chunks=num_model_chunks,
        offload_optimizer=offload_optimizer,
        mp_reduce_block_leaves=frozenset(
            {"ln1_w", "ln2_w"} if sp else ()))
