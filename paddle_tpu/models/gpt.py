"""GPT model family — the flagship train config (BASELINE configs 4/6:
GPT-3 1.3B mp2×pp2, GPT-3 13B north star).

Reference model: the fleet GPT used by auto-parallel tests
(/root/reference/test/auto_parallel/get_gpt_model.py) built from
fleet.meta_parallel mp layers.  Two execution paths:

* :class:`GPTForCausalLM` — imperative Layer graph with TP-annotated
  parameters (Column/RowParallelLinear, VocabParallelEmbedding); runs eager,
  under the hapi trainer, or sharded via DistributedEngine (dp/mp/sharding).
* :func:`build_gpt_train_step` — fully-compiled hybrid
  dp×mp×pp×sharding×sep train step: one fully-MANUAL shard_map over all
  five mesh axes, Megatron-style tensor parallelism via explicit
  collectives (parallel/manual.py), the scan pipeline over ``pp``
  (parallel/pipeline.py), ring/Ulysses context parallelism over ``sep``,
  and flat ZeRO stage-2 Adam over the ``sharding`` axis.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer.common import Dropout, Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import LayerNorm
from ..parallel.mp_layers import (ColumnParallelLinear, RowParallelLinear,
                                  VocabParallelEmbedding, constrain,
                                  mark_sharding)
from ..parallel.topology import (DP_AXIS, MP_AXIS, PP_AXIS, SEP_AXIS,
                                 SHARDING_AXIS, get_topology)

__all__ = ["GPTConfig", "GPTBlock", "GPTModel", "GPTForCausalLM",
           "gpt_tiny", "gpt_125m", "gpt_1p3b", "gpt_6p7b", "gpt_13b",
           "stack_block_params", "block_apply", "build_gpt_train_step"]


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: Optional[int] = None
    max_position_embeddings: int = 1024
    dropout: float = 0.0
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    use_mp: bool = False       # build with tensor-parallel layers
    tie_word_embeddings: bool = True
    dtype: str = "float32"
    # Mixture-of-experts FFN (0 = dense).  Experts are sharded over the
    # dp mesh axis in the compiled hybrid step (expert parallelism, the
    # reference's moe_layer.py:263 EP group) with all_to_all dispatch.
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # expert_choice capacity is a DIFFERENT quantity (average experts per
    # token, not GShard slack); moe_ec_capacity names it explicitly and
    # falls back to moe_capacity_factor when unset (ADVICE r4)
    moe_ec_capacity: "Optional[float]" = None
    moe_aux_coef: float = 1e-2
    # "topk" (GShard-style token choice) or "expert_choice" (experts pick
    # their top-C tokens — perfectly balanced, no aux loss; best for
    # encoder-style training, routing is batch-global so NOT causal)
    moe_router: str = "topk"
    moe_dropless: bool = False  # sorted ragged_dot experts (no drops;
    # local banks only — mutually exclusive with dp-EP / mp expert TP)
    # logits-free fused cross-entropy head (ops/fused_cross_entropy):
    # the eager CausalLM loss and build_gpt_train_step's head_nll_fn
    # stream vocab chunks instead of materializing [B, S, V] logits
    fused_head: bool = True


    def moe_capacity(self) -> float:
        if self.moe_router == "expert_choice" and \
                self.moe_ec_capacity is not None:
            return self.moe_ec_capacity
        return self.moe_capacity_factor

    @property
    def ffn_size(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


def gpt_tiny(**kw) -> GPTConfig:
    return GPTConfig(vocab_size=128, hidden_size=64, num_layers=4,
                     num_heads=4, max_position_embeddings=64, **kw)


def gpt_125m(**kw) -> GPTConfig:
    return GPTConfig(hidden_size=768, num_layers=12, num_heads=12, **kw)


def gpt_1p3b(**kw) -> GPTConfig:
    return GPTConfig(hidden_size=2048, num_layers=24, num_heads=16,
                     max_position_embeddings=2048, **kw)


def gpt_6p7b(**kw) -> GPTConfig:
    return GPTConfig(hidden_size=4096, num_layers=32, num_heads=32,
                     max_position_embeddings=2048, **kw)


def gpt_13b(**kw) -> GPTConfig:
    return GPTConfig(hidden_size=5120, num_layers=40, num_heads=40,
                     max_position_embeddings=2048, **kw)


def _pallas_epilogue_gate() -> bool:
    """Same dispatch rule as attention: Pallas on TPU, or when
    interpret mode is forced (CPU kernel tests)."""
    from ..nn.functional.attention import _should_use_pallas
    return _should_use_pallas(None)


class GPTBlock(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        self.ln1 = LayerNorm(h, epsilon=cfg.layer_norm_eps)
        self.ln2 = LayerNorm(h, epsilon=cfg.layer_norm_eps)
        if cfg.use_mp:
            self.qkv = ColumnParallelLinear(h, 3 * h, gather_output=False)
            self.proj = RowParallelLinear(h, h, input_is_parallel=True)
        else:
            self.qkv = Linear(h, 3 * h)
            self.proj = Linear(h, h)
        if cfg.moe_num_experts:
            # eager MoE path: the incubate MoELayer (GShard gate, dense
            # capacity dispatch); expert TP/EP belong to the compiled
            # hybrid step (build_gpt_train_step + parallel/moe.py)
            from ..incubate.distributed.models.moe import MoELayer
            if cfg.moe_dropless or cfg.moe_router != "topk":
                # expert_choice / dropless run the SAME moe_ffn_ep routine
                # as the compiled hybrid step (eager-vs-compiled logit
                # equivalence by construction; VERDICT r4 item 7) — the
                # gate zoo below covers the reference's capacity dispatch
                self.moe = MoELayer(
                    h, cfg.ffn_size, cfg.moe_num_experts, gate="naive",
                    top_k=cfg.moe_top_k, aux_coef=cfg.moe_aux_coef,
                    router=cfg.moe_router, dropless=cfg.moe_dropless,
                    capacity_factor=cfg.moe_capacity())
            else:
                self.moe = MoELayer(h, cfg.ffn_size, cfg.moe_num_experts,
                                    gate="gshard", top_k=cfg.moe_top_k,
                                    aux_coef=cfg.moe_aux_coef)
        elif cfg.use_mp:
            self.fc1 = ColumnParallelLinear(h, cfg.ffn_size,
                                            gather_output=False)
            self.fc2 = RowParallelLinear(cfg.ffn_size, h,
                                         input_is_parallel=True)
        else:
            self.fc1 = Linear(h, cfg.ffn_size)
            self.fc2 = Linear(cfg.ffn_size, h)
        self.drop = Dropout(cfg.dropout)

    def forward(self, x):
        from ..ops import api as _api
        cfg = self.cfg
        b, s = x.shape[0], x.shape[1]
        # Pallas epilogues (norms.py kernels) on the eager path: fused
        # layer_norm for ln1 and bias+dropout+residual+layer_norm for the
        # attention epilogue — gated exactly like attention dispatch
        # (_should_use_pallas: TPU, or interpret forced for tests) and
        # off under eager tensor parallelism (Row/ColumnParallelLinear
        # own their collectives and bias placement).
        fuse = (not cfg.use_mp) and _pallas_epilogue_gate()
        residual = x
        y = F.fused_layer_norm(x, self.ln1.weight, self.ln1.bias,
                               epsilon=cfg.layer_norm_eps) if fuse \
            else self.ln1(x)
        qkv = self.qkv(y)
        qkv = _api.reshape(qkv, [b, s, cfg.num_heads, 3 * cfg.head_dim])
        q, k, v = _api.split(qkv, 3, axis=-1)
        attn = F.scaled_dot_product_attention(
            q, k, v, is_causal=True, dropout_p=cfg.dropout,
            training=self.training)
        attn = _api.reshape(attn, [b, s, cfg.hidden_size])
        if fuse:
            proj = _api.matmul(attn, self.proj.weight)
            y, x = F.fused_bias_dropout_residual_layer_norm(
                proj, residual, self.proj.bias, self.ln2.weight,
                self.ln2.bias, dropout_rate=cfg.dropout,
                epsilon=cfg.layer_norm_eps, training=self.training,
                return_add_out=True)
        else:
            x = residual + self.drop(self.proj(attn))
            y = self.ln2(x)
        residual = x
        if cfg.moe_num_experts:
            y = self.moe(y)
        else:
            y = self.fc2(F.gelu(self.fc1(y), approximate=True))
        return residual + self.drop(y)


class GPTModel(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        from ..nn.attr import ParamAttr
        emb_attr = ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range))
        if cfg.use_mp:
            self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size,
                                              weight_attr=emb_attr)
        else:
            self.wte = Embedding(cfg.vocab_size, cfg.hidden_size,
                                 weight_attr=emb_attr)
        self.wpe = Embedding(cfg.max_position_embeddings, cfg.hidden_size,
                             weight_attr=ParamAttr(
                                 initializer=I.Normal(
                                     0.0, cfg.initializer_range)))
        self.drop = Dropout(cfg.dropout)
        self.blocks = LayerList([GPTBlock(cfg) for _ in range(cfg.num_layers)])
        self.ln_f = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)

    def forward(self, input_ids):
        from ..ops import api as _api
        b, s = input_ids.shape[0], input_ids.shape[1]
        pos = _api.arange(0, s, 1, dtype="int64")
        x = self.wte(input_ids) + self.wpe(pos)
        x = self.drop(x)
        for blk in self.blocks:
            x = blk(x)
        return self.ln_f(x)


class GPTForCausalLM(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)
        if not cfg.tie_word_embeddings:
            if cfg.use_mp:
                self.lm_head = ColumnParallelLinear(
                    cfg.hidden_size, cfg.vocab_size, has_bias=False)
            else:
                self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size,
                                      bias_attr=False)

    def forward(self, input_ids, labels=None):
        from ..ops import api as _api
        h = self.gpt(input_ids)
        if labels is not None and self.cfg.fused_head \
                and not self.cfg.use_mp:
            # logits-free loss: the head matmul fuses into the chunked
            # softmax-CE reduction — [B, S, V] never materializes
            w = self.gpt.wte.weight if self.cfg.tie_word_embeddings \
                else self.lm_head.weight
            layout = "vh" if self.cfg.tie_word_embeddings else "hv"
            return F.fused_linear_cross_entropy(h, w, labels,
                                                w_layout=layout)
        if self.cfg.tie_word_embeddings:
            logits = _api.matmul(h, self.gpt.wte.weight, transpose_y=True)
        else:
            logits = self.lm_head(h)
        if labels is not None:
            loss = F.cross_entropy(
                _api.reshape(logits, [-1, self.cfg.vocab_size]),
                _api.reshape(labels, [-1]))
            return loss
        return logits


# ---------------------------------------------------------------------------
# Pipelined pure-function path
# ---------------------------------------------------------------------------
def init_block_params(cfg: GPTConfig, key) -> Dict[str, jax.Array]:
    """Pure init of one block's params (names match block_apply)."""
    h, f = cfg.hidden_size, cfg.ffn_size
    std = cfg.initializer_range
    # 4-way split as always — the dense init streams must stay stable
    # across versions (recorded bench losses); the MoE gate key is derived
    # separately via fold_in so moe_num_experts=0 reproduces exactly
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    out = {
        "ln1_w": jnp.ones((h,), dt), "ln1_b": jnp.zeros((h,), dt),
        "ln2_w": jnp.ones((h,), dt), "ln2_b": jnp.zeros((h,), dt),
        "qkv_w": jax.random.normal(ks[0], (h, 3 * h), dt) * std,
        "qkv_b": jnp.zeros((3 * h,), dt),
        "proj_w": jax.random.normal(ks[1], (h, h), dt) * std,
        "proj_b": jnp.zeros((h,), dt),
    }
    if cfg.moe_num_experts:
        E = cfg.moe_num_experts
        gate_key = jax.random.fold_in(key, 4)
        out.update({
            "gate_w": jax.random.normal(gate_key, (h, E), dt) * std,
            "e_w1": jax.random.normal(ks[2], (E, h, f), dt) * std,
            "e_b1": jnp.zeros((E, f), dt),
            "e_w2": jax.random.normal(ks[3], (E, f, h), dt) * std,
            "e_b2": jnp.zeros((E, h), dt),
        })
    else:
        out.update({
            "fc1_w": jax.random.normal(ks[2], (h, f), dt) * std,
            "fc1_b": jnp.zeros((f,), dt),
            "fc2_w": jax.random.normal(ks[3], (f, h), dt) * std,
            "fc2_b": jnp.zeros((h,), dt),
        })
    return out


def block_param_specs(cfg: GPTConfig, pipeline: bool) -> Dict[str, P]:
    """TP sharding for block params; with pipeline=True add leading
    [pp, per] dims."""
    base = {
        "ln1_w": P(), "ln1_b": P(), "ln2_w": P(), "ln2_b": P(),
        "qkv_w": P(None, MP_AXIS), "qkv_b": P(MP_AXIS),
        "proj_w": P(MP_AXIS, None), "proj_b": P(),
    }
    if cfg.moe_num_experts:
        # expert parallelism: expert dim over dp (each data rank owns
        # E/dp experts), Megatron TP inside each expert over mp
        base.update({
            "gate_w": P(),
            "e_w1": P(DP_AXIS, None, MP_AXIS), "e_b1": P(DP_AXIS, MP_AXIS),
            "e_w2": P(DP_AXIS, MP_AXIS, None), "e_b2": P(DP_AXIS, None),
        })
    else:
        base.update({
            "fc1_w": P(None, MP_AXIS), "fc1_b": P(MP_AXIS),
            "fc2_w": P(MP_AXIS, None), "fc2_b": P(),
        })
    if not pipeline:
        return base
    return {k: P(PP_AXIS, None, *list(v)) for k, v in base.items()}


def dense_causal_attention(q: jax.Array, k: jax.Array,
                           v: jax.Array) -> jax.Array:
    """Plain-XLA causal attention, [B, S, H, D] in/out.  XLA fuses this
    into its own attention kernel; on v5e it beats the Pallas flash path
    whenever the f32 logit residuals fit HBM (see ops/attention_policy)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = q.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = jnp.tril(jnp.ones((s, s), bool))
    logits = jnp.where(mask, logits.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(logits, -1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def block_apply(params: Dict[str, jax.Array], x: jax.Array,
                cfg: GPTConfig, attn_fn=None,
                mp_axis: Optional[str] = None,
                sequence_parallel: bool = False,
                tp_overlap: bool = False,
                ep_axis: Optional[str] = None,
                moe_aux_coef: Optional[float] = None) -> jax.Array:
    """One transformer block, pure jnp (used stacked under lax.scan).

    ``attn_fn(q, k, v) -> out`` (all [b, s, heads_local, head_dim])
    overrides the attention op — used for ring/Ulysses context parallelism
    where the seq dim is a manual mesh axis (parallel/context_parallel.py).

    ``mp_axis``: when set, params are the Megatron-style LOCAL shards of a
    tensor-parallel block (qkv/fc1 column-split, proj/fc2 row-split,
    reference fleet/layers/mpu/mp_layers.py:334/541) and the function runs
    inside a manual shard_map: ``mp_copy`` before column matmuls (identity
    fwd / psum bwd), ``psum`` after row matmuls, biases added post-psum.

    ``sequence_parallel`` (with mp_axis): Megatron-SP — ``x`` arrives with
    its SEQ dim sharded over mp; column inputs all-gather the sequence and
    row outputs reduce-scatter it back (parallel/sequence_parallel.py,
    reference sequence_parallel_utils.py:427/562).  LayerNorms and biases
    then act on the shard, so their grads are partial over mp (see
    build_hybrid_train_step's mp_reduce_block_leaves).

    ``tp_overlap`` (with sequence_parallel): decompose each seq
    all-gather + column matmul and row matmul + reduce-scatter into a
    ppermute ring (parallel/overlap.py) so XLA hides the ICI hops behind
    the chunked gemms — the reference's sequence_parallel_utils.py:255
    overlap path, TPU-native."""
    b = x.shape[0]

    def ln(v, w, bia):
        mean = jnp.mean(v, -1, keepdims=True)
        var = jnp.var(v, -1, keepdims=True)
        return (v - mean) * jax.lax.rsqrt(var + cfg.layer_norm_eps) * w + bia

    def col_in(y):
        if mp_axis is not None:
            if sequence_parallel:
                from ..parallel.sequence_parallel import all_gather_op
                return all_gather_op(y, mp_axis)
            from ..parallel.manual import mp_copy
            return mp_copy(y, mp_axis)
        return y

    def row_out(z):
        if mp_axis is not None:
            if sequence_parallel:
                from ..parallel.sequence_parallel import reduce_scatter_op
                return reduce_scatter_op(z, mp_axis)
            from ..parallel.manual import fwd_psum
            return fwd_psum(z, mp_axis)
        return z

    from ..parallel.overlap import sp_matmul_helpers
    col_mm, row_mm = sp_matmul_helpers(mp_axis, sequence_parallel,
                                       tp_overlap, col_in, row_out)

    res = x
    (qkv,) = col_mm(ln(x, params["ln1_w"], params["ln1_b"]),
                    params["qkv_w"])
    qkv = qkv + params["qkv_b"]
    s = qkv.shape[1]   # full (gathered) seq length under SP
    qkv = qkv.reshape(b, s, -1, 3 * cfg.head_dim)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    if attn_fn is not None:
        attn = attn_fn(q, k, v)
        attn = attn.reshape(b, s, attn.shape[2] * attn.shape[3])
    else:
        attn = dense_causal_attention(q, k, v)
        attn = attn.reshape(b, s, attn.shape[2] * attn.shape[3])
    x = res + row_mm(attn, params["proj_w"]) + params["proj_b"]
    res = x
    y_in = ln(x, params["ln2_w"], params["ln2_b"])
    if cfg.moe_num_experts:
        from ..parallel.moe import moe_ffn_ep
        if mp_axis is not None and sequence_parallel:
            from ..parallel.sequence_parallel import (all_gather_op,
                                                      scatter_op)
            y_in = all_gather_op(y_in, mp_axis)
        out = moe_ffn_ep(
            y_in, params["gate_w"], params["e_w1"], params["e_b1"],
            params["e_w2"], params["e_b2"], top_k=cfg.moe_top_k,
            capacity_factor=cfg.moe_capacity(), ep_axis=ep_axis,
            mp_axis=mp_axis, sequence_parallel=sequence_parallel,
            aux_coef=(cfg.moe_aux_coef if moe_aux_coef is None
                      else moe_aux_coef),
            router=cfg.moe_router,
            dropless=cfg.moe_dropless)
        if mp_axis is not None and sequence_parallel:
            out = scatter_op(out, mp_axis)
        return res + out
    (y,) = col_mm(y_in, params["fc1_w"])
    y = jax.nn.gelu(y + params["fc1_b"], approximate=True)
    return res + row_mm(y, params["fc2_w"]) + params["fc2_b"]


def stack_block_params(cfg: GPTConfig, key, num_stages: int
                       ) -> Dict[str, jax.Array]:
    """All layers' params stacked to [num_stages, per_stage, ...]."""
    per = cfg.num_layers // num_stages
    keys = jax.random.split(key, cfg.num_layers)
    blocks = [init_block_params(cfg, k) for k in keys]
    return {name: jnp.stack([b[name] for b in blocks]).reshape(
        (num_stages, per) + blocks[0][name].shape)
        for name in blocks[0]}


def build_gpt_train_step(cfg: GPTConfig, topo=None,
                         num_microbatches: int = 4,
                         learning_rate: float = 1e-4,
                         cp_mode: str = None,
                         use_flash: Optional[bool] = None,
                         remat: bool = True,
                         remat_policy=None,
                         schedule: str = "1f1b",
                         num_model_chunks: int = 1,
                         sharding_stage: int = 2,
                         offload_optimizer: bool = False,
                         sequence_parallel: bool = False,
                         tp_overlap: bool = False,
                         fused_head: Optional[bool] = None,
                         head_chunk: Optional[int] = None):
    """Compile a full hybrid-parallel GPT training step: dp×mp×pp×sharding×sep.

    Fully-MANUAL SPMD: one ``shard_map`` over ALL five mesh axes.  Tensor
    parallelism is Megatron-style local shards + explicit collectives
    (parallel/manual.py — vocab-parallel embedding/cross-entropy, mp_copy/
    psum around column/row matmuls, matching reference mp_layers.py
    semantics); pp is the scan pipeline (parallel/pipeline.py); sep is
    ring/Ulysses context parallelism; dp/sharding split the batch, with
    ZeRO stage-2 semantics on the sharding axis (grads reduce-scattered,
    fp32 Adam moments stored 1/shard per device, params all-gathered —
    reference group_sharded_stage2.py:46).

    Round-1 GSPMD-sharded params *around* a partial-manual shard_map, which
    exploded SPMD partitioning on mp×pp meshes (compile >10min); manual
    collectives keep compile time flat in mesh size.

    ``cp_mode``: None (auto: "ring" when sep>1), "ring", or "ulysses".

    ``fused_head`` (default: ``cfg.fused_head``, i.e. on): compute the
    loss through the logits-free chunked linear+softmax-CE head
    (``ops/fused_cross_entropy``) instead of materializing [b, s, V]
    fp32 logits; ``head_chunk`` overrides the vocab chunk width.

    Returns (step_fn, init_fn):
      init_fn(seed) -> state pytree placed on the mesh
      step_fn(state, batch_ids, batch_labels) -> (state, loss)
    """
    from ..parallel import manual as man
    topo = topo or get_topology()
    mesh = topo.mesh
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
                          ("ffn_size", cfg.ffn_size)):
            if val % mp != 0:
                raise ValueError(f"{name}={val} not divisible by mp={mp}")
    if cp_mode not in (None, "ring", "ulysses", "zigzag"):
        raise ValueError(f"unknown cp_mode {cp_mode!r}")
    if tp_overlap and not (sequence_parallel and mp > 1):
        # the ring decomposes the SP gather/scatter around each matmul;
        # plain-TP psum has no correct autodiff ring yet (the fwd_psum
        # custom-VJP convention would double-count) — fail loudly rather
        # than silently not overlapping
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
        # Pallas flash attention on the device-local shard: inside a fully
        # manual shard_map the custom-call needs no partitioning rule, so
        # it is usable on ANY mesh (round-1 limited it to mesh.size==1).
        if use_flash is None and jax.default_backend() not in ("cpu",):
            # auto: dense XLA attention while its residuals fit HBM, the
            # best tuned flash backend once they don't (ops/attention_policy
            # + ops/pallas/flash_backends — decided at trace time on the
            # device-LOCAL q/k shapes)
            from ..ops.attention_policy import make_auto_attn
            from ..ops.pallas.flash_backends import tuned_flash
            cp_attn = make_auto_attn(
                cfg.num_layers, S, num_microbatches, schedule, remat,
                remat_policy, functools.partial(tuned_flash, causal=True),
                dense_causal_attention,
                state_bytes=lambda: man.train_state_bytes(
                    jax.eval_shape(init_params_fn, 0), param_specs, topo))
        elif isinstance(use_flash, str):
            # explicit backend pin ("ours" / "jax_flash" / "splash") —
            # the bench sweep's per-backend rows
            from ..ops.pallas.flash_backends import run_backend
            import math as _math

            def cp_attn(q, k, v, _b=use_flash):
                return run_backend(_b, q, k, v,
                                   1.0 / _math.sqrt(q.shape[-1]), True)
        elif use_flash:
            from ..ops.pallas.flash_backends import tuned_flash
            cp_attn = functools.partial(tuned_flash, causal=True)
        else:
            cp_attn = None

    emb_specs = {
        "wte": P(MP_AXIS, None), "wpe": P(), "lnf_w": P(), "lnf_b": P(),
    }
    vpp = num_model_chunks if schedule == "interleave" else 1
    blk_specs, _vpp_restack = man.vpp_block_layout(
        block_param_specs(cfg, pipeline=True), S, vpp, cfg.num_layers)
    param_specs = dict(emb_specs, blocks=blk_specs)

    def _stacked_blocks(k3):
        if vpp == 1:
            return stack_block_params(cfg, k3, S)
        return _vpp_restack(stack_block_params(cfg, k3, S * vpp))

    def sh(spec):
        return NamedSharding(mesh, spec)

    def init_params_fn(seed: int = 0):
        key = jax.random.key(seed)
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "wte": jax.device_put(
                jax.random.normal(k1, (cfg.vocab_size, cfg.hidden_size),
                                  jnp.dtype(cfg.dtype))
                * cfg.initializer_range, sh(emb_specs["wte"])),
            "wpe": jax.device_put(
                jax.random.normal(k2, (cfg.max_position_embeddings,
                                       cfg.hidden_size), jnp.dtype(cfg.dtype))
                * cfg.initializer_range, sh(emb_specs["wpe"])),
            "lnf_w": jax.device_put(jnp.ones(cfg.hidden_size), sh(P())),
            "lnf_b": jax.device_put(jnp.zeros(cfg.hidden_size), sh(P())),
            "blocks": {n: jax.device_put(v, sh(blk_specs[n]))
                       for n, v in _stacked_blocks(k3).items()},
        }

    sp = sequence_parallel and mp > 1
    if sp:
        from ..parallel.sequence_parallel import gather_op, scatter_op

    def embed_fn(params, ids):
        s_l = ids.shape[1]
        x = man.vocab_parallel_embedding(ids, params["wte"])
        if cp_mode == "zigzag":
            # zigzag CP: this rank holds original blocks (i, 2R-1-i) —
            # learned position embeddings must use ORIGINAL positions
            from ..parallel.context_parallel import zigzag_positions
            pos = zigzag_positions(s_l, SEP_AXIS)
        else:
            pos = jax.lax.axis_index(SEP_AXIS) * s_l + jnp.arange(s_l)
        x = x + jnp.take(params["wpe"], pos, axis=0)[None]
        if sp:   # activations between blocks keep seq sharded over mp
            x = scatter_op(x, MP_AXIS)
        return x

    # MoE aux-loss injection coefficient: inject_aux_grad adds a CONSTANT
    # cotangent per site (layer x microbatch x data rank), while the two
    # schedule families normalize grads differently — the pipeline paths
    # divide the summed vjp by norm = b_l*s_l*dp*shard*sep afterwards,
    # the S==1 path divides the loss (but not the injected constant)
    # inside loss_fn.  These factors make both equal an effective
    #   loss += moe_aux_coef * mean_over_sites(aux)
    step_ctx_fn = None
    if cfg.moe_num_experts:
        def step_ctx_fn(s_l):
            return {"s_l": s_l}

    def _moe_coef(x, ctx):
        if not cfg.moe_num_experts:
            return None
        from ..parallel.moe import schedule_aux_coef
        return schedule_aux_coef(
            cfg.moe_aux_coef, cfg.num_layers, schedule, S,
            num_microbatches, dp * shard * sep, x.shape[0] * ctx["s_l"])

    def block_fn(layer_params, x, ctx):
        return block_apply(layer_params, x, cfg, cp_attn, mp_axis=MP_AXIS,
                           sequence_parallel=sp, tp_overlap=tp_overlap,
                           ep_axis=DP_AXIS if cfg.moe_num_experts else None,
                           moe_aux_coef=_moe_coef(x, ctx))

    use_fused_head = cfg.fused_head if fused_head is None else fused_head

    def head_nll_fn(params, x, labels):
        if sp:   # head/loss run on the full (replicated) sequence
            x = gather_op(x, MP_AXIS)
        mean = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        x = (x - mean) * jax.lax.rsqrt(var + cfg.layer_norm_eps) \
            * params["lnf_w"] + params["lnf_b"]
        if use_fused_head:
            # logits-free fused head (ops/fused_cross_entropy): no
            # [b, s, V] tensor, no mp_copy — its dx psum lives in the
            # fused VJP.  mp==1 runs the dense tier (Pallas on TPU);
            # mp>1 the vocab-parallel chunk loop with fused collectives.
            if mp > 1:
                return man.vocab_parallel_linear_nll(
                    x, params["wte"], labels, w_layout="vh",
                    chunk=head_chunk)
            from ..ops.fused_cross_entropy import linear_cross_entropy
            return linear_cross_entropy(x, params["wte"], labels,
                                        w_layout="vh", chunk=head_chunk)
        xf = man.mp_copy(x, MP_AXIS)   # tied head: column-parallel matmul
        logits = jnp.einsum("bsh,vh->bsv", xf, params["wte"],
                            preferred_element_type=jnp.float32)
        return man.vocab_parallel_nll(logits, labels)

    # Under SP, biases added on the mp-sharded sequence have mp-partial
    # grads.  The MoE block adds its expert biases BEFORE the scatter
    # back to the sequence shard (replicated over mp), so only proj_b
    # stays partial there.
    sp_reduce = {"ln1_w", "ln1_b", "ln2_w", "ln2_b", "proj_b"}
    if not cfg.moe_num_experts:
        sp_reduce.add("fc2_b")
    return man.build_hybrid_train_step(
        topo=topo, param_specs=param_specs, init_params_fn=init_params_fn,
        embed_fn=embed_fn, block_fn=block_fn, head_nll_fn=head_nll_fn,
        step_ctx_fn=step_ctx_fn,
        num_microbatches=num_microbatches, learning_rate=learning_rate,
        remat=remat, remat_policy=remat_policy,
        schedule=schedule, sharding_stage=sharding_stage,
        num_model_chunks=num_model_chunks,
        offload_optimizer=offload_optimizer,
        mp_reduce_block_leaves=frozenset(sp_reduce if sp else ()))
