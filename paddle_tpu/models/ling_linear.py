"""Ling-3.0-flash style decoder (``model_type`` ``bailing_hybrid``): five
layers in six mix tokens by KDA (Kimi Delta Attention, arXiv:2510.26692:
a delta-rule linear attention with a matrix state a head), the sixth by
multi-head latent attention; a leading run of dense SwiGLU layers, then
layers of sigmoid-routed, group-limited experts beside a shared one.

Serving only: this module offers the configuration, the seeded
parameter tree and the two programs the continuous-batching engine
compiles for such a model.  ``inference/serving.py`` reads BOTH
``cfg.layer_types`` (a recurrent state and a conv tail a decode slot,
pages for the attending layers only) and ``cfg.kv_lora_rank`` (those
pages are ONE latent pool) and finds :func:`build_step`,
:func:`build_chunk_fill` and :func:`init_slot_state` here, through the
configuration's module.  There is no train step; the vision tower of the
``-VL`` checkpoint and the multi-token-prediction layer are not held.

The equations (``rms`` an RMS norm with a gain, eps 1e-6)::

    h = wte[ids];  h = h + mix(rms(h));  h = h + ffn(rms(h))
    logits = rms(h) @ head                         (untied, no bias)

Layer ``i`` attends iff ``(i + 1) % layer_group_size == 0``.

``mix``, KDA (``nh`` heads of ``d`` = ``head_dim`` keys and values)::

    [q | k | v] = silu(conv4(x [W_q | W_k | W_v]))      depthwise, causal
    q = l2norm(q) / sqrt(d);  k = l2norm(k)             a head
    log a = kda_lower_bound * sigmoid(exp(A_log) (x W_f + dt_bias))
    beta  = sigmoid(x W_beta)                           a head
    S <- Diag(a) S;  S <- S + beta k (v - S^T k)^T;  o = S^T q
    y = (rms_head(o) * sigmoid(x W_g)) W_o              the gate a head

the recurrence ``ops/kda.py``'s: :func:`~paddle_tpu.ops.kda.
kda_chunk_scan` in a chunk fill, ``kda_state_update_row`` (on a TPU the
Pallas kernel ``kda_state_update``) in a decode step.  ``mix``, latent:
``ops/mla.py``'s, its query without a low-rank step, heads of 128 | 64 |
128, the output gated a head by ``sigmoid(x W_g)`` before ``W_o``; the
absorbed form in a decode step, the expanded form in a fill, as
``models/glm_moe_lite.py``.  ``ffn`` of the first
``first_k_dense_replace`` layers a SwiGLU of ``intermediate_size``; of
the others ``sum_j g_j E_j(x) + shared(x)`` with ``parallel/moe.py:
route_sigmoid``'s gate over ALL ``num_experts`` (8 groups, the 4 whose
two best scores sum highest, the 8 best in them).  A rank may hold a
contiguous share of the experts (``experts_held`` from
``expert_offset``): it adds only the terms of those it holds, gates
unchanged (``moe_swiglu_ffn_routed``; expert parallelism's partial sum:
the pairs on held experts sorted by expert and multiplied once each, in
a decode step as in a chunk fill, so that an expert's weights are read
only where a row chose it: a step of 128 rows hits half of 128 held,
PERF.md PR 37).

Parameter tree: ``{"wte" [V, H], "head" [H, V], "lnf_w" [H], "runs":
(run, ...)}``, one ``run`` a maximal stretch of layers of one kind
(``cfg.runs()``; a kind is ``<mixer>_<ffn>``), leaves stacked ``[n,
...]``.  Every layer has ``ln1_w, ln2_w, g_w [H, nh], o_w [nh d_v, H]``;
a KDA layer ``qkv_w [H, 3 nh d], conv_w [3 nh d, 4], f_w [H, nh d],
dt_bias [nh d], A_log [nh], beta_w [H, nh], o_norm_w [nh d]``; a latent
layer ``q_w [H, nh (d_n + d_r)], kv_a_w [H, r_kv + d_r], kv_a_ln_w,
uk_w [nh, r_kv, d_n], uv_w [nh, r_kv, d_v]``; the two kinds of ``ffn``
as ``models/glm_moe_lite.py``'s (``e_gate`` .. ``[Eh, H, Fe]``).

Per-sequence state beside the paged latent: ``ssm [Lk, B, nh, d, d]``
float32 and ``conv [Lk, B, 3 x 3 nh d]`` in the served dtype (the last
three inputs of the conv, one after the other, as ONE row: an axis of 3
before the channels would be padded to 4 in every program and the array
copied into that layout and back), one row a
decode slot (``B``) of each KDA layer (``Lk``); the pool ``[La, NB, BS,
640]`` has a row a LATENT layer (``La``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .glm_moe_lite import _embed, _head, _scan_runs
from .granite_hybrid import _row, _set_row

__all__ = ["LingLinearConfig", "PRESETS", "build_chunk_fill", "build_step",
           "init_ling_linear_params", "init_slot_state", "kernel_tiers",
           "ling_3_0_flash", "ling_linear_tiny"]

#: the zoo's presets (``serving/http.py --model``)
PRESETS = ("ling_linear_tiny", "ling_3_0_flash")

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class LingLinearConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144         # the dense layers' width
    moe_intermediate_size: int = 768      # one expert's width
    moe_shared_expert_intermediate_size: int = 768
    num_hidden_layers: int = 42
    first_k_dense_replace: int = 2
    layer_group_size: int = 6             # the last of each attends
    num_heads: int = 32
    head_dim: int = 128                   # a KDA head's keys and values
    short_conv_kernel_size: int = 4
    kda_safe_gate: bool = True
    kda_lower_bound: float = -5.0
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    num_experts: int = 512                # the router's width
    # the experts THIS rank holds: [expert_offset, expert_offset + held)
    experts_held: Optional[int] = None    # None: all of them
    expert_offset: int = 0
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    # a clamp inside the SwiGLU of the published model's LAST layers
    # (``expert_swiglu_limit_list``, ``share_expert_swiglu_limit_list``:
    # 0 = off): its form is not public, so a non-zero entry is refused
    swiglu_limits: Tuple[float, ...] = ()
    rope_theta: float = 6e6
    rope_interleave: bool = True
    rms_norm_eps: float = 1e-6
    latent_norm_eps: float = 1e-6
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace lies outside the "
                             f"{self.num_hidden_layers} layers")
        held = self.num_experts if self.experts_held is None \
            else self.experts_held
        object.__setattr__(self, "experts_held", held)
        if not 0 < held <= self.num_experts - self.expert_offset:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset + held})"
                f" are not among the router's {self.num_experts}")
        if self.num_experts % self.n_group \
                or not 0 < self.topk_group <= self.n_group:
            raise ValueError("n_group must divide num_experts and "
                             "topk_group lie in [1, n_group]")
        if self.q_lora_rank is not None:
            raise NotImplementedError(
                "a low-rank query step (q_lora_rank) is not among this "
                "family's leaves: the published model has none")
        if any(self.swiglu_limits):
            raise NotImplementedError(
                "a non-zero SwiGLU limit (expert_swiglu_limit_list / "
                "share_expert_swiglu_limit_list) clamps inside the expert "
                "in a form that is not public: not guessed at")
        object.__setattr__(self, "swiglu_limits", tuple(self.swiglu_limits))

    # what the serving engine reads of any model's configuration
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def layer_types(self) -> Tuple[str, ...]:
        g = self.layer_group_size
        return tuple("attention" if (i + 1) % g == 0 else "kda"
                     for i in range(self.num_hidden_layers))

    @property
    def num_attention_layers(self) -> int:
        return self.layer_types.count("attention")

    @property
    def num_state_layers(self) -> int:
        return self.layer_types.count("kda")

    @property
    def num_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_width(self) -> int:
        """A row of the latent pool (``ops.mla.MlaSpec.pool_width``)."""
        return -(-self.latent_width // 128) * 128

    # this family's own
    @property
    def kda_width(self) -> int:
        return self.num_heads * self.head_dim

    def kinds(self) -> Tuple[str, ...]:
        """Every layer's ``<mixer>_<ffn>``."""
        return tuple(f"{m}_" + ("dense" if i < self.first_k_dense_replace
                                else "expert")
                     for i, m in enumerate(self.layer_types))

    def runs(self) -> List[Tuple[str, int, int]]:
        """``(kind, layers, first)``: the maximal stretches of one kind,
        ``first`` the stretch's first layer."""
        out: List[Tuple[str, int, int]] = []
        for i, kind in enumerate(self.kinds()):
            if out and out[-1][0] == kind:
                out[-1] = (kind, out[-1][1] + 1, out[-1][2])
            else:
                out.append((kind, 1, i))
        return out

    def rows(self) -> Tuple[int, ...]:
        """Layer ``i``'s index among the layers of ITS mixer: its row of
        the state arrays, or of the latent pool."""
        seen = {"kda": 0, "attention": 0}
        out = []
        for m in self.layer_types:
            out.append(seen[m])
            seen[m] += 1
        return tuple(out)


def ling_linear_tiny(**kw) -> LingLinearConfig:
    """1 dense + 3 expert layers at toy widths: KDA, KDA, latent, KDA."""
    for k, v in dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32,
            moe_shared_expert_intermediate_size=32, num_hidden_layers=4,
            first_k_dense_replace=1, layer_group_size=3, num_heads=4,
            head_dim=16, kv_lora_rank=16, qk_nope_head_dim=24,
            qk_rope_head_dim=8, v_head_dim=16, num_experts=16,
            num_experts_per_tok=2, n_group=4, topk_group=2,
            max_position_embeddings=512).items():
        kw.setdefault(k, v)
    return LingLinearConfig(**kw)


def ling_3_0_flash(**kw) -> LingLinearConfig:
    """inclusionAI/Ling-3.0-flash (~124B, 5.5B active): the defaults.
    The published model clamps the SwiGLU of its last 8 layers
    (``swiglu_limits``): cut it to at most 34 layers to build it."""
    kw.setdefault("dtype", "bfloat16")
    return LingLinearConfig(**kw)


# ---------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------
def layer_shapes(cfg: LingLinearConfig, kind: str) -> Dict[str, tuple]:
    H, nh, W = cfg.hidden_size, cfg.num_heads, cfg.kda_width
    mixer, ffn = kind.split("_")
    out = {"ln1_w": (H,), "ln2_w": (H,), "g_w": (H, nh)}
    if mixer == "kda":
        out.update(qkv_w=(H, 3 * W),
                   conv_w=(3 * W, cfg.short_conv_kernel_size),
                   f_w=(H, W), dt_bias=(W,), A_log=(nh,), beta_w=(H, nh),
                   o_norm_w=(W,), o_w=(W, H))
    else:
        rkv, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                           cfg.qk_rope_head_dim, cfg.v_head_dim)
        out.update(q_w=(H, nh * (dn + dr)), kv_a_w=(H, rkv + dr),
                   kv_a_ln_w=(rkv,), uk_w=(nh, rkv, dn),
                   uv_w=(nh, rkv, dv), o_w=(nh * dv, H))
    if ffn == "dense":
        F = cfg.intermediate_size
        out.update(gate_w=(H, F), up_w=(H, F), down_w=(F, H))
    else:
        E, Eh, Fe = cfg.num_experts, cfg.experts_held, \
            cfg.moe_intermediate_size
        Fs = cfg.moe_shared_expert_intermediate_size \
            * cfg.num_shared_experts
        out.update(router_w=(H, E), router_b=(E,), e_gate=(Eh, H, Fe),
                   e_up=(Eh, H, Fe), e_down=(Eh, Fe, H), s_gate=(H, Fs),
                   s_up=(H, Fs), s_down=(Fs, H))
    return out


_ONES = ("ln1_w", "ln2_w", "lnf_w", "kv_a_ln_w", "o_norm_w")
#: a channel's log decay a token, drawn log-uniformly between these
RATE_MIN, RATE_MAX = 2e-3, 0.3


def draw_leaf(key, name: str, shape, cfg: LingLinearConfig, dtype):
    """One leaf as this module initialises it: matrices N(0, std); norm
    gains one; the router's bias zero; the depthwise conv uniform in
    +-0.5; ``A_log`` uniform in +-0.25; ``dt_bias`` such that a
    channel's log decay at ``x W_f = 0`` (and ``A_log = 0``) is drawn
    log-uniformly from [0.002, 0.3] a token (a channel remembers over 3
    to 500 tokens: a head neither forgets within three tokens nor
    never)."""
    if name in _ONES:
        return jnp.ones(shape, dtype)
    if name == "router_b":
        return jnp.zeros(shape, dtype)
    if name == "conv_w":
        return jax.random.uniform(key, shape, F32, -0.5, 0.5).astype(dtype)
    if name == "A_log":
        return jax.random.uniform(key, shape, F32, -0.25,
                                  0.25).astype(dtype)
    if name == "dt_bias":
        u = jax.random.uniform(key, shape, F32)
        rate = jnp.exp(u * (math.log(RATE_MAX) - math.log(RATE_MIN))
                       + math.log(RATE_MIN)) / abs(cfg.kda_lower_bound)
        return (jnp.log(rate) - jnp.log1p(-rate)).astype(dtype)
    return (jax.random.normal(key, shape, F32)
            * cfg.initializer_range).astype(dtype)


def init_ling_linear_params(cfg: LingLinearConfig, seed: int = 0):
    """The seeded parameter tree (see the module docstring)."""
    dt = jnp.dtype(cfg.dtype)
    key = jax.random.key(seed)

    def layer(kind, i):
        lk = jax.random.fold_in(jax.random.fold_in(key, 1), i)
        return {n: draw_leaf(jax.random.fold_in(lk, j), n, s, cfg, dt)
                for j, (n, s) in enumerate(layer_shapes(cfg, kind).items())}

    runs = []
    for kind, n, first in cfg.runs():
        layers = [layer(kind, first + j) for j in range(n)]
        runs.append({k: jnp.stack([l[k] for l in layers])
                     for k in layers[0]})
    ok = jax.random.fold_in(key, 2)
    H, V = cfg.hidden_size, cfg.vocab_size
    return {"wte": draw_leaf(jax.random.fold_in(ok, 0), "wte", (V, H), cfg,
                             dt),
            "head": draw_leaf(jax.random.fold_in(ok, 1), "head", (H, V),
                              cfg, dt),
            "lnf_w": jnp.ones((H,), dt), "runs": tuple(runs)}


def init_slot_state(cfg: LingLinearConfig, max_batch: int):
    """``(ssm, conv)`` zeros for ``max_batch`` decode slots."""
    Lk, nh, d = cfg.num_state_layers, cfg.num_heads, cfg.head_dim
    return (jnp.zeros((Lk, max_batch, nh, d, d), F32),
            jnp.zeros((Lk, max_batch, (cfg.short_conv_kernel_size - 1)
                       * 3 * cfg.kda_width), jnp.dtype(cfg.dtype)))


def kernel_tiers(cfg: LingLinearConfig, state_shape):
    """``{"kda_state_update": {"tier", "reason"}}``: the tier the decode
    step's state update runs on, from the function its dispatch reads."""
    from ..ops.kda import kda_state_update_tier
    tier, why = kda_state_update_tier(state_shape)
    return {"kda_state_update": {"tier": tier, "reason": why}}


# ---------------------------------------------------------------------
# the layer equations
# ---------------------------------------------------------------------
def mla_spec(cfg: LingLinearConfig, block_size: int):
    from ..ops.mla import MlaSpec
    return MlaSpec(
        hidden=cfg.hidden_size, num_heads=cfg.num_heads,
        q_lora_rank=None, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rope_theta=cfg.rope_theta, rope_interleave=cfg.rope_interleave,
        latent_norm_eps=cfg.latent_norm_eps, block_size=block_size)


def _make_ffn_half(cfg: LingLinearConfig):
    """``ffn_half(x [T, H], lp, ffn, count_mask=None) -> (x, counts,
    fill)``: the second half of a layer.  Of an expert layer, over the
    pairs that chose a HELD expert: ``counts`` its
    ``parallel.moe.expert_counts`` (pairs, distinct experts hit, the
    most on one) over the tokens ``count_mask`` names, ``fill`` the
    int32 pair ``[pairs, rows its experts' matmuls multiplied for
    them]`` over all of ``x`` (zeros for a dense layer).  An expert
    layer's ``lp`` holds its run's three expert banks STACKED and
    ``"bank"``, its place in them (``glm_moe_lite._scan_runs``)."""
    from ..ops.mla import rms_norm
    from ..parallel import moe
    Eh, off = cfg.experts_held, cfg.expert_offset

    def swiglu(y, g, u, d):
        return ((jax.nn.silu(y @ g) * (y @ u)) @ d).astype(F32)

    def ffn_half(x, lp, ffn, count_mask=None):
        y32 = rms_norm(x, lp["ln2_w"], cfg.rms_norm_eps)
        y = y32.astype(lp["ln2_w"].dtype)
        if ffn == "dense":
            return x + swiglu(y, lp["gate_w"], lp["up_w"], lp["down_w"]), \
                jnp.zeros((3,), jnp.int32), jnp.zeros((2,), jnp.int32)
        # the router reads the float32 rows at full precision, as
        # glm_moe_lite's: a choice among 512 scores does not survive
        # rounding its inputs to bfloat16
        logits = jnp.matmul(y32, lp["router_w"].astype(F32),
                            precision=jax.lax.Precision.HIGHEST)
        w, idx = moe.route_sigmoid(
            logits, lp["router_b"], cfg.num_experts_per_tok,
            n_group=cfg.n_group, topk_group=cfg.topk_group,
            normalize=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor)
        out, rows = moe.moe_swiglu_ffn_routed(
            y, w, idx, lp["e_gate"], lp["e_up"], lp["e_down"],
            layer=lp["bank"], expert_offset=off,
            router_experts=cfg.num_experts)
        out = out.astype(F32) \
            + swiglu(y, lp["s_gate"], lp["s_up"], lp["s_down"])
        local, held = moe.held_choices(idx, Eh, off)
        return x + out, moe.expert_counts(local, Eh, count_mask), \
            jnp.stack([jnp.sum(held, dtype=jnp.int32), rows])

    return ffn_half


def _make_kda_mix(cfg: LingLinearConfig):
    """``mix(y [B, T, H], lp, S [B, nh, d, d], tail [B, 3 x 3 nh d],
    valid) -> (out [B, T, H] float32, S, tail)``: the KDA mixer of
    normed rows through the chunked scan, positions at or past ``valid``
    leaving state and tail alone.  With ``row`` (the decode step: ``T ==
    1``) ``S`` is the WHOLE state array ``[Lk, B, nh, d, d]`` and comes
    back whole, its row ``row`` stepped once in place
    (``ops.kda.kda_state_update_row``)."""
    # through the module, so that a planted fault (the benchmark's
    # calibration) can stand in for an op before a program is traced
    from ..ops import kda
    from ..ops.mla import gate_heads
    from ..ops.ssm import causal_conv_rows, causal_conv_step
    nh, d, W = cfg.num_heads, cfg.head_dim, cfg.kda_width
    eps = cfg.rms_norm_eps

    def l2norm(t):
        return t * jax.lax.rsqrt(
            jnp.sum(jnp.square(t), -1, keepdims=True) + 1e-6)

    def mix(y, lp, S, tail, valid=None, row=None):
        B, T, _ = y.shape
        with jax.named_scope("kda_conv"):
            if row is not None:
                qkv, tail = causal_conv_step((y @ lp["qkv_w"])[:, 0], tail,
                                             lp["conv_w"], None)
            else:
                qkv, tail = causal_conv_rows(
                    y @ lp["qkv_w"], tail.reshape(B, -1, 3 * W),
                    lp["conv_w"], None, valid=valid)
                tail = tail.reshape(B, -1)
            qkv = jax.nn.silu(qkv.astype(F32)).reshape(B, T, 3, nh, d)
        q = l2norm(qkv[:, :, 0]) * d ** -0.5
        k, v = l2norm(qkv[:, :, 1]), qkv[:, :, 2]
        f = (y @ lp["f_w"]).astype(F32) + lp["dt_bias"].astype(F32)
        A = jnp.exp(lp["A_log"].astype(F32))[:, None]
        f = f.reshape(B, T, nh, d)
        log_a = cfg.kda_lower_bound * jax.nn.sigmoid(A * f) \
            if cfg.kda_safe_gate else -A * jax.nn.softplus(f)
        beta = jax.nn.sigmoid((y @ lp["beta_w"]).astype(F32))
        if row is not None:
            o, S = kda.kda_state_update_row(
                q[:, 0], k[:, 0], v[:, 0], log_a[:, 0], beta[:, 0], S, row)
            o = o[:, None]
        else:
            if valid is not None:
                real = (jnp.arange(T) < valid)[None, :, None]
                log_a = jnp.where(real[..., None], log_a, 0.0)
                beta = jnp.where(real, beta, 0.0)
            o, S = kda.kda_chunk_scan(q, k, v, log_a, beta, S)
        # the norm a head, then the gate a head
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                              + eps)
        o = (o.reshape(B, T, W) * lp["o_norm_w"].astype(F32)).astype(
            y.dtype)
        return (gate_heads(o, y @ lp["g_w"]) @ lp["o_w"]).astype(F32), \
            S, tail

    return mix


def _closures(cfg: LingLinearConfig, block_size: int):
    return (mla_spec(cfg, block_size), _make_ffn_half(cfg),
            _make_kda_mix(cfg), jnp.asarray(cfg.rows(), jnp.int32))


def build_step(cfg: LingLinearConfig, block_size: int):
    """The decode program: ``step(params, pool, ssm, conv, bt, lengths,
    tokens) -> (pool, ssm, conv, logits [B, V], counts [4], greedy
    [B])``.  One ``lax.scan`` a run of ``cfg.runs()``; the latent pool
    ``[La, NB, BS, W]`` rides through them WHOLE as one pool of ``La x
    NB`` pages (``layers_as_one_pool``), the state arrays whole beside
    it, each updated in place, row by layer.  ``counts`` sums over the
    expert layers ``[pairs on held experts, distinct held experts hit,
    most pairs on one]`` of the rows that run a request (``lengths >
    0``), and the rows the experts' matmuls multiplied (for all ``B``
    rows: an idle slot's row is routed too); ``greedy`` is every row's
    first choice."""
    from ..ops import mla
    from ..ops.paged_kv import layer_pages, layers_as_one_pool
    spec, ffn_half, kda_mix, rows = _closures(cfg, block_size)

    def step(params, pool, ssm, conv, bt, lengths, tokens):
        x = _embed(params, tokens)
        live = lengths > 0
        NB = pool.shape[1]

        def layer(carry, lp, kind, i):
            x, pc, ssm, conv, cnt = carry
            mixer, ffn = kind.split("_")
            row = rows[i]
            y = mla.rms_norm(x, lp["ln1_w"], cfg.rms_norm_eps).astype(
                pc.dtype)
            if mixer == "kda":
                d, ssm, tl = kda_mix(y[:, None], lp, ssm, _row(conv, row),
                                     row=row)
                x, conv = x + d[:, 0], _set_row(conv, tl, row)
            else:
                pages = layer_pages(bt, row, NB)
                q_n, q_r, latent = mla.project(y, lp, lengths, spec)
                pc = mla.latent_append(pc, latent, pages, lengths,
                                       block_size)
                o = mla.paged_latent_attention(
                    mla.absorb_query(q_n, q_r, lp["uk_w"], spec.pool_width),
                    pc, pages, lengths + 1, spec.kv_lora_rank, spec.scale)
                o = mla.gate_heads(mla.lift_output(o, lp["uv_w"]),
                                   y @ lp["g_w"])
                x = x + (o @ lp["o_w"]).astype(F32)
            x, c, f = ffn_half(x, lp, ffn, count_mask=live)
            return x, pc, ssm, conv, cnt + jnp.concatenate([c, f[1:]])

        x, pc, ssm, conv, cnt = _scan_runs(
            cfg, params, layer,
            (x, layers_as_one_pool(pool), ssm, conv,
             jnp.zeros((4,), jnp.int32)))
        logits = _head(cfg, params, x)
        return (layers_as_one_pool(pc, like=pool), ssm, conv, logits, cnt,
                jnp.argmax(logits, axis=-1).astype(jnp.int32))

    return step


def build_chunk_fill(cfg: LingLinearConfig, block_size: int, Ts: int):
    """The chunk fill of ONE sequence: ``fill(params, pool, ssm, conv,
    bt_row, start, toks [Ts], slot, moe_rows, valid=None) -> (pool, ssm,
    conv, logits [1, V], moe_rows)``.  ``start == 0`` begins the slot's
    state at zero (a reused slot starts clean); a later chunk continues
    it.  With ``valid`` only the first ``valid`` tokens are real: the
    padded rows write no page, leave the state and the conv tail as the
    last valid token left them, and the logits come from row ``valid -
    1``.  ``moe_rows`` (int32 ``[2]``) rides through a prompt's chunks:
    each adds the token-expert pairs its expert layers routed to HELD
    experts (padded rows are routed too) and the rows their matmuls
    multiplied for them."""
    from ..ops import mla
    from ..ops.paged_kv import layer_pages, layers_as_one_pool
    spec, ffn_half, kda_mix, rows = _closures(cfg, block_size)
    BS = block_size

    def slot_row(a, row, slot):                   # [Lk, B, ...] -> [1, ...]
        return jax.lax.dynamic_slice(
            a, (row, slot) + (0,) * (a.ndim - 2),
            (1, 1) + a.shape[2:])[0]

    def set_slot_row(a, val, row, slot):
        return jax.lax.dynamic_update_slice(
            a, val[None].astype(a.dtype),
            (row, slot) + (0,) * (a.ndim - 2))

    def fill(params, pool, ssm, conv, bt_row, start, toks, slot, moe_rows,
             valid=None):
        pos = start + jnp.arange(Ts)
        real = jnp.arange(Ts) < (Ts if valid is None else valid)
        last = start + (Ts if valid is None else valid) - 1
        x = _embed(params, toks)                             # [Ts, H]
        blk = jnp.take(jnp.maximum(bt_row, 0), pos // BS)
        off = pos % BS
        La, NB = pool.shape[:2]
        fresh = start == 0

        def layer(carry, lp, kind, i):
            x, pc, ssm, conv, cnt = carry
            mixer, ffn = kind.split("_")
            row = rows[i]
            y = mla.rms_norm(x, lp["ln1_w"], cfg.rms_norm_eps).astype(
                pc.dtype)
            if mixer == "kda":
                S, tl = slot_row(ssm, row, slot), slot_row(conv, row, slot)
                S = jnp.where(fresh, jnp.zeros_like(S), S)
                tl = jnp.where(fresh, jnp.zeros_like(tl), tl)
                d, S, tl = kda_mix(y[None], lp, S, tl, valid=valid)
                x = x + d[0]
                ssm = set_slot_row(ssm, S, row, slot)
                conv = set_slot_row(conv, tl, row, slot)
            else:
                q_n, q_r, latent = mla.project(y, lp, pos, spec)
                # a padded row lands past the last layer's last page
                pc = pc.at[jnp.where(real, blk + row * NB, La * NB),
                           off].set(latent, mode="drop")
                o = mla.paged_expanded_attention(
                    q_n, q_r, pc, layer_pages(bt_row, row, NB), pos, last,
                    lp["uk_w"], lp["uv_w"], spec.scale)
                x = x + (mla.gate_heads(o, y @ lp["g_w"])
                         @ lp["o_w"]).astype(F32)
            x, _, c = ffn_half(x, lp, ffn)
            return x, pc, ssm, conv, cnt + c

        x, pc, ssm, conv, cnt = _scan_runs(
            cfg, params, layer,
            (x, layers_as_one_pool(pool), ssm, conv,
             jnp.zeros((2,), jnp.int32)))
        row = x[-1:] if valid is None \
            else jax.lax.dynamic_slice_in_dim(x, valid - 1, 1)
        return (layers_as_one_pool(pc, like=pool), ssm, conv,
                _head(cfg, params, row), moe_rows + cnt)

    return fill


# the name the zoo's CLI finds the parameters under
# (``serving/http.py:build_frontend``)
init_params = init_ling_linear_params
