"""GLM-4.7-Flash style decoder (``model_type`` ``glm4_moe_lite``; the
DeepSeek-V3 block at GLM's sizes): multi-head latent attention in every
layer, a leading run of dense SwiGLU layers, then layers of sigmoid-
routed experts beside an always-on shared expert.

Serving only: this module offers the configuration, the seeded
parameter tree and the two programs the continuous-batching engine
compiles for such a model (``inference/serving.py`` reads
``cfg.kv_lora_rank`` and builds these instead of the Llama-family
ones).  There is no train step, and the multi-token-prediction layer
(``num_nextn_predict_layers``) is neither held nor run: the model's
logits do not depend on it.

The equations (``rms`` an RMS norm with a gain)::

    h = wte[ids];  h = h + attn(rms(h));  h = h + ffn(rms(h))
    logits = rms(h) @ head                         (untied, no bias)

``attn`` is ``ops/mla.py``'s: a decode step runs the ABSORBED form over
the paged latent, a chunk fill the EXPANDED form over it (the cached
prefix and the fresh chunk alike: at 512 queries a cached token costs
19.7 MFLOP expanded — 9.2 to decompress it, 10.5 to attend — against
22.3 absorbed, at 2048 queries 51 against 89).  ``ffn`` of the first
``first_k_dense_replace`` layers is a SwiGLU of ``intermediate_size``;
of the others ``sum_j g_j E_j(x) + shared(x)`` with
``parallel/moe.py:route_sigmoid``'s gate (sigmoid scores in float32,
the choice by ``score + bias``, the weights from the scores alone,
renormalised, times ``routed_scaling_factor``), every expert held.
``moe_swiglu_ffn_routed`` has one form for a decode step's few rows
and a chunk fill's many: the token-expert pairs are sorted by expert
and multiplied once each by a grouped matmul that reads an expert's
weights once, in the stacked leaves, and only if a row chose it
(``ops/pallas/moe_grouped_matmul.py``; a step of 64 rows hits 86% of
the 64 experts, PERF.md PR 37).

Parameter tree: ``{"wte" [V, H], "head" [H, V], "lnf_w" [H], "runs":
(run, ...)}``, one ``run`` the layers of one kind (``cfg.runs()``:
dense, then expert), leaves stacked ``[n, ...]`` so that one
``lax.scan`` walks it.  Every layer has ``ln1_w, ln2_w, q_a_w [H, r_q],
q_a_ln_w, q_b_w [r_q, nh (d_n + d_r)], kv_a_w [H, r_kv + d_r],
kv_a_ln_w, uk_w [nh, r_kv, d_n], uv_w [nh, r_kv, d_v], o_w [nh d_v,
H]``; a dense layer adds ``gate_w, up_w [H, F], down_w [F, H]``; an
expert layer ``router_w [H, E], router_b [E], e_gate, e_up [E, H, Fe],
e_down [E, Fe, H], s_gate, s_up [H, Fs], s_down [Fs, H]``.

The cache: ONE pool ``[L, NB, BS, W]`` (no value pool), a token's
normed latent and rotated shared key, a layer, in whole lanes of 128
(``pool_width``: 640 for the 576 of GLM-4.7-Flash).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

__all__ = ["GlmMoeLiteConfig", "PRESETS", "build_chunk_fill",
           "build_latent_chunk_fill", "build_latent_step", "build_step", "glm_4_7_flash", "glm_moe_lite_tiny",
           "init_glm_moe_lite_params"]

#: the zoo's presets (``serving/http.py --model``)
PRESETS = ("glm_moe_lite_tiny", "glm_4_7_flash")


@dataclasses.dataclass(frozen=True)
class GlmMoeLiteConfig:
    vocab_size: int = 154880
    hidden_size: int = 2048
    intermediate_size: int = 10240        # the dense layers' width
    moe_intermediate_size: int = 1536     # one expert's width
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    num_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.8
    rope_theta: float = 1e6
    rope_interleave: bool = True
    rms_norm_eps: float = 1e-5
    latent_norm_eps: float = 1e-6
    max_position_embeddings: int = 202752
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace lies outside the "
                             f"{self.num_hidden_layers} layers")
        if self.n_routed_experts % self.n_group \
                or not 0 < self.topk_group <= self.n_group:
            raise ValueError("n_group must divide n_routed_experts and "
                             "topk_group lie in [1, n_group]")
        if self.n_group > 1 and self.n_routed_experts // self.n_group < 2:
            raise ValueError("a group is ranked by its two best experts")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")

    # what the serving engine reads of any model's configuration
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def num_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_width(self) -> int:
        """A row of the latent pool (``ops.mla.MlaSpec.pool_width``)."""
        return -(-self.latent_width // 128) * 128

    def runs(self) -> List[Tuple[str, int, int]]:
        """``(kind, layers, first)``: the dense layers, then the expert
        layers, ``first`` the run's first layer (its row of the pool)."""
        k = self.first_k_dense_replace
        return [r for r in (("dense", k, 0),
                            ("expert", self.num_expert_layers, k)) if r[1]]


def glm_moe_lite_tiny(**kw) -> GlmMoeLiteConfig:
    """1 dense + 2 expert layers at toy widths."""
    for k, v in dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=3, num_heads=4,
            q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=24,
            qk_rope_head_dim=8, v_head_dim=32, n_routed_experts=8,
            num_experts_per_tok=2, max_position_embeddings=512).items():
        kw.setdefault(k, v)
    return GlmMoeLiteConfig(**kw)


def glm_4_7_flash(**kw) -> GlmMoeLiteConfig:
    """zai-org/GLM-4.7-Flash (30B, 3B active): the defaults."""
    kw.setdefault("dtype", "bfloat16")
    return GlmMoeLiteConfig(**kw)


# ---------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------
def layer_shapes(cfg: GlmMoeLiteConfig, kind: str) -> Dict[str, tuple]:
    H, nh = cfg.hidden_size, cfg.num_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                  cfg.v_head_dim)
    out = {"ln1_w": (H,), "ln2_w": (H,), "q_a_w": (H, rq),
           "q_a_ln_w": (rq,), "q_b_w": (rq, nh * (dn + dr)),
           "kv_a_w": (H, rkv + dr), "kv_a_ln_w": (rkv,),
           "uk_w": (nh, rkv, dn), "uv_w": (nh, rkv, dv),
           "o_w": (nh * dv, H)}
    if kind == "dense":
        F = cfg.intermediate_size
        out.update(gate_w=(H, F), up_w=(H, F), down_w=(F, H))
    else:
        E, Fe = cfg.n_routed_experts, cfg.moe_intermediate_size
        Fs = Fe * cfg.n_shared_experts
        out.update(router_w=(H, E), router_b=(E,), e_gate=(E, H, Fe),
                   e_up=(E, H, Fe), e_down=(E, Fe, H), s_gate=(H, Fs),
                   s_up=(H, Fs), s_down=(Fs, H))
    return out


def init_glm_moe_lite_params(cfg: GlmMoeLiteConfig, seed: int = 0):
    """The seeded parameter tree (see the module docstring): matrices
    N(0, ``initializer_range``), norm gains one, the router's bias zero
    (as the public implementation initialises it)."""
    dt = jnp.dtype(cfg.dtype)
    key = jax.random.key(seed)
    std = cfg.initializer_range

    def leaf(k, name, shape):
        if name.endswith("ln_w") or name in ("ln1_w", "ln2_w", "lnf_w"):
            return jnp.ones(shape, dt)
        if name == "router_b":
            return jnp.zeros(shape, dt)
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    def layer(kind, i):
        lk = jax.random.fold_in(jax.random.fold_in(key, 1), i)
        return {n: leaf(jax.random.fold_in(lk, j), n, s)
                for j, (n, s) in enumerate(layer_shapes(cfg, kind).items())}

    runs = []
    for kind, n, first in cfg.runs():
        layers = [layer(kind, first + j) for j in range(n)]
        runs.append({k: jnp.stack([l[k] for l in layers])
                     for k in layers[0]})
    ok = jax.random.fold_in(key, 2)
    H, V = cfg.hidden_size, cfg.vocab_size
    return {"wte": leaf(jax.random.fold_in(ok, 0), "wte", (V, H)),
            "head": leaf(jax.random.fold_in(ok, 1), "head", (H, V)),
            "lnf_w": jnp.ones((H,), dt), "runs": tuple(runs)}


# ---------------------------------------------------------------------
# the layer equations
# ---------------------------------------------------------------------
def mla_spec(cfg: GlmMoeLiteConfig, block_size: int):
    from ..ops.mla import MlaSpec
    return MlaSpec(
        hidden=cfg.hidden_size, num_heads=cfg.num_heads,
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rope_theta=cfg.rope_theta, rope_interleave=cfg.rope_interleave,
        latent_norm_eps=cfg.latent_norm_eps, block_size=block_size)


_BANKS = ("e_gate", "e_up", "e_down")


def _make_ffn_half(cfg: GlmMoeLiteConfig):
    """``ffn_half(x [T, H], lp, kind, count_mask=None) -> (x, counts,
    rows)``: the second half of a layer; ``counts`` an expert layer's
    ``parallel.moe.expert_counts`` over the tokens ``count_mask`` names,
    ``rows`` the rows its experts' matmuls multiplied for its ``T k``
    pairs (zeros for a dense layer).  An expert layer's ``lp`` holds its
    run's three expert banks STACKED and ``"bank"``, its place in them
    (``_scan_runs``)."""
    from ..ops.mla import rms_norm
    from ..parallel import moe
    E = cfg.n_routed_experts

    def swiglu(y, g, u, d):
        return ((jax.nn.silu(y @ g) * (y @ u)) @ d).astype(jnp.float32)

    def ffn_half(x, lp, kind, count_mask=None):
        y32 = rms_norm(x, lp["ln2_w"], cfg.rms_norm_eps)
        y = y32.astype(lp["ln2_w"].dtype)
        if kind == "dense":
            return x + swiglu(y, lp["gate_w"], lp["up_w"], lp["down_w"]), \
                jnp.zeros((3,), jnp.int32), jnp.int32(0)
        # the router reads the float32 rows, at full precision: a choice
        # among 64 scores a hundredth apart does not survive rounding
        # its 2048 inputs to bfloat16 (a few tokens in a hundred would
        # choose another expert than the reference's)
        logits = jnp.matmul(y32, lp["router_w"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        # through the module, so that a planted fault (the benchmark's
        # calibration) can stand in for the gate before a program is traced
        w, idx = moe.route_sigmoid(
            logits, lp["router_b"], cfg.num_experts_per_tok,
            n_group=cfg.n_group, topk_group=cfg.topk_group,
            normalize=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor)
        out, rows = moe.moe_swiglu_ffn_routed(
            y, w, idx, lp["e_gate"], lp["e_up"], lp["e_down"],
            layer=lp["bank"])
        out = out.astype(jnp.float32) \
            + swiglu(y, lp["s_gate"], lp["s_up"], lp["s_down"])
        return x + out, moe.expert_counts(idx, E, count_mask), rows

    return ffn_half


def _embed(params, tokens):
    """The residual stream: float32 whatever the served dtype (it is a
    few rows wide, and every layer's norm reads it again: rounded to
    bfloat16 after each layer it moves the routers' near-ties)."""
    return jnp.take(params["wte"], tokens, axis=0).astype(jnp.float32)


def _head(cfg: GlmMoeLiteConfig, params, x):
    """``[n, H]`` -> float32 logits."""
    from ..ops.mla import rms_norm
    y = rms_norm(x, params["lnf_w"], cfg.rms_norm_eps)
    return jnp.einsum("bh,hv->bv", y.astype(params["head"].dtype),
                      params["head"], preferred_element_type=jnp.float32)


def _scan_runs(cfg: GlmMoeLiteConfig, params, layer, carry):
    """One ``lax.scan`` a run of ``cfg.runs()``: ``layer(carry, lp, kind,
    i) -> carry`` with ``i`` the layer's index among all layers.  The
    expert banks are not scanned: ``lp`` holds the run's stacks and
    ``lp["bank"]``, the layer's place in them, so that the expert layer
    reads its bank where it lies (``moe_swiglu_ffn_routed``: a scanned
    bank is a copy of it, 1.2 GB a layer at GLM-4.7-Flash's sizes)."""
    for (kind, n, first), run in zip(cfg.runs(), params["runs"]):
        banks = {k: run[k] for k in _BANKS if k in run}
        rest = {k: v for k, v in run.items() if k not in banks}

        def body(carry, inp, kind=kind, first=first, banks=banks):
            lp, j = inp
            return layer(carry, dict(lp, bank=j, **banks), kind,
                         first + j), None

        carry, _ = jax.lax.scan(body, carry,
                                (rest, jnp.arange(n, dtype=jnp.int32)))
    return carry


def build_latent_step(cfg: GlmMoeLiteConfig, block_size: int):
    """The decode program: ``step(params, pool, bt, lengths, tokens) ->
    (pool, logits [B, V], counts [4], greedy [B])``.  The latent pool
    ``[L, NB, BS, W]`` rides through the layer scans WHOLE, in their
    carry, as one pool of ``L x NB`` pages (``layers_as_one_pool``), so
    a layer's append lands in place.  ``counts`` sums over the expert
    layers ``[pairs, distinct experts hit, most pairs on one expert]``
    of the rows that run a request (``lengths > 0``), and the rows the
    experts' matmuls multiplied (for all ``B`` rows: an idle slot's row
    is routed too); ``greedy`` is every row's first choice, so that the
    engine need not fetch the logits to pick."""
    from ..ops import mla
    from ..ops.paged_kv import layer_pages, layers_as_one_pool
    spec = mla_spec(cfg, block_size)
    ffn_half = _make_ffn_half(cfg)

    def step(params, pool, bt, lengths, tokens):
        x = _embed(params, tokens)
        live = lengths > 0
        NB = pool.shape[1]

        def layer(carry, lp, kind, i):
            x, pc, cnt = carry
            pages = layer_pages(bt, i, NB)
            y = mla.rms_norm(x, lp["ln1_w"], cfg.rms_norm_eps).astype(
                pc.dtype)
            q_n, q_r, latent = mla.project(y, lp, lengths, spec)
            pc = mla.latent_append(pc, latent, pages, lengths, block_size)
            o = mla.paged_latent_attention(
                mla.absorb_query(q_n, q_r, lp["uk_w"], spec.pool_width),
                pc, pages,
                lengths + 1, spec.kv_lora_rank, spec.scale)
            x = x + (mla.lift_output(o, lp["uv_w"])
                     @ lp["o_w"]).astype(jnp.float32)
            x, c, rows = ffn_half(x, lp, kind, count_mask=live)
            return x, pc, cnt + jnp.concatenate([c, rows[None]])

        x, pc, cnt = _scan_runs(
            cfg, params, layer,
            (x, layers_as_one_pool(pool), jnp.zeros((4,), jnp.int32)))
        logits = _head(cfg, params, x)
        return (layers_as_one_pool(pc, like=pool), logits, cnt,
                jnp.argmax(logits, axis=-1).astype(jnp.int32))

    return step


def build_latent_chunk_fill(cfg: GlmMoeLiteConfig, block_size: int,
                            Ts: int):
    """The chunk fill of ONE sequence: ``fill(params, pool, bt_row,
    start, toks [Ts], moe_rows, valid=None) -> (pool, logits [1, V],
    moe_rows)``: ``Ts`` prompt tokens from position ``start`` on, their
    latents written into the row's pages, each attending over everything
    cached before it and the chunk up to itself
    (``ops.mla.paged_expanded_attention``: the walk ends at the chunk's
    last real token, whatever the table's width).  With ``valid`` only
    the first ``valid`` tokens are real: the padded rows write no page,
    and the logits come from row ``valid - 1``.  ``moe_rows`` (int32
    ``[2]``) rides through a prompt's chunks: each adds the token-expert
    pairs its expert layers routed (padded rows are routed too) and the
    rows their matmuls multiplied for them."""
    from ..ops import mla
    from ..ops.paged_kv import layer_pages, layers_as_one_pool
    spec = mla_spec(cfg, block_size)
    ffn_half = _make_ffn_half(cfg)
    BS = block_size
    pairs = Ts * cfg.num_experts_per_tok * cfg.num_expert_layers

    def fill(params, pool, bt_row, start, toks, moe_rows, valid=None):
        pos = start + jnp.arange(Ts)
        real = jnp.arange(Ts) < (Ts if valid is None else valid)
        last = start + (Ts if valid is None else valid) - 1
        x = _embed(params, toks)                             # [Ts, H]
        blk = jnp.take(jnp.maximum(bt_row, 0), pos // BS)
        off = pos % BS
        L, NB = pool.shape[:2]

        def layer(carry, lp, kind, i):
            x, pc, rows = carry
            y = mla.rms_norm(x, lp["ln1_w"], cfg.rms_norm_eps).astype(
                pc.dtype)
            q_n, q_r, latent = mla.project(y, lp, pos, spec)
            # a padded row lands past the last layer's last page
            pc = pc.at[jnp.where(real, blk + i * NB, L * NB), off].set(
                latent, mode="drop")
            o = mla.paged_expanded_attention(
                q_n, q_r, pc, layer_pages(bt_row, i, NB), pos, last,
                lp["uk_w"], lp["uv_w"], spec.scale)
            x = x + (o @ lp["o_w"]).astype(jnp.float32)
            x, _, r = ffn_half(x, lp, kind)
            return x, pc, rows + r

        x, pc, rows = _scan_runs(
            cfg, params, layer,
            (x, layers_as_one_pool(pool), jnp.int32(0)))
        row = x[-1:] if valid is None \
            else jax.lax.dynamic_slice_in_dim(x, valid - 1, 1)
        return (layers_as_one_pool(pc, like=pool), _head(cfg, params, row),
                moe_rows + jnp.stack([jnp.int32(pairs), rows]))

    return fill


# the names the serving engine and the zoo's CLI find a family's
# programs and parameters under (``inference/serving.py:_model_module``,
# ``serving/http.py:build_frontend``)
build_step = build_latent_step
build_chunk_fill = build_latent_chunk_fill
init_params = init_glm_moe_lite_params
