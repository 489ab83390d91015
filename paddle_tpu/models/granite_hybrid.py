"""Granite-4.0-H style hybrid decoder: Mamba-2 layers and a few
attention layers, every layer followed by a mixture of experts plus an
always-on shared MLP (``model_type`` ``granitemoehybrid``).

Serving only: this module offers the configuration, the seeded
parameter tree and the two programs the continuous-batching engine
compiles for such a model (``inference/serving.py`` reads
``cfg.layer_types`` and builds these instead of the Llama-family ones).
There is no train step.

The equations (``y = rms(h)`` is an RMS norm with a gain)::

    h0 = embedding_multiplier * wte[ids]
    h  = h + r * mix(rms(h));   h = h + r * (moe(rms(h)) + shared(rms(h)))
    logits = rms(h) @ wte.T / logits_scaling          (tied head)

``mix`` is Mamba-2 (``[z | xBC | dt] = u @ in_w``; ``xBC`` through a
causal depthwise conv of width 4 and SiLU; the selective state-space
recurrence of ``ops/ssm.py`` with a float32 state per head; ``rms(y *
silu(z)) @ out_w``, the gate BEFORE the norm) or grouped-query causal
attention with no positional encoding and a stated softmax scale.
``moe``: the ``k`` largest router logits, a softmax over those, a SwiGLU
per expert.  A rank may hold a contiguous share of the experts
(``experts_held`` from ``expert_offset``): it routes over all of them
and adds only the terms of those it holds, gates unchanged — what the
absent experts would add is left out (expert parallelism's partial
sum).

Parameter tree: ``{"wte" [V, H], "lnf_w" [H], "runs": (run, ...)}``,
one ``run`` a maximal stretch of layers of one kind in
``layer_types``, its leaves stacked ``[n, ...]`` so that one
``lax.scan`` walks it.  Every layer has ``ln1_w, ln2_w, router_w [H,
E], e_gate, e_up [Eh, H, F], e_down [Eh, F, H], s_gate, s_up [H, Fs],
s_down [Fs, H]``; a Mamba layer adds ``in_w, conv_w [C, W], conv_b,
dt_bias, A_log, D, norm_w, out_w``; an attention layer ``q_w, k_w, v_w,
o_w``.

Per-sequence state beside the paged K/V: ``ssm [Lm, B, nh, P, N]``
float32 and ``conv [Lm, B, C, W - 1]`` in the served dtype, one row a
decode slot (``B``) of each Mamba layer (``Lm``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["GraniteHybridConfig", "PRESETS", "build_chunk_fill",
           "build_hybrid_chunk_fill", "build_hybrid_step", "build_step",
           "granite_4_0_h_small", "granite_hybrid_tiny",
           "init_granite_hybrid_params", "init_slot_state",
           "kernel_tiers"]

#: the zoo's presets (``serving/http.py --model``)
PRESETS = ("granite_hybrid_tiny", "granite_4_0_h_small")

#: the published model's layer pattern: ten periods of this
_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 4096
    intermediate_size: int = 768          # one expert's width
    shared_intermediate_size: int = 1536
    layer_types: Tuple[str, ...] = _PERIOD * 4
    num_heads: int = 32
    num_kv_heads: Optional[int] = 8
    num_local_experts: int = 72           # the router's width
    num_experts_per_tok: int = 10
    # the experts THIS rank holds: [expert_offset, expert_offset + held)
    experts_held: Optional[int] = None    # None: all of them
    expert_offset: int = 0
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.0078125
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = set(self.layer_types) - {"mamba", "attention"}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types must name 'mamba' or "
                             f"'attention' layers, got {sorted(bad)}")
        held = self.num_local_experts if self.experts_held is None \
            else self.experts_held
        object.__setattr__(self, "experts_held", held)
        if not 0 < held <= self.num_local_experts - self.expert_offset:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset + held})"
                f" are not among the router's {self.num_local_experts}")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("mamba_n_groups must divide mamba_n_heads")

    # what the serving engine reads of any model's configuration
    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    # this family's own
    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def num_mamba_layers(self) -> int:
        return self.layer_types.count("mamba")

    @property
    def num_attention_layers(self) -> int:
        return self.layer_types.count("attention")

    def runs(self) -> List[Tuple[str, int, int]]:
        """``(kind, layers, first)``: the maximal stretches of one kind,
        ``first`` the stretch's first index among the layers of ITS
        kind (the row of the state or of the K/V pool)."""
        out: List[Tuple[str, int, int]] = []
        seen = {"mamba": 0, "attention": 0}
        for kind in self.layer_types:
            if out and out[-1][0] == kind:
                out[-1] = (kind, out[-1][1] + 1, out[-1][2])
            else:
                out.append((kind, 1, seen[kind]))
            seen[kind] += 1
        return out


def granite_hybrid_tiny(**kw) -> GraniteHybridConfig:
    """2 Mamba + 1 attention + 1 Mamba layers at toy widths."""
    for k, v in dict(
            vocab_size=256, hidden_size=64, intermediate_size=16,
            shared_intermediate_size=32,
            layer_types=("mamba", "mamba", "attention", "mamba"),
            num_heads=4, num_kv_heads=2, num_local_experts=8,
            num_experts_per_tok=3, mamba_n_heads=4, mamba_d_head=32,
            mamba_d_state=16, mamba_chunk_size=8,
            attention_multiplier=0.0625,
            max_position_embeddings=512).items():
        kw.setdefault(k, v)
    return GraniteHybridConfig(**kw)


def granite_4_0_h_small(**kw) -> GraniteHybridConfig:
    """ibm-granite/granite-4.0-h-small (32B, 9B active): the defaults."""
    kw.setdefault("dtype", "bfloat16")
    return GraniteHybridConfig(**kw)


# ---------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------
def layer_shapes(cfg: GraniteHybridConfig, kind: str) -> Dict[str, tuple]:
    H, F, Fs = (cfg.hidden_size, cfg.intermediate_size,
                cfg.shared_intermediate_size)
    E, Eh = cfg.num_local_experts, cfg.experts_held
    out = {"ln1_w": (H,), "ln2_w": (H,), "router_w": (H, E),
           "e_gate": (Eh, H, F), "e_up": (Eh, H, F), "e_down": (Eh, F, H),
           "s_gate": (H, Fs), "s_up": (H, Fs), "s_down": (Fs, H)}
    if kind == "mamba":
        di, C, nh = cfg.d_inner, cfg.conv_dim, cfg.mamba_n_heads
        out.update(in_w=(H, di + C + nh), conv_w=(C, cfg.mamba_d_conv),
                   conv_b=(C,), dt_bias=(nh,), A_log=(nh,), D=(nh,),
                   norm_w=(di,), out_w=(di, H))
    else:
        D, nq, nkv = cfg.head_dim, cfg.num_heads, cfg.kv_heads
        out.update(q_w=(H, nq * D), k_w=(H, nkv * D), v_w=(H, nkv * D),
                   o_w=(nq * D, H))
    return out


_ONES = ("ln1_w", "ln2_w", "norm_w", "D")
DT_MIN, DT_MAX = 1e-3, 1e-1


def draw_leaf(key, name: str, shape, std: float, dtype):
    """One leaf as the family initialises it: matrices N(0, std); norm
    gains and ``D`` one; ``A_log = log(1..nh)``; the depthwise conv
    uniform in +-1/sqrt(width); ``dt_bias`` the inverse softplus of a
    step drawn log-uniformly from [0.001, 0.1] (Mamba-2's, so that the
    heads remember over a few to a few hundred tokens)."""
    if name in _ONES:
        return jnp.ones(shape, dtype)
    if name == "A_log":
        return jnp.log(jnp.arange(1, shape[0] + 1,
                                  dtype=jnp.float32)).astype(dtype)
    if name in ("conv_w", "conv_b"):
        return jax.random.uniform(key, shape, jnp.float32, -0.5,
                                  0.5).astype(dtype)
    if name == "dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(DT_MAX) - math.log(DT_MIN))
                     + math.log(DT_MIN))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_granite_hybrid_params(cfg: GraniteHybridConfig, seed: int = 0):
    """The seeded parameter tree (see the module docstring)."""
    dt = jnp.dtype(cfg.dtype)
    key = jax.random.key(seed)
    std = cfg.initializer_range

    def layer(kind, i):
        lk = jax.random.fold_in(jax.random.fold_in(key, 1), i)
        return {n: draw_leaf(jax.random.fold_in(lk, j), n, s, std, dt)
                for j, (n, s) in enumerate(layer_shapes(cfg, kind).items())}

    runs, i = [], 0
    for kind, n, _ in cfg.runs():
        layers = [layer(kind, i + j) for j in range(n)]
        runs.append({k: jnp.stack([l[k] for l in layers])
                     for k in layers[0]})
        i += n
    wte = draw_leaf(jax.random.fold_in(key, 2), "wte",
                    (cfg.vocab_size, cfg.hidden_size), std, dt)
    return {"wte": wte, "lnf_w": jnp.ones((cfg.hidden_size,), dt),
            "runs": tuple(runs)}


def init_slot_state(cfg: GraniteHybridConfig, max_batch: int):
    """``(ssm, conv)`` zeros for ``max_batch`` decode slots."""
    Lm = cfg.num_mamba_layers
    return (jnp.zeros((Lm, max_batch, cfg.mamba_n_heads, cfg.mamba_d_head,
                       cfg.mamba_d_state), jnp.float32),
            jnp.zeros((Lm, max_batch, cfg.conv_dim, cfg.mamba_d_conv - 1),
                      jnp.dtype(cfg.dtype)))


def kernel_tiers(cfg: GraniteHybridConfig, state_shape):
    """``{"ssm_state_update": {"tier", "reason"}}``: the tier the decode
    step's state update runs on, from the function its dispatch reads."""
    from ..ops.ssm import ssm_state_update_tier
    tier, why = ssm_state_update_tier(state_shape, cfg.mamba_n_groups)
    return {"ssm_state_update": {"tier": tier, "reason": why}}


# ---------------------------------------------------------------------
# the layer equations
# ---------------------------------------------------------------------
def _attention_spec(cfg: GraniteHybridConfig, block_size: int):
    from ..ops.decode_block import DecodeBlockSpec
    return DecodeBlockSpec(
        hidden=cfg.hidden_size, num_heads=cfg.num_heads,
        kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
        block_size=block_size, norm="rms", activation="swiglu",
        eps=cfg.rms_norm_eps, rope=False,
        attn_scale=cfg.attention_multiplier,
        residual_scale=cfg.residual_multiplier)


def _make_ffn_half(cfg: GraniteHybridConfig, norm):
    """``ffn_half(x, lp, count_mask=None) -> (x, counts)``: the second
    half of every layer, ``counts`` the int32 pair ``[assignments that
    landed on held experts, distinct held experts hit]`` over the tokens
    of ``x`` that ``count_mask`` names."""
    from ..parallel.moe import moe_swiglu_ffn_masked

    def ffn_half(x, lp, count_mask=None):
        y = norm(x, lp["ln2_w"])
        out, counts = moe_swiglu_ffn_masked(
            y, lp["router_w"], lp["e_gate"], lp["e_up"], lp["e_down"],
            top_k=cfg.num_experts_per_tok, gate="topk_softmax",
            expert_offset=cfg.expert_offset, with_counts=True,
            count_mask=count_mask)
        out = out + (jax.nn.silu(y @ lp["s_gate"])
                     * (y @ lp["s_up"])) @ lp["s_down"]
        r = jnp.asarray(cfg.residual_multiplier, x.dtype)
        return x + out * r, counts

    return ffn_half


def _make_mamba_mix(cfg: GraniteHybridConfig, norm):
    """``mix(x [B, T, H], lp, S [B, nh, P, N], tail [B, C, W-1], valid)
    -> (x, S, tail)``: the Mamba-2 half of a layer through the chunked
    scan, positions at or past ``valid`` leaving state and tail alone.
    With ``row`` (the decode step: ``T == 1``) ``S`` is the WHOLE state
    array ``[Lm, B, nh, P, N]`` and comes back whole, its row ``row``
    stepped once in place (``ops.ssm.ssm_state_update_row``)."""
    from ..ops.ssm import (causal_conv, ssd_chunk_scan,
                           ssm_state_update_row)
    di, nh, P = cfg.d_inner, cfg.mamba_n_heads, cfg.mamba_d_head
    G, N, C = cfg.mamba_n_groups, cfg.mamba_d_state, cfg.conv_dim
    eps = cfg.rms_norm_eps
    f32 = jnp.float32

    def mix(x, lp, S, tail, valid=None, row=None):
        B, T, _ = x.shape
        u = norm(x, lp["ln1_w"]) @ lp["in_w"]
        z, xbc, dt = u[..., :di], u[..., di:di + C], u[..., di + C:]
        xbc, tail = causal_conv(xbc, tail, lp["conv_w"], lp["conv_b"],
                                valid=valid)
        xbc = jax.nn.silu(xbc)
        xs = xbc[..., :di].reshape(B, T, nh, P)
        Bm = xbc[..., di:di + G * N].reshape(B, T, G, N)
        Cm = xbc[..., di + G * N:].reshape(B, T, G, N)
        dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
        A = -jnp.exp(lp["A_log"].astype(f32))
        if row is not None:
            y, S = ssm_state_update_row(xs[:, 0], dt[:, 0], A, Bm[:, 0],
                                        Cm[:, 0], lp["D"], S, row)
            y = y[:, None]
        else:
            if valid is not None:
                dt = jnp.where((jnp.arange(T) < valid)[None, :, None],
                               dt, 0.0)
            y, S = ssd_chunk_scan(xs, dt, A, Bm, Cm, lp["D"], S,
                                  chunk=cfg.mamba_chunk_size)
        # the gated norm: the gate first, then the norm, in float32
        g = y.reshape(B, T, di).astype(f32) * jax.nn.silu(z.astype(f32))
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                              + eps)
        out = (lp["norm_w"] * g.astype(x.dtype)) @ lp["out_w"]
        return x + out * jnp.asarray(cfg.residual_multiplier,
                                     x.dtype), S, tail

    return mix


def _closures(cfg: GraniteHybridConfig, block_size: int):
    from ..ops.decode_block import make_norm
    spec = _attention_spec(cfg, block_size)
    norm = make_norm(spec)
    return spec, norm, _make_ffn_half(cfg, norm), _make_mamba_mix(cfg, norm)


def _row(a, i):
    return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)


def _set_row(a, row, i):
    return jax.lax.dynamic_update_index_in_dim(a, row, i, 0)


def _head(cfg: GraniteHybridConfig, norm, params, x):
    """``[n, H]`` -> float32 logits over the tied table."""
    xf = norm(x, params["lnf_w"])
    logits = jnp.einsum("bh,vh->bv", xf, params["wte"],
                        preferred_element_type=jnp.float32)
    return logits / cfg.logits_scaling


def build_hybrid_step(cfg: GraniteHybridConfig, block_size: int):
    """The decode program: ``step(params, pool_k, pool_v, ssm, conv, bt,
    lengths, tokens) -> (pool_k, pool_v, ssm, conv, logits [B, V],
    counts [2], greedy [B])``.  One ``lax.scan`` a run of ``cfg.runs()``; pools and
    state ride in the carry and are updated in place, row by layer.
    ``counts`` sums the expert layers' pair over the layers and over
    the rows that run a request (``lengths > 0``: a running slot holds
    at least its prompt's first token); ``greedy`` is every row's
    first choice (``argmax``, the lowest index of equals as on the
    host), so that the engine need not fetch the logits to pick."""
    from ..ops.decode_block import decode_attention_xla
    spec, norm, ffn_half, mix = _closures(cfg, block_size)
    emb = cfg.embedding_multiplier

    def step(params, pool_k, pool_v, ssm, conv, bt, lengths, tokens):
        x = jnp.take(params["wte"], tokens, axis=0)
        x = x * jnp.asarray(emb, x.dtype)
        live = lengths > 0
        carry = (x, pool_k, pool_v, ssm, conv, jnp.zeros((2,), jnp.int32))
        for (kind, n, first), run in zip(cfg.runs(), params["runs"]):
            def body(carry, inp, kind=kind, first=first):
                x, pk, pv, ssm, conv, cnt = carry
                lp, i = inp
                row = first + i
                if kind == "mamba":
                    x3, ssm, tl = mix(x[:, None], lp, ssm,
                                      _row(conv, row), row=row)
                    x, conv = x3[:, 0], _set_row(conv, tl, row)
                else:
                    x, k1, v1 = decode_attention_xla(
                        x, lp, _row(pk, row), _row(pv, row), bt, lengths,
                        None, None, spec=spec)
                    pk, pv = _set_row(pk, k1, row), _set_row(pv, v1, row)
                x, c = ffn_half(x, lp, live)
                return (x, pk, pv, ssm, conv, cnt + c), None

            carry, _ = jax.lax.scan(body, carry,
                                    (run, jnp.arange(n, dtype=jnp.int32)))
        x, pool_k, pool_v, ssm, conv, cnt = carry
        logits = _head(cfg, norm, params, x)
        return (pool_k, pool_v, ssm, conv, logits, cnt,
                jnp.argmax(logits, axis=-1).astype(jnp.int32))

    return step


def build_hybrid_chunk_fill(cfg: GraniteHybridConfig, block_size: int,
                            Ts: int):
    """The chunk fill of ONE sequence: ``fill(params, pool_k, pool_v,
    ssm, conv, bt_row, start, toks [Ts], slot, valid=None) -> (pool_k,
    pool_v, ssm, conv, logits [1, V])``.  ``start == 0`` begins the
    slot's state at zero (a reused slot starts clean); a later chunk
    continues it.  With ``valid`` only the first ``valid`` tokens are
    real: the padded rows write no K/V, leave the state and the conv
    tail as the last valid token left them, and the logits come from
    row ``valid - 1``."""
    from ..ops.decode_block import prefill_attention_xla
    from ..ops.paged_kv import pool_geometry
    spec, norm, ffn_half, mix = _closures(cfg, block_size)
    BS = block_size
    emb = cfg.embedding_multiplier

    def slot_row(a, row, slot):                   # [Lm, B, ...] -> [1, ...]
        return jax.lax.dynamic_slice(
            a, (row, slot) + (0,) * (a.ndim - 2),
            (1, 1) + a.shape[2:])[0]

    def set_slot_row(a, val, row, slot):
        return jax.lax.dynamic_update_slice(
            a, val[None].astype(a.dtype),
            (row, slot) + (0,) * (a.ndim - 2))

    def fill(params, pool_k, pool_v, ssm, conv, bt_row, start, toks, slot,
             valid=None):
        pos = start + jnp.arange(Ts)
        x = jnp.take(params["wte"], toks, axis=0)[None]
        x = x * jnp.asarray(emb, x.dtype)
        blk = jnp.take(jnp.maximum(bt_row, 0), pos // BS)
        if valid is not None:
            blk = jnp.where(jnp.arange(Ts) < valid, blk,
                            pool_geometry(pool_k)[0])
        off = pos % BS
        jpos = jnp.arange(bt_row.shape[0] * BS)[None, None, None, :]
        mask = jpos <= pos[None, None, :, None]
        fresh = start == 0
        carry = (x, pool_k, pool_v, ssm, conv)
        for (kind, n, first), run in zip(cfg.runs(), params["runs"]):
            def body(carry, inp, kind=kind, first=first):
                x, pk, pv, ssm, conv = carry
                lp, i = inp
                row = first + i
                if kind == "mamba":
                    S = slot_row(ssm, row, slot)
                    tl = slot_row(conv, row, slot)
                    S = jnp.where(fresh, jnp.zeros_like(S), S)
                    tl = jnp.where(fresh, jnp.zeros_like(tl), tl)
                    x, S, tl = mix(x, lp, S, tl, valid=valid)
                    ssm = set_slot_row(ssm, S, row, slot)
                    conv = set_slot_row(conv, tl, row, slot)
                else:
                    x, k1, v1 = prefill_attention_xla(
                        x, lp, _row(pk, row), _row(pv, row), blk, off,
                        bt_row, mask, None, None, spec=spec)
                    pk, pv = _set_row(pk, k1, row), _set_row(pv, v1, row)
                x, _ = ffn_half(x, lp)
                return (x, pk, pv, ssm, conv), None

            carry, _ = jax.lax.scan(body, carry,
                                    (run, jnp.arange(n, dtype=jnp.int32)))
        x, pool_k, pool_v, ssm, conv = carry
        last = x[:, -1] if valid is None \
            else jnp.take(x, valid - 1, axis=1)
        return pool_k, pool_v, ssm, conv, _head(cfg, norm, params, last)

    return fill


# the names the serving engine and the zoo's CLI find a family's
# programs and parameters under (``inference/serving.py:_model_module``,
# ``serving/http.py:build_frontend``)
build_step = build_hybrid_step
build_chunk_fill = build_hybrid_chunk_fill
init_params = init_granite_hybrid_params
