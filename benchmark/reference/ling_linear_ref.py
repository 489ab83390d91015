"""Plain float32 reference of the Ling-3.0-flash decoder (``model_type``
``bailing_hybrid``: KDA linear-attention layers with one latent-attention
layer in every ``layer_group_size``, leading dense layers, then
sigmoid-routed group-limited experts beside a shared one).

Straightforward ``jax.numpy`` at float32 with ``Precision.HIGHEST``: no
kernels, no cache, no chunked scan, no absorbed form.  It imports
nothing of ``paddle_tpu`` and takes nothing the program made: the
weights are drawn HERE from the seed and the harness hands the program
the same draw (``benchmark/programs/ling_linear.py``).  The model's own
code is not public in the installed ``transformers``; the equations
follow the catalog row's keys, the KDA paper (arXiv:2510.26692) and the
public blocks they name (``tests/test_ling_linear.py`` ties the
recurrence to ``qwen3_next``'s ``torch_recurrent_gated_delta_rule`` and
the latent mixer to ``DeepseekV3Attention`` at a tiny size):

* ``h = wte[ids]``; a layer is ``h = h + mix(rms(h))`` then ``h = h +
  ffn(rms(h))``; ``logits = rms(h) @ head`` (untied; no bias anywhere;
  eps ``rms_norm_eps``).  Layer ``i`` attends iff ``(i + 1) %
  layer_group_size == 0``, else it is KDA.
* ``mix``, KDA (``num_attention_heads`` heads of ``head_dim`` keys AND
  values: ``num_kv_heads_for_linear_attn`` 0): ``q, k, v = silu(conv(x
  W_q)), silu(conv(x W_k)), silu(conv(x W_v))``, a causal depthwise conv
  over the last ``short_conv_kernel_size`` positions, no bias; ``q =
  l2norm(q) / sqrt(d)``, ``k = l2norm(k)`` a head (eps 1e-6 under the
  root); ``log a = kda_lower_bound * sigmoid(exp(A_log) * (x W_f +
  dt_bias))`` a CHANNEL (``kda_safe_gate``; without it ``-exp(A_log)
  softplus(..)``), ``A_log`` a head; ``beta = sigmoid(x W_beta)`` a
  head; per head a float32 state ``S [d, d]``: ``S <- Diag(a) S; S <- S
  + beta k (v - S^T k)^T; o = S^T q``, a ``lax.scan`` over TIME, one
  token a step; ``y = (rms_head(o) * sigmoid(x W_g)) W_o``, the norm
  over each head's values with a gain, the gate one scalar a head.
* ``mix``, latent, the EXPANDED form only: ``q = x W_q`` (no low-rank
  step: ``q_lora_rank`` null) -> per head ``[q_n | q_r]``; ``[c | k_r] =
  x W_kva``, ``c = rms(c)``; per head ``[k_n | v] = c W_kvb``; RoPE
  (theta ``rope_theta``) turns ``q_r`` of every head and the one ``k_r``;
  ``score = (q_n . k_n + q_r . k_r) * (d_n + d_r) ** -0.5``, causal
  softmax, ``o = sum p v``; ``(concat_heads(o) * sigmoid(x W_g)) W_o``,
  the gate a head.
* ``ffn``: layers below ``first_k_dense_replace`` a SwiGLU of
  ``intermediate_size``.  The others: ``s = sigmoid(x W_r)`` over ALL
  ``router_num_experts``; the CHOICE is the ``num_experts_per_tok``
  largest of ``s + b``, among the ``topk_group`` groups of ``n_group``
  whose two best ``s + b`` sum highest (the others count as 0); the
  WEIGHTS are ``s`` of the chosen, without ``b``, over their sum + 1e-20
  (``norm_topk_prob``), times ``routed_scaling_factor``; ``sum_j g_j
  E_j(x) + shared(x)``.

Departures from the published model, all of them the configuration's:

* **the share.**  The file's ``num_experts`` is the number of experts
  HELD, ``[expert_offset, expert_offset + num_experts)`` of the router's
  ``router_num_experts``.  Each token keeps of its chosen experts those
  that are held and adds their terms with their gates UNCHANGED; what
  the absent experts would add is left out (a dense loop over the held
  experts).  Nothing stands in for the other chips.
* **the vocabulary** is the file's ``vocab_size`` rows of the table and
  columns of the head: a slice is a smaller vocabulary.
* **the readings** the catalog's keys leave open (``assumed`` in the
  file, with reasons): ``use_qk_norm`` as KDA's l2norm and as the latent
  norm; ``rope_interleave`` true; the norm's gain over all ``nh x d``
  values; one shared expert; the swiglu limits off in the layers held.
* **the initialisation**: matrices N(0, ``initializer_range``), an
  expert's hanging on its number among the router's; norm gains one; the
  conv uniform in +-0.5; ``A_log`` uniform in +-0.25; ``dt_bias`` the
  logit of ``rate / |kda_lower_bound|``, ``rate`` log-uniform in [0.002,
  0.3]: a channel's log decay a token at ``x W_f = 0``, so that channels
  remember over 3 to 500 tokens; the router's bias uniform in
  +-``router_bias_range``.
* not held: the vision tower (the ``-VL`` row's ``config`` holds the
  language model only) and the multi-token-prediction layer.

``prec="fp8"`` is the CONTROL, not a mode of the benchmark: the same
arithmetic with every matmul operand (and the recurrence's ``q``, ``k``,
``v``, attention's ``q``, ``k``, ``v`` and probabilities) rounded to
float8 e4m3, the nearest precision below the bfloat16 the configuration
states.  It has to come out as not correct.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_ONES = ("ln1_w", "ln2_w", "kv_a_ln_w", "o_norm_w", "lnf_w")
_EXPERT_LEAVES = ("e_gate", "e_up", "e_down")
_CONVS = ("conv_q_w", "conv_k_w", "conv_v_w")
RATE_MIN, RATE_MAX = 2e-3, 0.3
QUERY_BLOCK = 256
ROW_BLOCK = 1024


# ---------------------------------------------------------------------
# sizes and weights
# ---------------------------------------------------------------------
def sizes(cfg: dict) -> dict:
    """The numbers the equations need, from the published keys."""
    L = int(cfg["num_hidden_layers"])
    kd = min(int(cfg["first_k_dense_replace"]), L)
    g = int(cfg["layer_group_size"])
    nh, d = int(cfg["num_attention_heads"]), int(cfg["head_dim"])
    held = int(cfg["num_experts"])
    if any(cfg.get("expert_swiglu_limit_list", [])[:L]) \
            or any(cfg.get("share_expert_swiglu_limit_list", [])[:L]):
        raise NotImplementedError("a non-zero SwiGLU limit in a layer held")
    if cfg.get("q_lora_rank") is not None:
        raise NotImplementedError("a low-rank query step")
    mixers = tuple("attention" if (i + 1) % g == 0 else "kda"
                   for i in range(L))
    return dict(
        H=int(cfg["hidden_size"]), NH=nh, D=d, W=nh * d,
        DN=int(cfg["qk_nope_head_dim"]), DR=int(cfg["qk_rope_head_dim"]),
        DV=int(cfg["v_head_dim"]), RKV=int(cfg["kv_lora_rank"]),
        V=int(cfg["vocab_size"]), L=L, KD=kd, mixers=mixers,
        types=tuple(f"{m}_" + ("dense" if i < kd else "expert")
                    for i, m in enumerate(mixers)),
        CW=int(cfg["short_conv_kernel_size"]),
        F=int(cfg["intermediate_size"]),
        FE=int(cfg["moe_intermediate_size"]),
        FS=int(cfg["moe_shared_expert_intermediate_size"])
        * int(cfg.get("num_shared_experts", 1)),
        E=int(cfg.get("router_num_experts", held)), EH=held,
        E0=int(cfg.get("expert_offset", 0)),
        K=int(cfg["num_experts_per_tok"]),
        NG=int(cfg["n_group"]), TG=int(cfg["topk_group"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        rsf=float(cfg["routed_scaling_factor"]),
        eps=float(cfg["rms_norm_eps"]),
        lower=float(cfg["kda_lower_bound"]),
        safe_gate=bool(cfg["kda_safe_gate"]),
        theta=float(cfg["rope_theta"]),
        interleave=bool(cfg.get("rope_interleave", True)),
        std=float(cfg.get("initializer_range", 0.02)),
        bias_range=float(cfg.get("router_bias_range", 0.0)))


def layer_shapes(cfg: dict, kind: str) -> Dict[str, Tuple[int, ...]]:
    z = sizes(cfg)
    H, nh, W = z["H"], z["NH"], z["W"]
    mixer, ffn = kind.split("_")
    out = {"ln1_w": (H,), "ln2_w": (H,), "g_w": (H, nh)}
    if mixer == "kda":
        out.update(q_w=(H, W), k_w=(H, W), v_w=(H, W),
                   conv_q_w=(W, z["CW"]), conv_k_w=(W, z["CW"]),
                   conv_v_w=(W, z["CW"]), f_w=(H, W), dt_bias=(W,),
                   A_log=(nh,), beta_w=(H, nh), o_norm_w=(W,),
                   o_w=(W, H))
    elif mixer == "attention":
        out.update(q_w=(H, nh * (z["DN"] + z["DR"])),
                   kv_a_w=(H, z["RKV"] + z["DR"]), kv_a_ln_w=(z["RKV"],),
                   kv_b_w=(z["RKV"], nh * (z["DN"] + z["DV"])),
                   o_w=(nh * z["DV"], H))
    else:
        raise ValueError(f"unknown mixer {mixer!r}")
    if ffn == "dense":
        out.update(gate_w=(H, z["F"]), up_w=(H, z["F"]),
                   down_w=(z["F"], H))
    else:
        out.update(router_w=(H, z["E"]), router_b=(z["E"],),
                   e_gate=(z["EH"], H, z["FE"]), e_up=(z["EH"], H, z["FE"]),
                   e_down=(z["EH"], z["FE"], H), s_gate=(H, z["FS"]),
                   s_up=(H, z["FS"]), s_down=(z["FS"], H))
    return out


def outer_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    z = sizes(cfg)
    return {"wte": (z["V"], z["H"]), "head": (z["H"], z["V"]),
            "lnf_w": (z["H"],)}


def param_count(cfg: dict) -> int:
    z = sizes(cfg)
    n = sum(math.prod(s) for kind in z["types"]
            for s in layer_shapes(cfg, kind).values())
    return n + sum(math.prod(s) for s in outer_shapes(cfg).values())


def _items(cfg: dict) -> tuple:
    """The configuration's numbers (and its two lists of limits) as a
    hashable key for the caches of compiled programs below."""
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in cfg.items()
        if isinstance(v, (int, float, str)) or v is None
        or (isinstance(v, list)
            and all(isinstance(x, (int, float)) for x in v))))


def _cfg_of(cfg_items: tuple) -> dict:
    return {k: list(v) if isinstance(v, tuple) else v for k, v in cfg_items}


@jax.jit
def _key_from_words(lo, hi):
    return jax.random.fold_in(jax.random.key(lo), hi)


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31).  The
    seed goes in as data, never as a constant of a compiled program."""
    seed = int(seed)
    return _key_from_words(jnp.int32(seed & 0x7FFFFFFF),
                           jnp.int32(seed >> 31))


def _expert(key, shape, std: float, dtype, e):
    """Expert ``e``'s matrix of one bank: it hangs on ITS number among
    all the router's experts, so that any share draws what the whole
    draws."""
    return (jax.random.normal(jax.random.fold_in(key, e), shape,
                              jnp.float32) * std).astype(dtype)


def _draw(key, name: str, shape, z: dict, dtype, banks: bool = True):
    if name in _ONES:
        return jnp.ones(shape, dtype)
    if name == "router_b":
        return jax.random.uniform(key, shape, jnp.float32, -z["bias_range"],
                                  z["bias_range"]).astype(dtype)
    if name in _CONVS:
        return jax.random.uniform(key, shape, jnp.float32, -0.5,
                                  0.5).astype(dtype)
    if name == "A_log":
        return jax.random.uniform(key, shape, jnp.float32, -0.25,
                                  0.25).astype(dtype)
    if name == "dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32)
        rate = jnp.exp(u * (math.log(RATE_MAX) - math.log(RATE_MIN))
                       + math.log(RATE_MIN)) / abs(z["lower"])
        return (jnp.log(rate) - jnp.log1p(-rate)).astype(dtype)
    if name in _EXPERT_LEAVES:
        one = functools.partial(_expert, key, shape[1:], z["std"], dtype)
        return jax.vmap(one)(z["E0"] + jnp.arange(
            shape[0], dtype=jnp.int32)) if banks else one
    return (jax.random.normal(key, shape, jnp.float32)
            * z["std"]).astype(dtype)


def layer_weights(cfg: dict, key, i, dtype, kind: str = "",
                  banks: bool = True) -> Dict:
    """Layer ``i``'s leaves, rounded once to the served dtype.  ``i``
    may be traced when ``kind`` is given (else it is read from the
    layer's place).  ``banks=False``: the three expert banks are not
    made; in their place stands ``e -> expert e's matrix`` (``e`` its
    number among the router's), the same draw."""
    z = sizes(cfg)
    kind = kind or z["types"][int(i)]
    lk = jax.random.fold_in(jax.random.fold_in(key, 1), i)
    return {n: _draw(jax.random.fold_in(lk, j), n, s, z, dtype, banks)
            for j, (n, s) in enumerate(layer_shapes(cfg, kind).items())}


def outer_weights(cfg: dict, key, dtype) -> Dict[str, jax.Array]:
    z = sizes(cfg)
    ok = jax.random.fold_in(key, 2)
    return {n: _draw(jax.random.fold_in(ok, j), n, s, z, dtype)
            for j, (n, s) in enumerate(outer_shapes(cfg).items())}


# ---------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------
E4M3 = jnp.float8_e4m3fn


def _q(x):
    """Round to float8 with one scale per tensor (the usual recipe)."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / float(jnp.finfo(E4M3).max), 1.0)
    return (x / s).astype(E4M3).astype(jnp.float32) * s


def mm(a, b, prec: str):
    if prec == "highest":
        return jnp.matmul(a, b, precision=HIGHEST)
    if prec == "fp8":
        return jnp.matmul(_q(a), _q(b), precision=HIGHEST)
    raise ValueError(f"unknown precision {prec!r}")


def _low(x, prec: str):
    return _q(x) if prec == "fp8" else x


def rms_norm(x, w, eps: float):
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * w


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + 1e-6)


def swiglu(y, wg, wu, wd, prec: str):
    return mm(jax.nn.silu(mm(y, wg, prec)) * mm(y, wu, prec), wd, prec)


def in_row_blocks(fn, y):
    """``fn(y)`` for a row-wise ``fn``, ``ROW_BLOCK`` rows at a time.
    (Under the float8 control a block is rounded with its own scale.)"""
    T = y.shape[0]
    rb = min(T, ROW_BLOCK)
    n = -(-T // rb)
    yp = jnp.pad(y, ((0, n * rb - T), (0, 0))).reshape(n, rb, -1)
    return jax.lax.map(fn, yp).reshape(n * rb, -1)[:T]


def short_conv(x, w):
    """Causal depthwise conv of ``x [T, C]`` with ``w [C, width]`` from
    zeros before the first position; ``w[:, -1]`` meets the current
    one."""
    T, width = x.shape[0], w.shape[1]
    pad = jnp.pad(x, ((width - 1, 0), (0, 0)))
    return sum(pad[k:k + T] * w[:, k] for k in range(width))


def delta_rule(q, k, v, log_a, beta):
    """The KDA recurrence of ONE sequence from a zero state, a token a
    step: ``q, k, log_a [T, nh, d]``, ``v [T, nh, dv]``, ``beta [T,
    nh]`` -> ``o [T, nh, dv]``."""
    def step(S, inp):
        qt, kt, vt, gt, bt = inp
        S = S * jnp.exp(gt)[..., None]                       # Diag(a) S
        r = vt - jnp.einsum("hkv,hk->hv", S, kt, precision=HIGHEST)
        S = S + (bt[:, None] * kt)[..., None] * r[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=HIGHEST)

    nh, d, dv = q.shape[1], q.shape[2], v.shape[2]
    _, o = jax.lax.scan(step, jnp.zeros((nh, d, dv), jnp.float32),
                        (q, k, v, log_a, beta))
    return o


def kda_mix(u, w, z: dict, prec: str):
    """The KDA mixer on ONE sequence ``u [T, H]`` (already normed)."""
    T = u.shape[0]
    nh, d = z["NH"], z["D"]

    def proj(name, conv):
        y = jax.nn.silu(short_conv(mm(u, w[name], prec), w[conv]))
        return y.reshape(T, nh, d)

    q = l2norm(proj("q_w", "conv_q_w")) * d ** -0.5
    k = l2norm(proj("k_w", "conv_k_w"))
    v = proj("v_w", "conv_v_w")
    f = (mm(u, w["f_w"], prec) + w["dt_bias"]).reshape(T, nh, d)
    A = jnp.exp(w["A_log"])[:, None]
    log_a = z["lower"] * jax.nn.sigmoid(A * f) if z["safe_gate"] \
        else -A * jax.nn.softplus(f)
    beta = jax.nn.sigmoid(mm(u, w["beta_w"], prec))          # [T, nh]
    o = delta_rule(_low(q, prec), _low(k, prec), _low(v, prec), log_a,
                   beta)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + z["eps"])
    o = o * w["o_norm_w"].reshape(nh, d)
    o = o * jax.nn.sigmoid(mm(u, w["g_w"], prec))[..., None]
    return mm(o.reshape(T, nh * d), w["o_w"], prec)


def rope(t, pos, z: dict):
    """Turn the pairs of ``t [T, ..., d_r]`` by their position's angles,
    in place: pair ``i`` is columns ``(2i, 2i + 1)`` when interleaved,
    else ``(i, i + d_r / 2)``."""
    half = z["DR"] // 2
    inv = 1.0 / (z["theta"] ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv             # [T, half]
    ang = ang.reshape(ang.shape[:1] + (1,) * (t.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if z["interleave"]:
        a, b = t[..., 0::2], t[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         -1).reshape(t.shape)
    a, b = t[..., :half], t[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def latent_mix(u, w, z: dict, prec: str):
    """Multi-head latent attention, expanded, on ONE sequence ``u [T,
    H]`` (already normed), causal, the output gated a head.  A head at a
    time and within a head a block of queries at a time."""
    T = u.shape[0]
    nh, dn, dr, dv, rkv = z["NH"], z["DN"], z["DR"], z["DV"], z["RKV"]
    q = mm(u, w["q_w"], prec).reshape(T, nh, dn + dr)
    kv = mm(u, w["kv_a_w"], prec)
    c = rms_norm(kv[:, :rkv], w["kv_a_ln_w"], z["eps"])
    pos = jnp.arange(T)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, z)], -1)
    k_r = rope(kv[:, rkv:], pos, z)                  # one for all heads
    scale = (dn + dr) ** -0.5
    qb = min(T, QUERY_BLOCK)
    n = -(-T // qb)

    def head(inp):
        qh, w_kvb = inp                  # [T, d_n + d_r], [r_kv, d_n + d_v]
        kvb = mm(c, w_kvb, prec)
        k = _low(jnp.concatenate([kvb[:, :dn], k_r], -1), prec)
        v = _low(kvb[:, dn:], prec)
        qp = jnp.pad(_low(qh, prec), ((0, n * qb - T), (0, 0)))

        def block(inp):
            qi, first = inp
            s = jnp.matmul(qi, k.T, precision=HIGHEST) * scale
            seen = pos[None, :] <= (first + jnp.arange(qb))[:, None]
            p = _low(jax.nn.softmax(jnp.where(seen, s, -1e30), -1), prec)
            return jnp.matmul(p, v, precision=HIGHEST)

        return jax.lax.map(block, (qp.reshape(n, qb, dn + dr),
                                   jnp.arange(n) * qb)).reshape(-1, dv)[:T]

    a = jax.lax.map(head, (
        q.transpose(1, 0, 2),
        w["kv_b_w"].reshape(rkv, nh, dn + dv).transpose(1, 0, 2)))
    a = a.transpose(1, 0, 2) \
        * jax.nn.sigmoid(mm(u, w["g_w"], prec))[..., None]
    return mm(a.reshape(T, nh * dv), w["o_w"], prec)


def gate(scores, bias, z: dict):
    """``(weights [T, K], chosen [T, K])`` from the sigmoid scores
    ``[T, E]``: the bias in the choice only."""
    T, E = scores.shape
    choice = scores + bias
    if z["NG"] > 1:
        per = E // z["NG"]
        best2 = jax.lax.top_k(choice.reshape(T, z["NG"], per), 2)[0].sum(-1)
        kept = jax.lax.top_k(best2, z["TG"])[1]              # [T, TG]
        keep = (kept[:, :, None] == jnp.arange(z["NG"])[None, None]).any(1)
        choice = jnp.where(jnp.repeat(keep, per, axis=1), choice, 0.0)
    idx = jax.lax.top_k(choice, z["K"])[1]
    g = jnp.take_along_axis(scores, idx, axis=1)
    if z["norm_topk"]:
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    return g * z["rsf"], idx


def moe(y, w, z: dict, prec: str):
    """The HELD experts' terms of ``y [T, H]``: routed over all the
    router's experts, a dense loop over those held."""
    scores = jax.nn.sigmoid(mm(y, w["router_w"], prec))
    gates, idx = gate(scores, w["router_b"], z)

    def one(acc, j):
        e = z["E0"] + j
        # a bank (its row ``j``), or the draw of expert ``e``
        wg, wu, wd = ((w[n](e) if callable(w[n]) else w[n][j]).astype(
            jnp.float32) for n in _EXPERT_LEAVES)
        g = jnp.sum(jnp.where(idx == e, gates, 0.0), -1)      # [T]
        return acc + g[:, None] * swiglu(y, wg, wu, wd, prec), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y),
                          jnp.arange(z["EH"], dtype=jnp.int32))
    return out


def block(x, w, z: dict, kind: str, prec: str):
    """One decoder layer on ONE sequence ``x [T, H]`` (float32)."""
    w = {k: v if k in _EXPERT_LEAVES else v.astype(jnp.float32)
         for k, v in w.items()}
    mixer, ffn = kind.split("_")
    mix = kda_mix if mixer == "kda" else latent_mix
    x = x + mix(rms_norm(x, w["ln1_w"], z["eps"]), w, z, prec)
    y = rms_norm(x, w["ln2_w"], z["eps"])
    if ffn == "dense":
        return x + in_row_blocks(lambda r: swiglu(
            r, w["gate_w"], w["up_w"], w["down_w"], prec), y)
    return x + moe(y, w, z, prec) + in_row_blocks(lambda r: swiglu(
        r, w["s_gate"], w["s_up"], w["s_down"], prec), y)


# ---------------------------------------------------------------------
# serving: the gap of every served token
# ---------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _serve_programs(cfg_items: tuple, dtype: str, prec: str):
    cfg = _cfg_of(cfg_items)
    z = sizes(cfg)
    dt = jnp.dtype(dtype)

    @jax.jit
    def embed(key, ids):
        return jnp.take(outer_weights(cfg, key, dt)["wte"], ids,
                        axis=0).astype(jnp.float32)

    def make_layer(kind):
        @functools.partial(jax.jit, donate_argnums=(2,))
        def layer(key, i, x):
            w = layer_weights(cfg, key, i, dt, kind, banks=False)
            # one sequence at a time: a layer's temporaries once
            return jax.lax.map(lambda r: block(r, w, z, kind, prec), x)
        return layer

    layers = {kind: make_layer(kind) for kind in set(z["types"])}

    @jax.jit
    def head(key, x):
        """``[n, T, V]`` logits, one sequence at a time."""
        o = outer_weights(cfg, key, dt)
        wf = o["head"].astype(jnp.float32)
        lnf = o["lnf_w"].astype(jnp.float32)
        return jax.lax.map(
            lambda xr: mm(rms_norm(xr, lnf, z["eps"]), wf, prec), x)

    return embed, layers, head


def hidden_states(cfg: dict, seed: int, ids: np.ndarray, dtype: str,
                  prec: str = "highest"):
    """``[n, T, H]`` float32: the stream after the last layer, before
    the last norm.  Layer by layer, each layer's weights drawn again
    from the seed and dropped."""
    embed, layers, _ = _serve_programs(_items(cfg), dtype, prec)
    key = seed_key(seed)
    x = embed(key, jnp.asarray(ids, jnp.int32))
    for i, kind in enumerate(sizes(cfg)["types"]):
        x = layers[kind](key, jnp.int32(i), x)
    return x


def reference_logits(cfg: dict, seed: int, ids: np.ndarray, dtype: str,
                     prec: str = "highest"):
    """``[n, T, V]`` float32 logits of ``ids [n, T]`` (padded at the
    end; every layer is causal, which makes the padding harmless)."""
    head = _serve_programs(_items(cfg), dtype, prec)[2]
    return head(seed_key(seed), hidden_states(cfg, seed, ids, dtype, prec))


@functools.lru_cache(maxsize=None)
def _gap_program(cfg_items: tuple, dtype: str, control: str):
    """The gaps of ``n`` sequences from their last hidden states, a
    block of positions' ``[block, V]`` logits (and the control's) alive
    at a time."""
    cfg = _cfg_of(cfg_items)
    z = sizes(cfg)
    dt = jnp.dtype(dtype)

    @jax.jit
    def gaps(key, x_ref, x_low, ids, lo, hi):
        o = outer_weights(cfg, key, dt)
        wf = o["head"].astype(jnp.float32)
        lnf = o["lnf_w"].astype(jnp.float32)
        n, T = ids.shape
        pb = math.gcd(T, 128)

        def one(inp):
            xr, xl, row, a, b = inp
            # the program's choice is the served token itself: the
            # token at p + 1 is what position p produced
            nxt = jnp.concatenate([row[1:], row[:1]])

            def part(inp):
                xr, xl, nxt, pos = inp
                ref = mm(rms_norm(xr, lnf, z["eps"]), wf, "highest")
                live = (pos >= a) & (pos < b)
                # a control's choice is its own first, given the prefix
                tok = mm(rms_norm(xl, lnf, z["eps"]), wf,
                         control).argmax(-1) if control else nxt
                got = jnp.take_along_axis(ref, tok[:, None], 1)[:, 0]
                gap = jnp.where(live, ref.max(-1) - got, 0.0)
                agree = jnp.where(live, ref.argmax(-1) == tok, False)
                return gap.max(), gap.sum(), agree.sum(), live.sum()

            w, t, g, c = jax.lax.map(part, (
                xr.reshape(T // pb, pb, -1), xl.reshape(T // pb, pb, -1),
                nxt.reshape(T // pb, pb),
                jnp.arange(T).reshape(T // pb, pb)))
            return w.max(), t.sum(), g.sum(), c.sum()

        widest, total, agree, count = jax.lax.map(
            one, (x_ref, x_low, ids, lo, hi))
        return (widest.max(), total.sum() / count.sum(), agree.sum(),
                count.sum())

    return gaps


def served_gaps(cfg: dict, seed: int, seqs: Sequence[np.ndarray],
                prompt_lens: Sequence[int], pad_to: int, dtype: str,
                control: str = "") -> dict:
    """Compare served tokens with the reference.

    ``seqs[j]`` is prompt + served tokens of request ``j``.  Returns the
    widest and the mean gap (logit units) by which a served token lies
    below the reference's best, and how many tokens were compared.
    With ``control`` set (``"fp8"``) the 'served' token at every
    position is the one the lower precision puts first, given the same
    prefix."""
    n = len(seqs)
    ids = np.zeros((n, pad_to), np.int32)
    for j, s in enumerate(seqs):
        if len(s) > pad_to:
            raise ValueError(f"sequence of {len(s)} tokens > pad_to "
                             f"{pad_to}")
        ids[j, :len(s)] = s
    lo = jnp.asarray([p - 1 for p in prompt_lens], jnp.int32)
    hi = jnp.asarray([len(s) - 1 for s in seqs], jnp.int32)
    x_ref = hidden_states(cfg, seed, ids, dtype)
    x_low = hidden_states(cfg, seed, ids, dtype, control) \
        if control else x_ref
    widest, mean, agree, count = _gap_program(_items(cfg), dtype, control)(
        seed_key(seed), x_ref, x_low, jnp.asarray(ids), lo, hi)
    return {"widest_gap": float(widest), "mean_gap": float(mean),
            "agree": int(agree), "tokens": int(count)}
