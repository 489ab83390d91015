"""Plain float32 reference of the GLM-4.7-Flash decoder (``model_type``
``glm4_moe_lite``: multi-head latent attention in every layer, a leading
dense layer, then sigmoid-routed experts beside a shared one).

Straightforward ``jax.numpy`` at float32 with ``Precision.HIGHEST``: no
kernels, no cache, no absorbed form.  It imports nothing of
``paddle_tpu`` and takes nothing the program made: the weights are drawn
HERE from the seed and the harness hands the program the same draw
(``benchmark/programs/glm_moe_lite.py``).  The equations follow the
public implementation of the same block (``transformers`` 4.57,
``models/deepseek_v3/modeling_deepseek_v3.py``: ``DeepseekV3Attention``,
``DeepseekV3TopkRouter``, ``DeepseekV3MoE``; ``glm4_moe_lite`` itself is
not in that version; ``tests/test_glm_moe_lite.py`` compares this file
with ``DeepseekV3ForCausalLM`` at a tiny size on the same weights):

* ``h = wte[ids]``; a layer is ``h = h + attn(rms(h))`` then ``h = h +
  ffn(rms(h))``; ``logits = rms(h) @ head`` (untied; no bias anywhere).
* ``attn``, the EXPANDED form only: ``c_q = rms(x W_qa)``; ``q = c_q
  W_qb`` -> per head ``[q_n | q_r]``; ``[c | k_r] = x W_kva``, ``c =
  rms(c)``; per head ``[k_n | v] = c W_kvb``; RoPE turns ``q_r`` of
  every head and the one ``k_r`` all heads share; ``score = (q_n . k_n
  + q_r . k_r) * (d_n + d_r) ** -0.5``, causal softmax, ``o = sum p v``,
  ``concat_heads(o) W_o``.  Scores are made a block of 256 queries at a
  time (a 6,900-token sequence's ``[heads, T, T]`` would be 3.8 GB).
* ``ffn``: layers below ``first_k_dense_replace`` a SwiGLU of
  ``intermediate_size``.  The others: ``s = sigmoid(x W_r)`` (float32);
  the CHOICE is the ``num_experts_per_tok`` largest of ``s + b`` (``b``
  = ``e_score_correction_bias``), among the ``topk_group`` groups of
  ``n_group`` whose two best ``s + b`` sum highest (the others count as
  0, as the public code has it); the WEIGHTS are ``s`` of the chosen,
  WITHOUT ``b``, over their sum + 1e-20 (``norm_topk_prob``), times
  ``routed_scaling_factor``; ``sum_j g_j E_j(x) + shared(x)``, a dense
  loop over the experts.

What the configuration assumes, beside its published keys:

* ``rope_interleave`` (DeepseekV3's default, true): a head's rotated
  columns pair as ``(2i, 2i + 1)``.  Not among the catalog's keys; on
  seeded weights the other pairing is a permutation of columns.
* ``latent_norm_eps`` 1e-6: the public code builds ``q_a_layernorm`` and
  ``kv_a_layernorm`` with its RMS norm's default eps, not with
  ``rms_norm_eps`` (which the layers' and the last norm take).
* ``router_bias_range`` 0.1: ``b`` uniform in +-0.1 from the seed.  The
  public initialisation is zeros, under which a bias that leaked into
  the weights, or was left out of the choice, could not show.
* matrices N(0, ``initializer_range``), norm gains one.
* ``num_nextn_predict_layers``: the multi-token-prediction layer is a
  draft head after the last layer; the logits do not depend on it and
  it is neither drawn nor run.

``prec="fp8"`` is the CONTROL, not a mode of the benchmark: the same
arithmetic with every matmul operand (and attention's ``q``, ``k``,
``v`` and probabilities, a head's at a time) rounded to float8 e4m3,
the nearest precision below the bfloat16 the configuration states.  It has to come out as not
correct.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_ONES = ("ln1_w", "ln2_w", "q_a_ln_w", "kv_a_ln_w", "lnf_w")
_EXPERT_LEAVES = ("e_gate", "e_up", "e_down")
# rows a block: a 6,900-token sequence's scores ([heads, T, T]) or a
# dense layer's hidden rows ([T, 10240] three times) would each be
# gigabytes, and the served engine still stands beside the reference
QUERY_BLOCK = 256
ROW_BLOCK = 1024


# ---------------------------------------------------------------------
# sizes and weights
# ---------------------------------------------------------------------
def sizes(cfg: dict) -> dict:
    """The numbers the equations need, from the published keys."""
    L = int(cfg["num_hidden_layers"])
    kd = min(int(cfg["first_k_dense_replace"]), L)
    fe = int(cfg["moe_intermediate_size"])
    return dict(
        H=int(cfg["hidden_size"]), NH=int(cfg["num_attention_heads"]),
        DN=int(cfg["qk_nope_head_dim"]), DR=int(cfg["qk_rope_head_dim"]),
        DV=int(cfg["v_head_dim"]), RQ=int(cfg["q_lora_rank"]),
        RKV=int(cfg["kv_lora_rank"]), V=int(cfg["vocab_size"]), L=L,
        KD=kd, types=("dense",) * kd + ("expert",) * (L - kd),
        F=int(cfg["intermediate_size"]), FE=fe,
        FS=fe * int(cfg["n_shared_experts"]),
        E=int(cfg["n_routed_experts"]), K=int(cfg["num_experts_per_tok"]),
        NG=int(cfg["n_group"]), TG=int(cfg["topk_group"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        rsf=float(cfg["routed_scaling_factor"]),
        eps=float(cfg["rms_norm_eps"]),
        eps_latent=float(cfg.get("latent_norm_eps", 1e-6)),
        theta=float(cfg["rope_theta"]),
        interleave=bool(cfg.get("rope_interleave", True)),
        std=float(cfg.get("initializer_range", 0.02)),
        bias_range=float(cfg.get("router_bias_range", 0.0)))


def layer_shapes(cfg: dict, kind: str) -> Dict[str, Tuple[int, ...]]:
    z = sizes(cfg)
    H, nh = z["H"], z["NH"]
    out = {"ln1_w": (H,), "ln2_w": (H,), "q_a_w": (H, z["RQ"]),
           "q_a_ln_w": (z["RQ"],),
           "q_b_w": (z["RQ"], nh * (z["DN"] + z["DR"])),
           "kv_a_w": (H, z["RKV"] + z["DR"]), "kv_a_ln_w": (z["RKV"],),
           "kv_b_w": (z["RKV"], nh * (z["DN"] + z["DV"])),
           "o_w": (nh * z["DV"], H)}
    if kind == "dense":
        out.update(gate_w=(H, z["F"]), up_w=(H, z["F"]),
                   down_w=(z["F"], H))
    elif kind == "expert":
        out.update(router_w=(H, z["E"]), router_b=(z["E"],),
                   e_gate=(z["E"], H, z["FE"]), e_up=(z["E"], H, z["FE"]),
                   e_down=(z["E"], z["FE"], H), s_gate=(H, z["FS"]),
                   s_up=(H, z["FS"]), s_down=(z["FS"], H))
    else:
        raise ValueError(f"unknown layer type {kind!r}")
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
    """The configuration's numbers as a hashable key for the caches of
    compiled programs below."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


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
    """Expert ``e``'s matrix of one bank: it hangs on ITS number, so
    that a share of the experts would draw what the whole draws."""
    return (jax.random.normal(jax.random.fold_in(key, e), shape,
                              jnp.float32) * std).astype(dtype)


def _draw(key, name: str, shape, z: dict, dtype, banks: bool = True):
    if name in _ONES:
        return jnp.ones(shape, dtype)
    if name == "router_b":
        return jax.random.uniform(key, shape, jnp.float32, -z["bias_range"],
                                  z["bias_range"]).astype(dtype)
    if name in _EXPERT_LEAVES:
        one = functools.partial(_expert, key, shape[1:], z["std"], dtype)
        return jax.vmap(one)(jnp.arange(shape[0], dtype=jnp.int32)) \
            if banks else one
    return (jax.random.normal(key, shape, jnp.float32)
            * z["std"]).astype(dtype)


def layer_weights(cfg: dict, key, i, dtype, kind: str = "",
                  banks: bool = True) -> Dict:
    """Layer ``i``'s leaves, rounded once to the served dtype.  ``i``
    may be traced when ``kind`` is given (else it is read from the
    layer's place).  ``banks=False``: the three expert banks (600 MB a
    layer) are not made; in their place stands ``e -> expert e's
    matrix``, the same draw."""
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


def attention(u, w, z: dict, prec: str):
    """Multi-head latent attention, expanded, on ONE sequence ``u [T,
    H]`` (already normed), causal.  A head at a time (its keys and
    values decompressed from the latent there) and within a head a block
    of queries at a time, so that what is alive is a head's ``[T, d]``
    and a block's ``[block, T]``."""
    T = u.shape[0]
    nh, dn, dr, dv, rkv = z["NH"], z["DN"], z["DR"], z["DV"], z["RKV"]
    cq = rms_norm(mm(u, w["q_a_w"], prec), w["q_a_ln_w"], z["eps_latent"])
    q = mm(cq, w["q_b_w"], prec).reshape(T, nh, dn + dr)
    kv = mm(u, w["kv_a_w"], prec)
    c = rms_norm(kv[:, :rkv], w["kv_a_ln_w"], z["eps_latent"])
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
    return mm(a.transpose(1, 0, 2).reshape(T, nh * dv), w["o_w"], prec)


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
    """The routed experts' terms of ``y [T, H]``: a dense loop over the
    experts."""
    scores = jax.nn.sigmoid(mm(y, w["router_w"], prec))
    gates, idx = gate(scores, w["router_b"], z)

    def one(acc, e):
        # a bank, or the draw of one expert of it (``layer_weights``)
        wg, wu, wd = ((w[n](e) if callable(w[n]) else w[n][e]).astype(
            jnp.float32) for n in _EXPERT_LEAVES)
        g = jnp.sum(jnp.where(idx == e, gates, 0.0), -1)      # [T]
        return acc + g[:, None] * swiglu(y, wg, wu, wd, prec), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y),
                          jnp.arange(z["E"], dtype=jnp.int32))
    return out


def block(x, w, z: dict, kind: str, prec: str):
    """One decoder layer on ONE sequence ``x [T, H]`` (float32)."""
    # float32 throughout; the expert banks are cast one expert at a
    # time where they are used (a whole layer is 2.4 GB in float32)
    w = {k: v if k in _EXPERT_LEAVES else v.astype(jnp.float32)
         for k, v in w.items()}
    x = x + attention(rms_norm(x, w["ln1_w"], z["eps"]), w, z, prec)
    y = rms_norm(x, w["ln2_w"], z["eps"])
    if kind == "dense":
        return x + in_row_blocks(lambda r: swiglu(
            r, w["gate_w"], w["up_w"], w["down_w"], prec), y)
    return x + moe(y, w, z, prec) + in_row_blocks(lambda r: swiglu(
        r, w["s_gate"], w["s_up"], w["s_down"], prec), y)


# ---------------------------------------------------------------------
# serving: the gap of every served token
# ---------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _serve_programs(cfg_items: tuple, dtype: str, prec: str):
    cfg = dict(cfg_items)
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
    at a time (a whole 6,900-token sequence's would be 4.3 GB)."""
    cfg = dict(cfg_items)
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
