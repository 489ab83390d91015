"""Plain float32 reference of the granite-4.0-h hybrid decoder
(``model_type`` ``granitemoehybrid``: Mamba-2 layers, a few attention
layers, a mixture of experts and a shared MLP after every one).

Straightforward ``jax.numpy`` at float32 with ``Precision.HIGHEST``: no
kernels, no cache, no chunking.  It imports nothing of ``paddle_tpu``
and takes nothing the program made: the weights are drawn HERE from the
seed and the harness hands the program the same draw
(``benchmark/programs/granite_hybrid.py``).  The equations follow the
public implementation (``transformers`` 4.57,
``models/granitemoehybrid/modeling_granitemoehybrid.py``;
``tests/test_granite_hybrid.py`` compares the two at a tiny size with
the same weights):

* ``h0 = embedding_multiplier * wte[ids]``; a layer is ``h = h + r *
  mix(rms(h))`` then ``h = h + r * (moe(rms(h)) + shared(rms(h)))`` with
  ``r = residual_multiplier``; ``logits = rms(h) @ wte.T /
  logits_scaling`` (tied head).
* ``mix``, Mamba-2: ``[z | xBC | dt] = u @ in_w``; ``xBC = silu(conv(xBC)
  + b)``, a causal depthwise conv over the last ``mamba_d_conv``
  positions; ``[x | B | C] = xBC``; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; per head, a float32 state ``S_t = exp(dt_t A) S_{t-1} +
  dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``; ``rms_w(y * silu(z)) @
  out_w`` (the gate BEFORE the norm).  The recurrence runs as a
  ``lax.scan`` over TIME, one token a step: not the chunked form the
  program uses.
* ``mix``, attention: grouped-query, causal, NO positional encoding,
  scores times ``attention_multiplier``.
* ``moe``: router logits over ALL ``router_num_experts`` experts in
  float32, the ``num_experts_per_tok`` largest, a softmax over those;
  a SwiGLU per expert; ``shared``: the same SwiGLU, always on.

Departures from the public model, all of them the configuration's:

* **the share.**  The file's ``num_local_experts`` is the number of
  experts HELD, ``[expert_offset, expert_offset + num_local_experts)``
  of the router's ``router_num_experts``.  Each token keeps of its chosen
  experts those that are held and adds their terms with their gates
  UNCHANGED; what the absent experts would add is left out (a dense loop
  over the held experts).  Nothing stands in for the other chip.
* **the initialisation** (``assumed`` in the configuration's file):
  matrices N(0, ``initializer_range``); norm gains and ``D`` one; ``A_log
  = log(1..heads)``; the depthwise conv and its bias uniform in +-0.5
  (the framework's default for a width of 4); ``dt_bias`` the inverse
  softplus of a step drawn log-uniformly from [0.001, 0.1] (Mamba-2's,
  the layer's own ``time_step_min/max``).  The public ``_init_weights``
  fills ``dt_bias`` with 1.0, with which every head forgets within three
  tokens and a wrong hand-over of the state would go unseen.

``prec="fp8"`` is the CONTROL, not a mode of the benchmark: the same
arithmetic with every matmul operand (and the recurrence's ``x``, ``B``,
``C``, attention's ``q``, ``k``, ``v`` and probabilities) rounded to
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
_ONES = ("ln1_w", "ln2_w", "norm_w", "D", "lnf_w")
_EXPERT_LEAVES = ("e_gate", "e_up", "e_down")
DT_MIN, DT_MAX = 1e-3, 1e-1


# ---------------------------------------------------------------------
# sizes and weights
# ---------------------------------------------------------------------
def sizes(cfg: dict) -> dict:
    """The numbers the equations need, from the published keys."""
    h = int(cfg["hidden_size"])
    nh = int(cfg["num_attention_heads"])
    mh, mp = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    g, n = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    held = int(cfg["num_local_experts"])
    types = tuple(cfg["layer_types"])
    if len(types) != int(cfg["num_hidden_layers"]):
        raise ValueError("layer_types does not name num_hidden_layers "
                         "layers")
    return dict(
        H=h, NH=nh, KVH=int(cfg.get("num_key_value_heads") or nh),
        D=h // nh, V=int(cfg["vocab_size"]), L=len(types), types=types,
        F=int(cfg["intermediate_size"]),
        FS=int(cfg["shared_intermediate_size"]),
        E=int(cfg.get("router_num_experts", held)), EH=held,
        E0=int(cfg.get("expert_offset", 0)),
        K=int(cfg["num_experts_per_tok"]),
        MH=mh, MP=mp, G=g, N=n, DI=mh * mp, C=mh * mp + 2 * g * n,
        W=int(cfg["mamba_d_conv"]),
        eps=float(cfg["rms_norm_eps"]),
        emb=float(cfg["embedding_multiplier"]),
        att=float(cfg["attention_multiplier"]),
        res=float(cfg["residual_multiplier"]),
        lsc=float(cfg["logits_scaling"]),
        std=float(cfg.get("initializer_range", 0.02)),
        std_table=float(cfg.get("table_initializer_range",
                                cfg.get("initializer_range", 0.02))))


def layer_shapes(cfg: dict, kind: str) -> Dict[str, Tuple[int, ...]]:
    z = sizes(cfg)
    out = {"ln1_w": (z["H"],), "ln2_w": (z["H"],),
           "router_w": (z["H"], z["E"]),
           "e_gate": (z["EH"], z["H"], z["F"]),
           "e_up": (z["EH"], z["H"], z["F"]),
           "e_down": (z["EH"], z["F"], z["H"]),
           "s_gate": (z["H"], z["FS"]), "s_up": (z["H"], z["FS"]),
           "s_down": (z["FS"], z["H"])}
    if kind == "mamba":
        out.update(in_w=(z["H"], z["DI"] + z["C"] + z["MH"]),
                   conv_w=(z["C"], z["W"]), conv_b=(z["C"],),
                   dt_bias=(z["MH"],), A_log=(z["MH"],), D=(z["MH"],),
                   norm_w=(z["DI"],), out_w=(z["DI"], z["H"]))
    elif kind == "attention":
        out.update(q_w=(z["H"], z["NH"] * z["D"]),
                   k_w=(z["H"], z["KVH"] * z["D"]),
                   v_w=(z["H"], z["KVH"] * z["D"]),
                   o_w=(z["NH"] * z["D"], z["H"]))
    else:
        raise ValueError(f"unknown layer type {kind!r}")
    return out


def outer_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    z = sizes(cfg)
    return {"wte": (z["V"], z["H"]), "lnf_w": (z["H"],)}


def param_count(cfg: dict) -> int:
    z = sizes(cfg)
    n = sum(math.prod(s) for kind in z["types"]
            for s in layer_shapes(cfg, kind).values())
    return n + sum(math.prod(s) for s in outer_shapes(cfg).values())


def _items(cfg: dict) -> tuple:
    """The configuration's numbers (and its layer pattern) as a
    hashable key for the caches of compiled programs below."""
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in cfg.items()
        if isinstance(v, (int, float, str))
        or (isinstance(v, list)
            and all(isinstance(x, str) for x in v))))


@jax.jit
def _key_from_words(lo, hi):
    return jax.random.fold_in(jax.random.key(lo), hi)


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31).  The
    seed goes in as data, never as a constant of a compiled program."""
    seed = int(seed)
    return _key_from_words(jnp.int32(seed & 0x7FFFFFFF),
                           jnp.int32(seed >> 31))


def _draw(key, name: str, shape, z: dict, dtype):
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
    if name in _EXPERT_LEAVES:
        # an expert's weights hang on ITS number among all the router's
        # experts, so that any share of them draws what the whole draws
        ids = z["E0"] + jnp.arange(shape[0], dtype=jnp.int32)
        return jax.vmap(lambda e: (jax.random.normal(
            jax.random.fold_in(key, e), shape[1:], jnp.float32)
            * z["std"]).astype(dtype))(ids)
    std = z["std_table"] if name == "wte" else z["std"]
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def layer_weights(cfg: dict, key, i, dtype, kind: str = "") -> Dict:
    """Layer ``i``'s leaves, rounded once to the served dtype.  ``i``
    may be traced when ``kind`` is given (else it is read from
    ``layer_types[i]``)."""
    z = sizes(cfg)
    kind = kind or z["types"][int(i)]
    lk = jax.random.fold_in(jax.random.fold_in(key, 1), i)
    return {n: _draw(jax.random.fold_in(lk, j), n, s, z, dtype)
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


def mamba_mix(u, w, z: dict, prec: str):
    """The Mamba-2 mixer on ONE sequence ``u [T, H]`` (already normed)
    from a zero state: the recurrence token by token."""
    T = u.shape[0]
    di, c, mh, mp, g, n = z["DI"], z["C"], z["MH"], z["MP"], z["G"], z["N"]
    proj = mm(u, w["in_w"], prec)
    gate, xbc, dt = proj[:, :di], proj[:, di:di + c], proj[:, di + c:]
    pad = jnp.pad(xbc, ((z["W"] - 1, 0), (0, 0)))
    conv = w["conv_b"] + sum(pad[k:k + T] * w["conv_w"][:, k]
                             for k in range(z["W"]))
    xbc = jax.nn.silu(conv)
    x = _low(xbc[:, :di], prec).reshape(T, mh, mp)
    bm = _low(xbc[:, di:di + g * n], prec).reshape(T, g, n)
    cm = _low(xbc[:, di + g * n:], prec).reshape(T, g, n)
    bm = jnp.repeat(bm, mh // g, axis=1)               # [T, heads, N]
    cm = jnp.repeat(cm, mh // g, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])            # [T, heads]
    a = -jnp.exp(w["A_log"])

    def step(s, inp):
        xt, dtt, bt, ct = inp
        s = s * jnp.exp(dtt * a)[:, None, None] \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        y = jnp.einsum("hpn,hn->hp", s, ct, precision=HIGHEST) \
            + w["D"][:, None] * xt
        return s, y

    _, y = jax.lax.scan(step, jnp.zeros((mh, mp, n), jnp.float32),
                        (x, dt, bm, cm))
    y = y.reshape(T, di) * jax.nn.silu(gate)           # the gate first,
    y = rms_norm(y, w["norm_w"], z["eps"])             # then the norm
    return mm(y, w["out_w"], prec)


def attention_mix(u, w, z: dict, prec: str):
    """Causal grouped-query attention on ONE sequence, no positions."""
    T = u.shape[0]
    q = mm(u, w["q_w"], prec).reshape(T, z["NH"], z["D"])
    k = mm(u, w["k_w"], prec).reshape(T, z["KVH"], z["D"])
    v = mm(u, w["v_w"], prec).reshape(T, z["KVH"], z["D"])
    rep = z["NH"] // z["KVH"]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    q, k, v = _low(q, prec), _low(k, prec), _low(v, prec)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * z["att"]
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -1e30)
    p = _low(jax.nn.softmax(s, -1), prec)
    a = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
    return mm(a.reshape(T, -1), w["o_w"], prec)


def moe(y, w, z: dict, prec: str):
    """The held experts' terms of ``y [T, H]``: routed over all the
    router's experts, a dense loop over those held."""
    logits = mm(y, w["router_w"], prec)                       # [T, E]
    top, idx = jax.lax.top_k(logits, z["K"])
    gates = jax.nn.softmax(top, -1)                           # [T, K]

    def one(acc, inp):
        e, wg, wu, wd = (inp[0], *(a.astype(jnp.float32)
                                   for a in inp[1:]))
        g = jnp.sum(jnp.where(idx == e, gates, 0.0), -1)      # [T]
        return acc + g[:, None] * swiglu(y, wg, wu, wd, prec), None

    ids = z["E0"] + jnp.arange(z["EH"], dtype=jnp.int32)
    out, _ = jax.lax.scan(one, jnp.zeros_like(y),
                          (ids, w["e_gate"], w["e_up"], w["e_down"]))
    return out


def block(x, w, z: dict, kind: str, prec: str):
    """One decoder layer on ONE sequence ``x [T, H]`` (float32)."""
    # float32 throughout; the expert banks are cast one expert at a
    # time where they are used (a whole layer is 1.8 GB in float32)
    w = {k: v if k in _EXPERT_LEAVES else v.astype(jnp.float32)
         for k, v in w.items()}
    mix = mamba_mix if kind == "mamba" else attention_mix
    x = x + z["res"] * mix(rms_norm(x, w["ln1_w"], z["eps"]), w, z, prec)
    y = rms_norm(x, w["ln2_w"], z["eps"])
    return x + z["res"] * (
        moe(y, w, z, prec)
        + swiglu(y, w["s_gate"], w["s_up"], w["s_down"], prec))


# ---------------------------------------------------------------------
# serving: the gap of every served token
# ---------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _serve_programs(cfg_items: tuple, dtype: str, prec: str):
    cfg = {k: list(v) if isinstance(v, tuple) else v
           for k, v in cfg_items}
    z = sizes(cfg)
    dt = jnp.dtype(dtype)

    @jax.jit
    def embed(key, ids):
        return z["emb"] * jnp.take(
            outer_weights(cfg, key, dt)["wte"], ids,
            axis=0).astype(jnp.float32)

    def make_layer(kind):
        @functools.partial(jax.jit, donate_argnums=(2,))
        def layer(key, i, x):
            w = layer_weights(cfg, key, i, dt, kind)
            return jax.vmap(lambda r: block(r, w, z, kind, prec))(x)
        return layer

    layers = {kind: make_layer(kind) for kind in set(z["types"])}

    @jax.jit
    def head(key, x):
        """``[n, T, V]`` logits over the tied table, one sequence at a
        time so that one ``[T, V]`` product lives beside the result."""
        o = outer_weights(cfg, key, dt)
        wf = o["wte"].astype(jnp.float32).T
        lnf = o["lnf_w"].astype(jnp.float32)
        return jax.lax.map(
            lambda xr: mm(rms_norm(xr, lnf, z["eps"]), wf, prec)
            / z["lsc"], x)

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
    """The gaps of ``n`` sequences from their last hidden states, one
    sequence's ``[T, V]`` logits (and the control's) alive at a time."""
    cfg = {k: list(v) if isinstance(v, tuple) else v
           for k, v in cfg_items}
    z = sizes(cfg)
    dt = jnp.dtype(dtype)

    @jax.jit
    def gaps(key, x_ref, x_low, ids, lo, hi):
        o = outer_weights(cfg, key, dt)
        wf = o["wte"].astype(jnp.float32).T
        lnf = o["lnf_w"].astype(jnp.float32)
        pos = jnp.arange(ids.shape[1])

        def one(inp):
            xr, xl, row, a, b = inp
            ref = mm(rms_norm(xr, lnf, z["eps"]), wf, "highest") / z["lsc"]
            live = (pos >= a) & (pos < b)
            if control:
                # a control's choice is its own first, given the prefix
                tok = (mm(rms_norm(xl, lnf, z["eps"]), wf, control)
                       / z["lsc"]).argmax(-1)
            else:
                # the program's choice is the served token itself: the
                # token at p + 1 is what position p produced
                tok = jnp.concatenate([row[1:], row[:1]])
            got = jnp.take_along_axis(ref, tok[:, None], 1)[:, 0]
            gap = jnp.where(live, ref.max(-1) - got, 0.0)
            agree = jnp.where(live, ref.argmax(-1) == tok, False)
            return gap.max(), gap.sum(), agree.sum(), live.sum()

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
