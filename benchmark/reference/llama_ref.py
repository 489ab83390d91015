"""Plain float32 reference of the Llama/Mistral dense decoder.

Follows the published architecture (RMSNorm, rotary embeddings in the
rotate-half convention, grouped-query causal attention, SwiGLU, untied
head) in straightforward ``jax.numpy`` at float32 with
``Precision.HIGHEST``: no kernels, no cache, no batching tricks.  It
imports nothing of ``paddle_tpu`` and takes nothing the program made:
the weights are drawn HERE from the seed, and the harness hands the
program the same draw (``benchmark/programs/llama.py``).

Two entries decide ``correct``:

* :func:`served_gaps` — one forward over prompt + served tokens per
  sampled request; for every served token, how far its reference logit
  lies below the reference's best.
* :func:`train_steps` — three AdamW-free Adam steps as the train
  configuration states them (bf16 parameters rounded after every
  update, fp32 moments), layer by layer so that it fits beside nothing
  else on one 16 GB chip; returns each step's loss, the first
  gradient's norm per leaf and the parameters' change per leaf.

``prec="fp8"`` is the CONTROL, not a mode of the benchmark: the same
arithmetic with every matmul operand rounded to float8 (e4m3 forward,
e5m2 for the incoming gradient), the nearest precision below the
bfloat16 the configurations state.  It has to come out as not correct.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_NORM_LEAVES = ("ln1_w", "ln2_w", "lnf_w")


# ---------------------------------------------------------------------
# sizes and weights
# ---------------------------------------------------------------------
def sizes(cfg: dict) -> dict:
    """The numbers the equations need, from the published keys."""
    h = int(cfg["hidden_size"])
    nh = int(cfg["num_attention_heads"])
    return dict(H=h, F=int(cfg["intermediate_size"]), NH=nh,
                KVH=int(cfg.get("num_key_value_heads") or nh),
                D=int(cfg.get("head_dim") or h // nh),
                V=int(cfg["vocab_size"]),
                L=int(cfg["num_hidden_layers"]),
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]),
                std=float(cfg.get("initializer_range", 0.02)))


def layer_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    z = sizes(cfg)
    return {"ln1_w": (z["H"],), "ln2_w": (z["H"],),
            "q_w": (z["H"], z["NH"] * z["D"]),
            "k_w": (z["H"], z["KVH"] * z["D"]),
            "v_w": (z["H"], z["KVH"] * z["D"]),
            "o_w": (z["NH"] * z["D"], z["H"]),
            "gate_w": (z["H"], z["F"]), "up_w": (z["H"], z["F"]),
            "down_w": (z["F"], z["H"])}


def outer_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    z = sizes(cfg)
    return {"wte": (z["V"], z["H"]), "head": (z["H"], z["V"]),
            "lnf_w": (z["H"],)}


def param_count(cfg: dict, with_embedding: bool = True) -> int:
    n = sizes(cfg)["L"] * sum(math.prod(s)
                              for s in layer_shapes(cfg).values())
    out = outer_shapes(cfg)
    n += math.prod(out["head"]) + math.prod(out["lnf_w"])
    return n + (math.prod(out["wte"]) if with_embedding else 0)


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
    seed goes in as data, never as a constant of a compiled program: a
    program per seed would miss the compile cache in every run."""
    seed = int(seed)
    return _key_from_words(jnp.int32(seed & 0x7FFFFFFF),
                           jnp.int32(seed >> 31))


def _draw(key, name: str, shape, std: float, dtype):
    if name in _NORM_LEAVES:
        return jnp.ones(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def layer_weights(cfg: dict, key, i, dtype) -> Dict[str, jax.Array]:
    """Layer ``i``'s leaves (``i`` may be traced): N(0, std) in float32,
    rounded once to the served dtype; norm gains are ones."""
    z = sizes(cfg)
    lk = jax.random.fold_in(jax.random.fold_in(key, 1), i)
    return {n: _draw(jax.random.fold_in(lk, j), n, s, z["std"], dtype)
            for j, (n, s) in enumerate(layer_shapes(cfg).items())}


def outer_weights(cfg: dict, key, dtype) -> Dict[str, jax.Array]:
    z = sizes(cfg)
    ok = jax.random.fold_in(key, 2)
    return {n: _draw(jax.random.fold_in(ok, j), n, s, z["std"], dtype)
            for j, (n, s) in enumerate(outer_shapes(cfg).items())}


# ---------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------
E4M3, E5M2 = jnp.float8_e4m3fn, jnp.float8_e5m2


def _q(x, dt):
    """Round to float8 with one scale per tensor (the usual recipe)."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / float(jnp.finfo(dt).max), 1.0)
    return (x / s).astype(dt).astype(jnp.float32) * s


def _q_ste(x):
    return x + jax.lax.stop_gradient(_q(x, E4M3) - x)


@jax.custom_vjp
def _fp8_mm(a, b):
    return jnp.matmul(_q(a, E4M3), _q(b, E4M3), precision=HIGHEST)


def _fp8_mm_fwd(a, b):
    qa, qb = _q(a, E4M3), _q(b, E4M3)
    return jnp.matmul(qa, qb, precision=HIGHEST), (qa, qb)


def _fp8_mm_bwd(res, g):
    qa, qb = res
    qg = _q(g, E5M2)
    da = jnp.matmul(qg, qb.T, precision=HIGHEST)
    db = jnp.matmul(qa.reshape(-1, qa.shape[-1]).T,
                    qg.reshape(-1, qg.shape[-1]), precision=HIGHEST)
    return da, db


_fp8_mm.defvjp(_fp8_mm_fwd, _fp8_mm_bwd)


def mm(a, b, prec: str):
    if prec == "highest":
        return jnp.matmul(a, b, precision=HIGHEST)
    if prec == "fp8":
        return _fp8_mm(a, b)
    raise ValueError(f"unknown precision {prec!r}")


def rms_norm(x, w, eps: float):
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * w


def rope_tables(n: int, d: int, theta: float):
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    fr = jnp.outer(jnp.arange(n, dtype=jnp.float32), inv)
    emb = jnp.concatenate([fr, fr], -1)
    return jnp.cos(emb), jnp.sin(emb)


def _rot(x):
    d = x.shape[-1] // 2
    return jnp.concatenate([-x[..., d:], x[..., :d]], -1)


def block(x, w, z: dict, prec: str):
    """One decoder layer on ONE sequence ``x [T, H]`` (float32)."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    T = x.shape[0]
    cos, sin = rope_tables(T, z["D"], z["theta"])
    cos, sin = cos[:, None, :], sin[:, None, :]
    h = rms_norm(x, w["ln1_w"], z["eps"])
    q = mm(h, w["q_w"], prec).reshape(T, z["NH"], z["D"])
    k = mm(h, w["k_w"], prec).reshape(T, z["KVH"], z["D"])
    v = mm(h, w["v_w"], prec).reshape(T, z["KVH"], z["D"])
    q = q * cos + _rot(q) * sin
    k = k * cos + _rot(k) * sin
    rep = z["NH"] // z["KVH"]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    if prec == "fp8":
        q, k, v = _q_ste(q), _q_ste(k), _q_ste(v)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        / math.sqrt(z["D"])
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    if prec == "fp8":
        p = _q_ste(p)
    a = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
    x = x + mm(a.reshape(T, -1), w["o_w"], prec)
    h = rms_norm(x, w["ln2_w"], z["eps"])
    y = jax.nn.silu(mm(h, w["gate_w"], prec)) * mm(h, w["up_w"], prec)
    return x + mm(y, w["down_w"], prec)


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

    @functools.partial(jax.jit, donate_argnums=(2,))
    def layer(key, i, x):
        w = layer_weights(cfg, key, i, dt)
        return jax.lax.map(lambda r: block(r, w, z, prec), x)

    @jax.jit
    def head(key, x):
        """``[n, T, V]`` logits, one sequence at a time so that one
        ``[T, V]`` product is what lives beside the result."""
        o = outer_weights(cfg, key, dt)
        wf = o["head"].astype(jnp.float32)
        lnf = o["lnf_w"].astype(jnp.float32)
        return jax.lax.map(
            lambda xr: mm(rms_norm(xr, lnf, z["eps"]), wf, prec), x)

    return embed, layer, head


def reference_logits(cfg: dict, seed: int, ids: np.ndarray, dtype: str,
                     prec: str = "highest"):
    """``[n, T, V]`` float32 logits of ``ids [n, T]`` (padded at the
    end; causal attention makes the padding harmless).  Layer by layer,
    each layer's weights drawn again from the seed and dropped."""
    embed, layer, head = _serve_programs(_items(cfg), dtype, prec)
    key = seed_key(seed)
    jids = jnp.asarray(ids, jnp.int32)
    x = embed(key, jids)
    for i in range(sizes(cfg)["L"]):
        x = layer(key, jnp.int32(i), x)
    return head(key, x)


@jax.jit
def _gap_reduce(ref_logits, ids, lo, hi, choice_logits):
    """``ref_logits [n,T,V]``; token at ``p + 1`` is what position ``p``
    produced.  ``choice_logits`` ranks the candidates: the program's
    choice is the served token itself (pass ``None``), a control's is
    its own argmax."""
    n, T, _ = ref_logits.shape
    pos = jnp.arange(T)[None, :]
    live = (pos >= lo[:, None]) & (pos < hi[:, None])
    best = ref_logits.max(-1)
    if choice_logits is None:
        tok = jnp.concatenate([ids[:, 1:], ids[:, :1]], 1)
    else:
        tok = choice_logits.argmax(-1)
    got = jnp.take_along_axis(ref_logits, tok[..., None], 2)[..., 0]
    gap = jnp.where(live, best - got, 0.0)
    agree = jnp.where(live, ref_logits.argmax(-1) == tok, False)
    return gap.max(), gap.sum() / live.sum(), agree.sum(), live.sum()


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
    ref = reference_logits(cfg, seed, ids, dtype)
    low = reference_logits(cfg, seed, ids, dtype, control) \
        if control else None
    widest, mean, agree, count = _gap_reduce(
        ref, jnp.asarray(ids), lo, hi, low)
    return {"widest_gap": float(widest), "mean_gap": float(mean),
            "agree": int(agree), "tokens": int(count)}


# ---------------------------------------------------------------------
# training: three steps, layer by layer
# ---------------------------------------------------------------------
def _adam(p, g, m, v, t, lr, b1, b2, eps):
    m2 = b1 * m + (1 - b1) * g
    v2 = b2 * v + (1 - b2) * g * g
    upd = (m2 / (1 - b1 ** t)) / (jnp.sqrt(v2 / (1 - b2 ** t)) + eps)
    return (p.astype(jnp.float32) - lr * upd).astype(p.dtype), m2, v2


@functools.lru_cache(maxsize=None)
def _train_programs(cfg_items: tuple, prec: str, head_rows: int):
    cfg = dict(cfg_items)
    z = sizes(cfg)

    @jax.jit
    def layer_fwd(w, x):                       # x [b, T, H]
        return jax.lax.map(lambda r: block(r, w, z, prec), x)

    @jax.jit
    def layer_bwd(w, x, dy):
        """Gradients of one layer, one row of the batch at a time."""
        def row(carry, xs):
            xr, dyr = xs
            _, vjp = jax.vjp(lambda ww, xx: block(xx, ww, z, prec),
                             w, xr)
            dw, dx = vjp(dyr)
            return jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32), carry, dw), dx
        zero = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), w)
        dw, dx = jax.lax.scan(row, zero, (x, dy))
        return dw, dx

    @jax.jit
    def head_loss(outer, x, labels):
        """Mean NLL over every token and its gradients, in blocks of
        ``head_rows`` rows so that ``[rows, V]`` is what lives."""
        b, T, H = x.shape
        n = b * T
        xf = x.reshape(n // head_rows, head_rows, H)
        lab = labels.reshape(n // head_rows, head_rows)

        def nll(lnf, wh, xr, lr_):
            lg = mm(rms_norm(xr, lnf.astype(jnp.float32), z["eps"]),
                    wh.astype(jnp.float32), prec)
            lse = jax.nn.logsumexp(lg, -1)
            return jnp.sum(lse - jnp.take_along_axis(
                lg, lr_[:, None], 1)[:, 0]) / n

        def blk(carry, xs):
            xr, lr_ = xs
            val, (dl, dh, dxr) = jax.value_and_grad(
                nll, argnums=(0, 1, 2))(outer["lnf_w"], outer["head"],
                                        xr, lr_)
            tot, gl, gh = carry
            return (tot + val, gl + dl.astype(jnp.float32),
                    gh + dh.astype(jnp.float32)), dxr
        zero = (jnp.float32(0), jnp.zeros((H,), jnp.float32),
                jnp.zeros(outer["head"].shape, jnp.float32))
        (loss, gl, gh), dx = jax.lax.scan(blk, zero, (xf, lab))
        return loss, gl, gh, dx.reshape(b, T, H)

    @jax.jit
    def embed(wte, ids):
        return jnp.take(wte, ids, axis=0).astype(jnp.float32)

    @jax.jit
    def embed_grad(wte, ids, dx):
        return jnp.zeros(wte.shape, jnp.float32).at[ids.reshape(-1)].add(
            dx.reshape(-1, dx.shape[-1]))

    return layer_fwd, layer_bwd, head_loss, embed, embed_grad


def _norm(a) -> float:
    return float(jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))))


def train_steps(cfg: dict, seed: int,
                batches: List[Tuple[np.ndarray, np.ndarray]], *,
                dtype: str, lr: float, betas=(0.9, 0.95),
                eps: float = 1e-8, prec: str = "highest") -> dict:
    """Follow ``len(batches)`` Adam steps from the seed's weights.

    Parameters are kept in ``dtype`` and rounded to it after every
    update, moments in float32 — the state the configuration states.
    Returns ``losses`` (one a step), ``grad_norm`` (the first step's
    gradient, per leaf), ``delta_norm`` (the parameters' change over
    all the steps, per leaf); leaves are named ``<layer>.<name>`` and
    the three outer ones by their name."""
    z = sizes(cfg)
    dt = jnp.dtype(dtype)
    key = seed_key(seed)
    b1, b2 = betas
    layer_fwd, layer_bwd, head_loss, embed, embed_grad = _train_programs(
        _items(cfg), prec, min(2048, batches[0][0].size))
    draw_layer = functools.partial(
        jax.jit(lambda k, i: layer_weights(cfg, k, i, dt)), key)
    draw_outer = functools.partial(
        jax.jit(lambda k: outer_weights(cfg, k, dt)), key)
    adam = jax.jit(functools.partial(_adam, lr=lr, b1=b1, b2=b2, eps=eps),
                   donate_argnums=(0, 2, 3))
    zeros = lambda tree: jax.tree.map(
        lambda a: jnp.zeros(a.shape, jnp.float32), tree)
    layers = [draw_layer(jnp.int32(i)) for i in range(z["L"])]
    outer = draw_outer()
    m_l, v_l = [zeros(w) for w in layers], [zeros(w) for w in layers]
    m_o, v_o = zeros(outer), zeros(outer)
    losses, grad_norm = [], {}

    def update(tree, grads, m, v, t, prefix):
        for name in list(tree):
            if t == 1:
                grad_norm[prefix + name] = _norm(grads[name])
            tree[name], m[name], v[name] = adam(
                tree[name], grads[name], m[name], v[name],
                jnp.float32(t))

    for t, (ids, labels) in enumerate(batches, 1):
        jids = jnp.asarray(ids, jnp.int32)
        xs = [embed(outer["wte"], jids)]
        for w in layers:
            xs.append(layer_fwd(w, xs[-1]))
        loss, g_lnf, g_head, dx = head_loss(
            outer, xs.pop(), jnp.asarray(labels, jnp.int32))
        losses.append(float(loss))
        for i in reversed(range(z["L"])):
            dw, dx = layer_bwd(layers[i], xs.pop(), dx)
            update(layers[i], dw, m_l[i], v_l[i], t, f"{i}.")
            del dw
        g_wte = embed_grad(outer["wte"], jids, dx)
        update(outer, {"wte": g_wte, "head": g_head, "lnf_w": g_lnf},
               m_o, v_o, t, "")
        del g_wte, g_head, dx
    delta = {}
    for i, w in enumerate(layers):
        w0 = draw_layer(jnp.int32(i))
        for name in w:
            delta[f"{i}.{name}"] = _norm(
                w[name].astype(jnp.float32) - w0[name].astype(jnp.float32))
    o0 = draw_outer()
    for name in outer:
        delta[name] = _norm(outer[name].astype(jnp.float32)
                            - o0[name].astype(jnp.float32))
    return {"losses": losses, "grad_norm": grad_norm,
            "delta_norm": delta}
