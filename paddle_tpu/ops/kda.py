"""Kimi Delta Attention (KDA, arXiv:2510.26692): a linear-attention
layer whose per-head state is a MATRIX, decayed a channel at a time and
corrected by a rank-one delta rule a token.

One head, one token (``S [K, V]`` float32; ``q, k [K]``, ``v [V]``;
``a = exp(log_a) [K]`` in (0, 1], a decay a CHANNEL of the key;
``beta`` a scalar in (0, 1))::

    S <- Diag(a) S
    S <- S + beta k (v - S^T k)^T
    o  = S^T q

i.e. ``S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t
v_t^T``.  Three ops, each ``(inputs, state in) -> (outputs, state out)``
so that a serving engine hands a sequence's state from one prefill chunk
to the next and from the prefill to the decode step (the contract of
``ops/ssm.py``):

* :func:`kda_recurrence` — the three lines above, a token at a time:
  the oracle for the other two;
* :func:`kda_chunk_scan` — the same over ``T`` tokens in chunks.  With
  ``G_t`` the cumulative log decay inside a chunk and ``u_t = beta_t
  (v_t - (Diag(a_t) S_{t-1})^T k_t)`` the correction a token writes,
  ``S_t = Diag(e^{G_t}) S_0 + sum_{j<=t} Diag(e^{G_t - G_j}) k_j u_j^T``,
  so the ``u`` of a chunk solve ONE unit lower-triangular system ``(I +
  A) U = beta (V - (K e^G) S_0)``, ``A[t, j] = beta_t sum_c k_t[c]
  k_j[c] e^{G_t[c] - G_j[c]}`` (``j < t``), and ``O = (Q e^G) S_0 + B
  U`` with ``B`` the same sum over ``q_t`` (``j <= t``);
* :func:`kda_state_update` — one token a sequence, the decode form;
  :func:`kda_state_update_row` — the same against one layer's row of the
  engine's ``[L, B, nh, K, V]`` state array, in place (on a TPU the
  Pallas kernel ``kda_state_update``, which passes the state once).

**No cumulative decay is ever divided by.**  A channel's log decay may
be -5 a token (``kda_lower_bound``): 64 tokens reach ``e^-320``, which
float32 does not hold.  Every exponent here is a DIFFERENCE of
cumulative logs taken first, ``G_t - G_j`` with ``t >= j``, never
positive.  The pairwise tensor ``e^{G_t[c] - G_j[c]}`` is ``[C, C, K]``
a head and no matmul; it is formed only inside sub-chunks of
``SUB_CHUNK`` tokens (the diagonal blocks), and a pair in DIFFERENT
sub-chunks goes through the later one's first position ``r``: ``G_t -
G_j = (G_t - G_r) + (G_r - G_j)``, both non-positive, each folded into
its own operand of a plain matmul.

Padding contract (a bucketed chunk holds ``valid <= T`` real tokens):
the caller zeroes ``log_a`` AND ``beta`` at the padded positions, which
leaves the state exactly as it was.  Outputs at padded positions are
never read.

These are the XLA tier (float32 throughout, every product at
``Precision.HIGHEST``: the state is the one thing a sequence carries for
thousands of tokens); the one Pallas tier is ``ops/pallas/kda.py``.
Shapes: ``q, k, log_a [B, T, nh, K]``, ``v [B, T, nh, V]``, ``beta [B,
T, nh]``, state ``[B, nh, K, V]``; outputs float32.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["CHUNK", "SUB_CHUNK", "chunk_sizes", "kda_chunk_scan",
           "kda_recurrence", "kda_state_update", "kda_state_update_row",
           "kda_state_update_tier"]

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
#: tokens a chunk at the most: one triangular system and one hand-over
#: of the state a chunk
CHUNK = 64
#: tokens a sub-chunk: the pairwise decays are formed inside one only
#: (``[T / s, s, s, K]`` a head: 16.8 MB for 32 heads of 128 at 16)
SUB_CHUNK = 16


def chunk_sizes(T: int) -> Tuple[int, int]:
    """``(chunk, sub_chunk)`` for ``T`` tokens: the largest sizes up to
    ``CHUNK`` / ``SUB_CHUNK`` with ``sub_chunk | chunk``; a short
    sequence is one chunk of whole sub-chunks."""
    sub = min(SUB_CHUNK, T)
    return min(CHUNK, -(-T // sub) * sub), sub


def kda_state_update(q, k, v, log_a, beta, state):
    """One token a sequence: ``q, k, log_a [B, nh, K]``, ``v [B, nh,
    V]``, ``beta [B, nh]``, ``state [B, nh, K, V]`` float32.  Returns
    ``(o [B, nh, V] float32, new state)``."""
    q, k, v = q.astype(F32), k.astype(F32), v.astype(F32)
    sd = state * jnp.exp(log_a.astype(F32))[..., None]
    r = v - jnp.sum(sd * k[..., None], axis=-2)
    new = sd + (beta.astype(F32)[..., None] * k)[..., None] \
        * r[..., None, :]
    return jnp.sum(new * q[..., None], axis=-2), new


def kda_state_update_tier(state_shape):
    """``(tier, reason)``: the tier ``kda_state_update_row(backend=
    None)`` runs on — ``"pallas"`` on a TPU when the geometry fits the
    kernel, else ``"xla"`` and why."""
    from ..core.device import on_tpu
    from ..core.flags import FLAGS
    from .pallas.kda import unsupported_reason
    if not (on_tpu() or FLAGS.pallas_force_compile):
        return "xla", "not on a TPU"
    reason = unsupported_reason(state_shape)
    return ("xla", reason) if reason else ("pallas", None)


def kda_state_update_row(q, k, v, log_a, beta, states, row, *,
                         backend: Optional[str] = None):
    """:func:`kda_state_update` against ``states[row]`` of ``states [L,
    B, nh, K, V]`` float32 (``row`` a traced scalar): returns ``(o,
    states)`` with that row stepped once and every other row as it was.
    ``backend``: ``"xla"`` the per-op chain (the bit anchor), ``"pallas"``
    the one-pass kernel, ``None`` as :func:`kda_state_update_tier`
    says."""
    if backend is None:
        backend = kda_state_update_tier(states.shape)[0]
    with jax.named_scope("kda_state_update"):
        if backend == "pallas":
            from .pallas.kda import kda_state_update_rows
            return kda_state_update_rows(q, k, v, log_a, beta, states, row)
        o, new = kda_state_update(
            q, k, v, log_a, beta,
            jax.lax.dynamic_index_in_dim(states, row, 0, keepdims=False))
        return o, jax.lax.dynamic_update_index_in_dim(states, new, row, 0)


def kda_recurrence(q, k, v, log_a, beta, state):
    """The recurrence token by token (``lax.scan`` over time of
    :func:`kda_state_update`): what :func:`kda_chunk_scan` must equal.
    Same arguments and results."""
    def step(s, inp):
        o, s = kda_state_update(*inp, s)
        return s, o

    mv = lambda a: jnp.moveaxis(a, 1, 0)
    state, os_ = jax.lax.scan(
        step, state.astype(F32),
        (mv(q), mv(k), mv(v), mv(log_a), mv(beta)))
    return jnp.moveaxis(os_, 0, 1), state


def _pair_scores(x, k, G, g, sub: int):
    """``M[..., t, j] = sum_c x[t, c] k[j, c] exp(G[t, c] - G[j, c])``
    for ``j <= t`` (0 elsewhere) of one chunk: ``x [X, ..., C, K]`` (a
    leading axis of operands that share ``k``), ``k, G, g [..., C, K]``,
    ``G`` the inclusive cumulative sum of the log decays ``g <= 0``
    along ``C``.  Diagonal sub-chunks pairwise, the others as matmuls
    through the later sub-chunk's first position (module docstring)."""
    C, K = k.shape[-2:]
    n = C // sub
    lead = k.shape[:-2]
    blocks = lambda a: a.reshape(a.shape[:-2] + (n, sub, K))
    xb, kb, Gb = blocks(x), blocks(k), blocks(G)
    # the cumulative log BEFORE a sub-chunk's first token
    R = Gb[..., 0, :] - blocks(g)[..., 0, :]                 # [.., n, K]
    # a pair in different sub-chunks: x_t e^(G_t - R_a) . k_j e^(R_a - G_j)
    xq = xb * jnp.exp(Gb - R[..., None, :])
    kk = k[..., None, :, :] * jnp.exp(jnp.minimum(
        R[..., :, None, :] - G[..., None, :, :], 0.0))       # [.., n, C, K]
    off = jnp.einsum("x...aik,...ajk->x...aij", xq, kk, precision=HIGHEST)
    earlier = (jnp.arange(C) // sub)[None, None, :] \
        < jnp.arange(n)[:, None, None]                       # [n, 1, C]
    off = jnp.where(earlier, off, 0.0).reshape(
        x.shape[:1] + lead + (n, sub, n, sub))
    # a pair in one sub-chunk: the pairwise decays themselves
    tri = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    D = jnp.where(tri, jnp.exp(jnp.where(
        tri, Gb[..., :, None, :] - Gb[..., None, :, :], 0.0)), 0.0)
    diag = jnp.einsum("x...aik,...alk,...ailk->x...ail", xb, kb, D,
                      precision=HIGHEST)
    eye = jnp.eye(n, dtype=F32)[:, None, :, None]
    return (off + diag[..., :, :, None, :] * eye).reshape(
        x.shape[:1] + lead + (C, C))


def kda_chunk_scan(q, k, v, log_a, beta, state, *,
                   chunk: Optional[int] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over ``T`` tokens in chunks with the state handed
    from chunk to chunk.  ``state`` is the state BEFORE the first token;
    returns ``(o [B, T, nh, V] float32, state after the last token)``.
    ``chunk`` (tokens, a multiple of its sub-chunk) is read from the
    shape when None (:func:`chunk_sizes`); a length that is no multiple
    of it is padded with ``log_a = 0, beta = 0`` (no change of
    state)."""
    Bsz, T, nh, K = q.shape
    V = v.shape[-1]
    C, sub = chunk_sizes(T) if chunk is None \
        else (chunk, min(SUB_CHUNK, chunk))
    if C % sub:
        raise ValueError(f"a chunk of {C} is not whole sub-chunks of {sub}")
    pad = -T % C
    nc = (T + pad) // C

    def chunks(a):          # [B, T, nh, ...] -> [nc, B, nh, C, ...]
        a = a.astype(F32)
        if pad:
            a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        a = a.reshape((Bsz, nc, C) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 2, 3)

    eye = jnp.eye(C, dtype=F32)

    def one(s, inp):
        qc, kc, vc, gc, bc = inp     # [B,nh,C,K] x2, [..,C,V], [..,C,K], [..,C]
        G = jnp.cumsum(gc, axis=-2)                          # <= 0
        eG = jnp.exp(G)
        Bm, A = _pair_scores(jnp.stack([qc, kc]), kc, G, gc, sub)
        A = jnp.tril(A, -1) * bc[..., None]
        rhs = bc[..., None] * (vc - jnp.einsum(
            "bhtk,bhkv->bhtv", kc * eG, s, precision=HIGHEST))
        # precision: solve_triangular's inner products follow the default
        with jax.default_matmul_precision("highest"):
            U = jax.scipy.linalg.solve_triangular(
                eye + A, rhs, lower=True, unit_diagonal=True)
        o = jnp.einsum("bhtk,bhkv->bhtv", qc * eG, s, precision=HIGHEST) \
            + jnp.einsum("bhtj,bhjv->bhtv", Bm, U, precision=HIGHEST)
        last = G[..., -1:, :]                                # [B,nh,1,K]
        s = s * jnp.swapaxes(jnp.exp(last), -1, -2) + jnp.einsum(
            "bhtk,bhtv->bhkv", kc * jnp.exp(last - G), U,
            precision=HIGHEST)
        return s, o

    with jax.named_scope("kda_chunk_scan"):
        state, os_ = jax.lax.scan(
            one, state.astype(F32),
            (chunks(q), chunks(k), chunks(v), chunks(log_a),
             chunks(beta[..., None])[..., 0]))
    # [nc, B, nh, C, V] -> [B, T, nh, V]
    o = jnp.moveaxis(os_, 0, 1).transpose(0, 1, 3, 2, 4).reshape(
        Bsz, nc * C, nh, V)[:, :T]
    return o, state
