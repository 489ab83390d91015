"""State-space (Mamba-2 / SSD) sequence ops with a carried state.

Three ops, each a function of ``(inputs, state in) -> (outputs, state
out)`` so that a serving engine can hand a sequence's state from one
prefill chunk to the next and from the prefill to the decode step:

* :func:`causal_conv` — depthwise causal convolution over the last
  ``W`` positions, with the ``W - 1`` columns before the chunk carried
  as a *tail*;
* :func:`ssd_chunk_scan` — the selective state-space recurrence

      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,   y_t = S_t C_t + D x_t

  computed chunk by chunk (the SSD form of Mamba-2: inside a chunk a
  masked, decay-weighted ``C B^T`` product, between chunks the state);
* :func:`ssm_state_update` — one step of the same recurrence, the
  decode form; :func:`ssm_state_update_row` — the same against one
  layer's row of the engine's ``[L, B, nh, P, N]`` state array, in
  place (on a TPU the Pallas kernel ``ssm_state_update``, which passes
  the state once).

Padding contract (a bucketed chunk holds ``valid <= T`` real tokens):
the caller zeroes ``dt`` at the padded positions, which leaves the state
exactly as it was (``exp(0) = 1`` and the input term vanishes), and
passes ``valid`` to :func:`causal_conv`, whose new tail is then the last
``W - 1`` VALID columns.  Outputs at padded positions are never read.

These are the XLA reference tier (the bit anchor; the one Pallas tier
is ``ops/pallas/ssm.py``); the state is float32 whatever the served
dtype.  Shapes: ``x [B, T, nh, P]``, ``dt [B, T,
nh]`` (after softplus), ``A, D [nh]``, ``Bm, Cm [B, T, G, N]`` (``G``
groups of ``nh / G`` heads share one B and C), state ``[B, nh, P, N]``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["causal_conv", "causal_conv_rows", "causal_conv_step",
           "ssd_chunk_scan", "ssm_state_update",
           "ssm_state_update_row", "ssm_state_update_tier",
           "ssm_recurrence"]

F32 = jnp.float32


def causal_conv(x, tail, w, b, valid=None):
    """Depthwise causal convolution with a carried tail.

    ``x [B, T, C]``: the chunk; ``tail [B, C, W - 1]``: the ``W - 1``
    inputs before it (zeros at a sequence's start); ``w [C, W]`` (``w[:,
    W - 1]`` multiplies the current position), ``b [C]`` or None (no
    bias).  Returns ``(y [B, T, C], new tail)``.  With ``valid`` (traced
    scalar or ``[B]``)
    the new tail holds the last ``W - 1`` inputs before position
    ``valid``; without it, before position ``T``."""
    y, new = causal_conv_rows(x, jnp.swapaxes(tail, 1, 2), w, b, valid)
    return y, jnp.swapaxes(new, 1, 2)


def causal_conv_rows(x, tail, w, b, valid=None):
    """:func:`causal_conv` with the tail held as ROWS, ``[B, W - 1,
    C]``: the channels on the minor axis, as ``x`` has them (a state
    array ``[.., C, 3]`` has three values on the lanes)."""
    B, T, C = x.shape
    W = w.shape[1]
    full = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    y = 0.0 if b is None else b.astype(F32)           # [B, W-1+T, C]
    for k in range(W):
        y = y + full[:, k:k + T].astype(F32) * w[:, k].astype(F32)
    if valid is None:
        new = full[:, T:]
    else:
        v = jnp.broadcast_to(jnp.asarray(valid, jnp.int32), (B,))
        new = jax.vmap(lambda f, s: jax.lax.dynamic_slice_in_dim(
            f, s, W - 1, axis=0))(full, v)
    return y.astype(x.dtype), new.astype(tail.dtype)


def causal_conv_step(x, tail, w, b):
    """One position of :func:`causal_conv_rows`, every array 2-D: ``x
    [B, C]``, ``tail [B, (W - 1) C]`` (the last ``W - 1`` inputs one
    after the other, the oldest first).  Returns ``(y [B, C], new
    tail)``.  What a decode step wants: slices and a concatenation along
    the lanes, no axis of ``W - 1`` for a layout to pad."""
    C = x.shape[-1]
    W = w.shape[1]
    taps = [tail[:, k * C:(k + 1) * C] for k in range(W - 1)] + [x]
    y = 0.0 if b is None else b.astype(F32)
    for k, t in enumerate(taps):
        y = y + t.astype(F32) * w[:, k].astype(F32)
    return y.astype(x.dtype), jnp.concatenate(taps[1:], axis=1).astype(
        tail.dtype)


def _heads(m, nh: int):
    """``[..., G, N]`` -> ``[..., nh, N]``: each group's B / C for the
    heads that share it."""
    G = m.shape[-2]
    return m if G == nh else jnp.repeat(m, nh // G, axis=-2)


def ssm_state_update(x, dt, A, Bm, Cm, D, state):
    """One token a sequence: ``x [B, nh, P]``, ``dt [B, nh]``, ``Bm, Cm
    [B, G, N]``, ``state [B, nh, P, N]`` float32.  Returns ``(y [B, nh,
    P] in x's dtype, new state)``."""
    nh = x.shape[1]
    x32, dt = x.astype(F32), dt.astype(F32)
    Bh, Ch = _heads(Bm.astype(F32), nh), _heads(Cm.astype(F32), nh)
    decay = jnp.exp(dt * A.astype(F32))                       # [B, nh]
    new = state * decay[..., None, None] \
        + (dt[..., None] * x32)[..., None] * Bh[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", new, Ch) \
        + D.astype(F32)[None, :, None] * x32
    return y.astype(x.dtype), new


def ssm_state_update_tier(state_shape, groups: int):
    """``(tier, reason)``: the tier ``ssm_state_update_row(backend=
    None)`` runs on — ``"pallas"`` on a TPU when the geometry fits the
    kernel, else ``"xla"`` and why."""
    from ..core.device import on_tpu
    from ..core.flags import FLAGS
    from .pallas.ssm import unsupported_reason
    if not (on_tpu() or FLAGS.pallas_force_compile):
        return "xla", "not on a TPU"
    reason = unsupported_reason(state_shape, groups)
    return ("xla", reason) if reason else ("pallas", None)


def ssm_state_update_row(x, dt, A, Bm, Cm, D, states, row, *,
                         backend: Optional[str] = None):
    """:func:`ssm_state_update` against ``states[row]`` of ``states [L,
    B, nh, P, N]`` float32 (``row`` a traced scalar): returns ``(y,
    states)`` with that row stepped once and every other row as it was.
    ``backend``: ``"xla"`` the per-op chain (the bit anchor), ``"pallas"``
    the one-pass kernel, ``None`` as :func:`ssm_state_update_tier`
    says."""
    if backend is None:
        backend = ssm_state_update_tier(states.shape, Bm.shape[1])[0]
    if backend == "pallas":
        from .pallas.ssm import ssm_state_update_rows
        return ssm_state_update_rows(x, dt, A, Bm, Cm, D, states, row)
    y, new = ssm_state_update(
        x, dt, A, Bm, Cm, D,
        jax.lax.dynamic_index_in_dim(states, row, 0, keepdims=False))
    return y, jax.lax.dynamic_update_index_in_dim(states, new, row, 0)


def ssm_recurrence(x, dt, A, Bm, Cm, D, state):
    """The recurrence token by token (``lax.scan`` over time of
    :func:`ssm_state_update`): what :func:`ssd_chunk_scan` must equal.
    Same arguments and results as :func:`ssd_chunk_scan`."""
    def step(s, inp):
        xt, dtt, bt, ct = inp
        y, s = ssm_state_update(xt, dtt, A, bt, ct, D, s)
        return s, y

    mv = lambda a: jnp.moveaxis(a, 1, 0)
    state, ys = jax.lax.scan(step, state.astype(F32),
                             (mv(x), mv(dt), mv(Bm), mv(Cm)))
    return jnp.moveaxis(ys, 0, 1), state


def ssd_chunk_scan(x, dt, A, Bm, Cm, D, state, *, chunk: int = 256
                   ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over ``T`` tokens in chunks of ``chunk`` with the
    state handed from chunk to chunk.  ``state`` is the state BEFORE the
    first token; returns ``(y [B, T, nh, P] in x's dtype, state after
    the last token)``.  A length that is no multiple of ``chunk`` is
    padded with ``dt = 0`` (no change of state)."""
    Bsz, T, nh, P = x.shape
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        padt = lambda a: jnp.pad(a, [(0, 0), (0, pad)]
                                 + [(0, 0)] * (a.ndim - 2))
        x, dt, Bm, Cm = padt(x), padt(dt), padt(Bm), padt(Cm)
    nc = (T + pad) // Q
    A32, D32 = A.astype(F32), D.astype(F32)

    def chunks(a):                       # [B, nc*Q, ...] -> [nc, B, Q, ...]
        return jnp.moveaxis(a.reshape(Bsz, nc, Q, *a.shape[2:]), 1, 0)

    tri = jnp.tril(jnp.ones((Q, Q), bool))

    def one(s, inp):
        xc, dtc, bc, cc = inp            # [B,Q,nh,P] [B,Q,nh] [B,Q,G,N]
        xc, dtc = xc.astype(F32), dtc.astype(F32)
        bh, ch = _heads(bc.astype(F32), nh), _heads(cc.astype(F32), nh)
        cum = jnp.cumsum(dtc * A32, axis=1)               # [B,Q,nh] <= 0
        xdt = xc * dtc[..., None]
        # inside the chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) C_i.B_j
        #                                     dt_j x_j
        seg = cum[:, :, None, :] - cum[:, None, :, :]     # [B,i,j,nh]
        L = jnp.where(tri[None, :, :, None], jnp.exp(
            jnp.where(tri[None, :, :, None], seg, 0.0)), 0.0)
        cb = jnp.einsum("bign,bjgn->bijg", cc.astype(F32),
                        bc.astype(F32))           # a group's C_i.B_j
        cb = cb if cb.shape[-1] == nh else jnp.repeat(
            cb, nh // cb.shape[-1], axis=-1)
        y = jnp.einsum("bijh,bjhp->bihp", L * cb, xdt)
        # from the state before the chunk
        y = y + jnp.einsum("bihn,bhpn->bihp", ch, s) \
            * jnp.exp(cum)[..., None]
        # the state after the chunk
        last = cum[:, -1]                                  # [B,nh]
        w = jnp.exp(last[:, None, :] - cum)               # [B,Q,nh]
        s = s * jnp.exp(last)[..., None, None] \
            + jnp.einsum("bjhp,bjhn->bhpn", xdt * w[..., None], bh)
        return s, y + D32[None, None, :, None] * xc

    state, ys = jax.lax.scan(
        one, state.astype(F32),
        (chunks(x), chunks(dt), chunks(Bm), chunks(Cm)))
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, nc * Q, nh, P)[:, :T]
    return y.astype(x.dtype), state
