"""``ssm_state_update``: one step of the Mamba-2 recurrence for every
decode slot, against ONE layer's rows of the engine's state array, in
place and in one pass.

    S' = exp(dt A) S + (dt x) B^T        y = S' C

The state ``[L, B, nh, P, N]`` float32 is the largest thing a hybrid's
decode step touches after its weights (4 MiB a slot a layer at the
published widths).  The per-op tier reads it twice a layer (XLA makes
one fusion for ``y``, recomputing ``S'``, and one for the update in
place); this kernel reads each ``[hb, P, N]`` block once, writes it
once (the output aliases the input: rows of other layers are not
touched) and leaves ``y`` beside it.  Its name is what a trace finds.

Layout inside a block: ``N`` lies on the lanes, ``P`` on the sublanes.
``dt x`` and the decay are per ``(head, p)`` and per head: the wrapper
hands them over as ``[B, nh / hb, P, hb]`` (heads on the lanes), so that
a head's column ``[:, j:j+1]`` broadcasts along the lanes with no
relayout; ``y`` comes back in the same layout.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...analysis.kernel import cost
from .common import use_interpret

__all__ = ["ssm_state_update_rows", "unsupported_reason"]

F32 = jnp.float32
#: a block of state goes in and comes out, each double-buffered: those
#: four take a third of the kernel budget (1 MiB a block: 32 heads of
#: 64 x 128 float32 at the published widths)
BLOCK_BYTES = cost.budget_bytes() // 12


def _heads_per_block(nh: int, G: int, P: int, N: int) -> int:
    """The most heads a block that lie within ONE group's heads and
    within ``BLOCK_BYTES`` (one head where a head alone is larger)."""
    per_group = nh // G
    return next(hb for hb in (32, 16, 8, 4, 2, 1) if per_group % hb == 0
                and (hb * P * N * 4 <= BLOCK_BYTES or hb == 1))


def unsupported_reason(state_shape, G: int) -> Optional[str]:
    """Why the Mosaic kernel cannot take this geometry, or None."""
    _, _, nh, P, N = state_shape
    if N % 128:
        return f"state width {N} is no multiple of the 128 lanes"
    if P % 8:
        return f"head width {P} is no multiple of the 8 sublanes"
    if nh % G:
        return f"{G} groups do not divide {nh} heads"
    return None


def _kernel(row_ref, s_ref, xdt_ref, dec_ref, b_ref, c_ref,
            o_ref, y_ref, *, hb: int):
    del row_ref                                   # used by the index maps
    bv = b_ref[...].astype(F32)                   # [1, N]
    cv = c_ref[...].astype(F32)
    lane = jax.lax.broadcasted_iota(jnp.int32, y_ref.shape, 1)
    y = jnp.zeros(y_ref.shape, F32)               # [P, hb]
    for j in range(hb):
        new = s_ref[j] * dec_ref[:, j:j + 1] \
            + xdt_ref[:, j:j + 1] * bv            # [P, N]
        o_ref[j] = new
        col = jnp.sum(new * cv, axis=-1, keepdims=True)       # [P, 1]
        y = jnp.where(lane == j, col, y)
    y_ref[...] = y


def ssm_state_update_rows(x, dt, A, Bm, Cm, D, states, row):
    """``x [B, nh, P]``, ``dt [B, nh]`` (after softplus), ``A, D [nh]``,
    ``Bm, Cm [B, G, N]``; ``states [L, B, nh, P, N]`` float32, ``row``
    (traced int32 scalar) the layer's row of it.  Returns ``(y [B, nh,
    P] in x's dtype, states)`` with that row stepped once; ``states``
    should be donated (the kernel writes it in place)."""
    L, Bsz, nh, P, N = states.shape
    G = Bm.shape[1]
    hb = _heads_per_block(nh, G, P, N)
    nb = nh // hb
    x32, dt32 = x.astype(F32), dt.astype(F32)

    def lanes(a):                   # [B, nh, P] -> [B, nb, P, hb]
        return jnp.swapaxes(a.reshape(Bsz, nb, hb, P), 2, 3)

    xdt = lanes(x32 * dt32[..., None])
    dec = lanes(jnp.broadcast_to(
        jnp.exp(dt32 * A.astype(F32))[..., None], (Bsz, nh, P)))
    per_group = nh // G
    grp = lambda b, h, row: (b, (h * hb) // per_group, 0, 0)
    side = pl.BlockSpec((None, None, P, hb), lambda b, h, row: (b, h, 0, 0))
    blk = pl.BlockSpec((None, None, hb, P, N),
                       lambda b, h, row: (row[0], b, h, 0, 0))
    new, y = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(Bsz, nb),
            in_specs=[blk, side, side,
                      pl.BlockSpec((None, None, 1, N), grp),
                      pl.BlockSpec((None, None, 1, N), grp)],
            out_specs=[blk, side]),
        out_shape=[jax.ShapeDtypeStruct(states.shape, F32),
                   jax.ShapeDtypeStruct((Bsz, nb, P, hb), F32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=use_interpret(),
        name="ssm_state_update",
    )(jnp.reshape(row, (1,)).astype(jnp.int32), states, xdt, dec,
      Bm[:, :, None, :], Cm[:, :, None, :])
    y = jnp.swapaxes(y, 2, 3).reshape(Bsz, nh, P) \
        + D.astype(F32)[None, :, None] * x32
    return y.astype(x.dtype), new
