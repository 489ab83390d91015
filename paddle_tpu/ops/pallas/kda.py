"""``kda_state_update``: one step of the KDA delta-rule recurrence for
every decode slot, against ONE layer's rows of the engine's state array,
in place and in one pass.

    Sd = Diag(a) S      S' = Sd + (beta k) (v - Sd^T k)^T      o = S'^T q

The state ``[L, B, nh, K, V]`` float32 is the largest thing such a
model's decode step touches after its weights (64 KiB a head: 2 MiB a
slot a layer at 32 heads of 128 x 128).  The per-op tier reads it three
times a layer (the correction ``v - Sd^T k`` is a reduction over the
whole state that the update then needs, so XLA cannot fuse the two);
this kernel reads each ``[hb, K, V]`` block once, writes it once (the
output aliases the input: rows of other layers are not touched) and
leaves ``o`` beside it.  Its name is what a trace finds.

Layout inside a block: ``V`` lies on the lanes, ``K`` on the sublanes.
``v`` and ``o`` are rows ``[1, V]`` a head and broadcast along the
sublanes as they are.  The decay, ``k``, ``beta k`` and ``q`` are per
``(head, K)``: the wrapper hands the four over as ONE ``[B, nh / hb, 4,
K, hb]`` array (heads on the lanes), so that a head's column ``[:,
j:j+1]`` broadcasts along the lanes with no relayout
(``ops/pallas/ssm.py``'s pattern).
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

__all__ = ["kda_state_update_rows", "unsupported_reason"]

F32 = jnp.float32
#: a block of state goes in and comes out, each double-buffered: those
#: four take a third of the kernel budget (1 MiB a block: 16 heads of
#: 128 x 128 float32)
BLOCK_BYTES = cost.budget_bytes() // 12


def _heads_per_block(nh: int, K: int, V: int) -> int:
    """The most heads a block within ``BLOCK_BYTES`` (one head where a
    head alone is larger)."""
    return next(hb for hb in (32, 16, 8, 4, 2, 1) if nh % hb == 0
                and (hb * K * V * 4 <= BLOCK_BYTES or hb == 1))


def unsupported_reason(state_shape) -> Optional[str]:
    """Why the Mosaic kernel cannot take this geometry, or None."""
    _, _, nh, K, V = state_shape
    if V % 128:
        return f"value width {V} is no multiple of the 128 lanes"
    if K % 8:
        return f"key width {K} is no multiple of the 8 sublanes"
    return None


def _kernel(row_ref, s_ref, side_ref, v_ref, o_ref, y_ref, *, hb: int):
    del row_ref                                   # used by the index maps
    for j in range(hb):
        col = lambda i: side_ref[i, :, j:j + 1]               # [K, 1]
        sd = s_ref[j] * col(0)                                # [K, V]
        r = v_ref[j:j + 1, :] - jnp.sum(sd * col(1), axis=0,
                                        keepdims=True)        # [1, V]
        new = sd + col(2) * r
        o_ref[j] = new
        y_ref[j:j + 1, :] = jnp.sum(new * col(3), axis=0, keepdims=True)


def kda_state_update_rows(q, k, v, log_a, beta, states, row):
    """``q, k, log_a [B, nh, K]``, ``v [B, nh, V]``, ``beta [B, nh]``;
    ``states [L, B, nh, K, V]`` float32, ``row`` (traced int32 scalar)
    the layer's row of it.  Returns ``(o [B, nh, V] float32, states)``
    with that row stepped once; ``states`` should be donated (the kernel
    writes it in place)."""
    L, Bsz, nh, K, V = states.shape
    hb = _heads_per_block(nh, K, V)
    nb = nh // hb
    k32 = k.astype(F32)
    # [4, B, nh, K] -> [B, nb, 4, K, hb]: heads on the lanes
    side = jnp.stack([jnp.exp(log_a.astype(F32)), k32,
                      beta.astype(F32)[..., None] * k32, q.astype(F32)])
    side = side.reshape(4, Bsz, nb, hb, K).transpose(1, 2, 0, 4, 3)
    blk = pl.BlockSpec((None, None, hb, K, V),
                       lambda b, h, row: (row[0], b, h, 0, 0))
    rows = pl.BlockSpec((None, None, hb, V), lambda b, h, row: (b, h, 0, 0))
    new, o = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(Bsz, nb),
            in_specs=[blk,
                      pl.BlockSpec((None, None, 4, K, hb),
                                   lambda b, h, row: (b, h, 0, 0, 0)),
                      rows],
            out_specs=[blk, rows]),
        out_shape=[jax.ShapeDtypeStruct(states.shape, F32),
                   jax.ShapeDtypeStruct((Bsz, nb, hb, V), F32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=use_interpret(),
        name="kda_state_update",
    )(jnp.reshape(row, (1,)).astype(jnp.int32), states, side,
      v.astype(F32).reshape(Bsz, nb, hb, V))
    return o.reshape(Bsz, nh, V), new
