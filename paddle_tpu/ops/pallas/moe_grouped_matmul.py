"""``moe_grouped_matmul``: the matmuls of an expert layer over rows
SORTED by expert, against the experts' weights where they lie: a stack
``[G, K, N]`` of every layer's experts (``G = layers x experts``, a
bitcast of the parameter leaf), out of which a row tile's weights are
found by index.  Nothing is cut out of the stack and nothing is copied.

    out[tile t] = lhs[tile t] @ rhs[group[t]]                  (one stack)
    out[tile t] = silu(lhs[t] @ gate[group[t]]) * (lhs[t] @ up[group[t]])

The rows come laid out in TILES of ``tm`` rows, each tile all of ONE
expert (``parallel/moe.py`` pads an expert's rows up to whole tiles), so
a tile needs no mask and no second visit.  The grid is ``(N / tn,
tiles)``: for one column block of the weights the tiles stream past in
order, and since consecutive tiles of one expert name the same weight
block, the pipeline fetches an expert's ``[K, tn]`` block ONCE and
keeps it while that expert's tiles run: the bank is read once a call,
whatever the routing (``jax.experimental.pallas.ops.tpu.megablox.gmm``
tiles K and so fetches an expert's block again for every row tile).
``K`` is whole in a block (2048 and 1536 at GLM-4.7-Flash's widths), the
float32 accumulator lives in the matmul's result, and a tile is written
once.

The number of tiles that hold rows is known only where the program
runs: the grid has the static bound (``tiles``), the prefetched
``visited`` says how many of them are real, and the steps past it name
the last real tile's blocks again (nothing is fetched, nothing is
written) and do nothing.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...analysis.kernel import cost
from .common import use_interpret

__all__ = ["grouped_tiles", "moe_grouped_matmul"]

F32 = jnp.float32
#: the weight blocks of a step, each double-buffered, take at most two
#: thirds of the kernel budget; a row tile and an output tile the rest
WEIGHT_BYTES = cost.budget_bytes() * 2 // 3
#: a row tile is at most the MXU's height: taller tiles multiply more
#: padding (an expert's rows are padded to whole tiles) for no fewer
#: weight pushes
MAX_ROWS = 128


def grouped_tiles(rows: int, n_experts: int, K: int, N: int,
                  n_rhs: int, itemsize: int) -> Tuple[int, int]:
    """``(tm, tn)`` from what the call can see.  ``tm``: an even share
    of the ``rows`` an expert (a tile is one expert's: shorter tiles pad
    less, ``rows + experts x tm / 2`` rows are multiplied on average),
    in whole sublane packs, at most ``MAX_ROWS``.  ``tn``: the widest
    multiple of 128 lanes that divides ``N`` and whose ``n_rhs``
    double-buffered ``[K, tn]`` blocks fit ``WEIGHT_BYTES`` (the lhs is
    read again for every column block); all of ``N`` where no such
    multiple exists (toy widths)."""
    pack = 8 * max(1, 4 // itemsize)
    even = -(-rows // n_experts)
    tm = min(MAX_ROWS, -(-even // pack) * pack)
    fit = [t for t in range(128, N + 1, 128)
           if N % t == 0 and 2 * n_rhs * K * t * itemsize <= WEIGHT_BYTES]
    return tm, (max(fit) if fit else N)


def _kernel(group_ref, row_ref, visited_ref, x_ref, *refs):
    del group_ref, row_ref                        # used by the index maps
    *w_refs, o_ref = refs

    @pl.when(pl.program_id(1) < visited_ref[0])
    def _():
        x = x_ref[...]
        # each product leaves the matmul in the operands' dtype, as the
        # einsum it stands for does (float32 inside)
        y = [jnp.dot(x, w[...], preferred_element_type=F32).astype(
            o_ref.dtype) for w in w_refs]
        if len(y) == 2:
            gate, up = (a.astype(F32) for a in y)
            y = [(jax.nn.silu(gate) * up).astype(o_ref.dtype)]
        o_ref[...] = y[0]


def moe_grouped_matmul(lhs: jax.Array, stacks: Sequence[jax.Array],
                       tile_group: jax.Array, tile_row: jax.Array,
                       visited: jax.Array, tm: int, tn: int) -> jax.Array:
    """``lhs [M, K]`` in tiles of ``tm`` rows; ``stacks`` one ``[G, K,
    N]`` (a plain product) or two (gate and up: ``silu(a) * b``);
    ``tile_row [M / tm]`` each grid step's row tile and ``tile_group``
    that tile's index into ``G``, ``visited`` (int32 scalar) how many
    steps are real: a step past it must name the blocks of step
    ``visited - 1``.  Returns ``[M, N]`` in ``lhs``'s dtype; tiles no
    step names are not written."""
    M, K = lhs.shape
    G, _, N = stacks[0].shape
    tiles = M // tm
    assert M % tm == 0 and N % tn == 0, (M, tm, N, tn)
    x_spec = pl.BlockSpec((tm, K), lambda n, t, grp, row, vis: (row[t], 0))
    w_spec = pl.BlockSpec((None, K, tn),
                          lambda n, t, grp, row, vis: (grp[t], 0, n))
    o_spec = pl.BlockSpec((tm, tn), lambda n, t, grp, row, vis: (row[t], n))
    item = lhs.dtype.itemsize
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N // tn, tiles),
            in_specs=[x_spec] + [w_spec] * len(stacks),
            out_specs=o_spec),
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * M * K * N * len(stacks),
            transcendentals=M * N * (len(stacks) - 1),
            bytes_accessed=item * (M * K * (N // tn) + M * N
                                   + len(stacks) * K * N
                                   * min(G, tiles))),
        interpret=use_interpret(),
        name="moe_grouped_matmul",
    )(tile_group.astype(jnp.int32), tile_row.astype(jnp.int32),
      jnp.reshape(visited, (1,)).astype(jnp.int32), lhs, *stacks)
