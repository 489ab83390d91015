"""Expert-parallel MoE FFN for the compiled hybrid train step.

The eager :class:`~paddle_tpu.incubate.distributed.models.moe.MoELayer`
covers the reference's imperative MoE API (moe_layer.py:263) with dense
[T, E, C] dispatch/combine einsums.  This module is the MANUAL-SPMD
counterpart used inside the all-axes shard_map of
:func:`~paddle_tpu.parallel.manual.build_hybrid_train_step`:

* Routing is scatter-based — positions come from a [T*k, E] cumsum and
  tokens are scattered straight into the [E, C, h] expert buffers — so
  memory is O(T*E + E*C*h) instead of the O(T*E*C) one-hot dispatch mask
  (which is quadratic in tokens at fixed expert count).
* Expert parallelism follows the reference's distributed design
  (global_scatter/global_gather over the expert-parallel group,
  moe_layer.py:55): expert weights are SHARDED over the ``dp`` mesh axis
  (each data rank owns E/ep experts) and tokens move with ONE
  ``lax.all_to_all`` each way.  The all_to_all rides ICI inside the
  compiled step — no host round trip, unlike the reference's NCCL
  global_scatter.
* Tensor parallelism inside experts is Megatron-style (w1 column-split,
  w2 row-split over ``mp``) with the same mp_copy / fwd_psum collectives
  as the dense block.
* The GShard load-balance loss enters training through
  :func:`inject_aux_grad` — a custom-VJP identity that contributes
  ``coef * d(aux)/dparams`` to the backward pass without threading an
  extra scalar through the pipeline schedules (the compiled-step analog
  of the reference gate's ``get_loss()`` being added to the model loss).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..incubate.distributed.models.moe.gating import (compute_capacity,
                                                      gshard_aux_loss)
from .manual import fwd_psum, mp_copy

__all__ = ["inject_aux_grad", "topk_scatter_routing", "moe_ffn_ep",
           "moe_swiglu_ffn_ep", "moe_dispatch_combine", "compute_capacity",
           "schedule_aux_coef", "expert_choice_routing",
           "moe_expert_choice_ffn", "moe_swiglu_ffn_grouped",
           "moe_swiglu_ffn_masked", "route_held", "moe_gelu_ffn_grouped",
           "route_sigmoid", "moe_swiglu_ffn_routed", "expert_counts",
           "held_choices"]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def inject_aux_grad(x, aux, coef: float):
    """Identity on ``x`` whose backward adds ``coef`` as the cotangent of
    ``aux`` — exactly as if ``coef * aux`` had been added to the final
    scalar loss, without changing any forward value or signature.

    This lets per-layer auxiliary losses (MoE load balance) reach the
    optimizer through pipeline schedules whose carries are activation
    tensors only.  The forward loss value deliberately EXCLUDES the aux
    term (monitor it separately if needed); gradients include it exactly.
    """
    del aux, coef
    return x


def _inject_fwd(x, aux, coef):
    del aux
    return x, None


def _inject_bwd(coef, _, g):
    return g, jnp.asarray(coef, jnp.float32)


inject_aux_grad.defvjp(_inject_fwd, _inject_bwd)


def schedule_aux_coef(coef: float, num_layers: int, schedule: str,
                      pp_degree: int, num_microbatches: int,
                      data_replicas: int, mb_tokens: int
                      ) -> Optional[float]:
    """Per-site injection coefficient so every schedule path realizes the
    same effective term ``loss += coef * mean_over_sites(aux)`` (sites =
    layers x microbatches x data ranks).

    Single source of the contract with build_hybrid_train_step's grad
    normalization (shared by the gpt/llama builders — do not fork):
    the manual-vjp pipeline schedules (1f1b/zbh1/interleave) divide the
    summed vjp by ``norm = b_l*s_l*R`` AFTER the fact, which also scales
    the injected constant, while the value_and_grad paths (pp==1, gpipe)
    divide the loss inside loss_fn, which the injected constant bypasses.

    Args:
      data_replicas: dp * sharding * sep (each rank's aux is a distinct
        site whose grads later sum across these axes).
      mb_tokens: per-microbatch local tokens b_mb * s_l (only used by the
        manual-vjp branch; pass 0 otherwise).
    """
    if not coef:
        return None
    if pp_degree > 1 and schedule in ("1f1b", "zbh1", "interleave"):
        return coef * mb_tokens / num_layers
    M = num_microbatches if pp_degree > 1 else 1
    return coef / (num_layers * M * data_replicas)


def topk_scatter_routing(logits: jax.Array, top_k: int, capacity: int,
                         normalize: bool = True
                         ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                    jax.Array]:
    """Top-k router emitting scatter indices instead of dispatch masks.

    Same semantics as :func:`...moe.gating.topk_capacity_gating` (GShard
    priority: every token's k-th choice is ranked after all (k-1)-th
    choices; overflow beyond ``capacity`` is dropped), but O(T*E) memory.

    Args:
      logits: [T, E] router logits (softmaxed in fp32).
    Returns:
      idx:  [T, k] int32 — expert id per assignment.
      pos:  [T, k] int32 — slot in the expert buffer; == ``capacity``
            where the assignment was dropped (out-of-range on purpose so
            mode="drop"/"fill" scatters/gathers ignore it).
      w:    [T, k] fp32 — combine weights (0 where dropped).
      aux:  scalar GShard load-balance loss.
    """
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    aux = gshard_aux_loss(probs, jnp.argmax(probs, axis=-1))
    w, idx = lax.top_k(probs, top_k)                    # [T, k]
    idx = idx.astype(jnp.int32)
    # slot = number of earlier assignments to the same expert, counting
    # k-major (all 1st choices in token order, then all 2nd choices)
    oh = jax.nn.one_hot(idx, E, dtype=jnp.int32)        # [T, k, E]
    ohf = oh.transpose(1, 0, 2).reshape(top_k * T, E)
    prior = jnp.cumsum(ohf, axis=0) - ohf
    pos = jnp.sum(prior * ohf, axis=-1).reshape(top_k, T).T  # [T, k]
    keep = pos < capacity
    w = w * keep
    if normalize and top_k > 1:
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    pos = jnp.where(keep, pos, capacity).astype(jnp.int32)
    return idx, pos, w, aux


def expert_choice_routing(logits: jax.Array, capacity: int
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Expert-choice routing (Zhou et al. 2022): EXPERTS pick their top-C
    tokens instead of tokens picking experts — perfect load balance by
    construction (every expert processes exactly C tokens), no aux loss
    and no dropped-capacity heuristics.  Complements the GShard/Switch
    token-choice gates the reference ships (gshard_gate.py/switch_gate.py).

    Args:
      logits: [T, E] router logits (softmax over experts in fp32).
      capacity: tokens per expert C (typically T * cf * k / E).
    Returns:
      sel: [E, C] int32 — token index chosen per expert slot.
      w:   [E, C] fp32 — combine weight (the token's gate prob for this
           expert).
      probs: [T, E] fp32 — full router probabilities (for monitoring).
    """
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, sel = lax.top_k(probs.T, min(capacity, T))        # [E, C]
    return sel.astype(jnp.int32), w, probs


def moe_expert_choice_ffn(x: jax.Array, gate_w: jax.Array,
                          expert_apply: Callable, n_experts_local: int, *,
                          capacity_factor: float = 2.0,
                          ep_axis: Optional[str] = None) -> jax.Array:
    """MoE FFN under expert-choice routing, expert-parallel over
    ``ep_axis``.

    Dispatch is a plain gather (each expert's C chosen tokens), combine a
    weighted scatter-add back to token positions; both are linear, so AD
    handles the transposes.  With ``ep_axis`` the gathered buffers move
    with the same pair of all_to_alls as the token-choice path.

    ``capacity_factor`` here means AVERAGE EXPERTS PER TOKEN (the
    expert-choice paper's c): C = T * c / E.
    """
    shape = x.shape
    h = shape[-1]
    tokens = x.reshape(-1, h)
    T = tokens.shape[0]
    ep = 1 if ep_axis is None else lax.axis_size(ep_axis)
    E = n_experts_local * ep
    if gate_w.shape[1] != E:
        raise ValueError(f"gate_w experts {gate_w.shape[1]} != "
                         f"{n_experts_local}x{ep} sharded expert bank")
    C = max(1, min(T, int(T * capacity_factor / E)))

    logits = tokens.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    sel, w, _ = expert_choice_routing(logits, C)          # [E, C]

    buf = tokens[sel]                                     # [E, C, h] gather
    if ep_axis is not None:
        buf = lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1,
                             tiled=True)
    out = expert_apply(buf)
    if ep_axis is not None:
        out = lax.all_to_all(out, ep_axis, split_axis=1, concat_axis=0,
                             tiled=True)
    # combine: weighted scatter-add back to token slots
    res = jnp.zeros((T, h), jnp.float32)
    res = res.at[sel.reshape(-1)].add(
        (w[..., None].astype(jnp.float32)
         * out.astype(jnp.float32)).reshape(E * C, h))
    return res.astype(x.dtype).reshape(shape)


def route_held(logits: jax.Array, top_k: int, n_held: int, *,
               normalize: bool = True, gate: str = "softmax_topk",
               expert_offset: int = 0):
    """Token-choice routing over ALL experts of the router for a rank
    that holds experts ``[expert_offset, expert_offset + n_held)``.

    ``logits [T, E]`` float32.  ``gate="softmax_topk"``: softmax over all
    experts, the ``top_k`` largest, renormalised when ``normalize`` (the
    Mixtral gate); ``"topk_softmax"``: the ``top_k`` largest logits,
    softmax over those (the granite gate).  Either way a token's gates
    are those of its full choice, UNCHANGED by what this rank holds.
    Returns ``(w [T, k], local [T, k], held [T, k])``: the gates, each
    choice's index into the held bank (``n_held`` where it is not held)
    and whether it is held."""
    if gate == "softmax_topk":
        w, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        if normalize and top_k > 1:
            w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    elif gate == "topk_softmax":
        lg, idx = lax.top_k(logits, top_k)
        w = jax.nn.softmax(lg, axis=-1)
    else:
        raise ValueError(f"unknown gate {gate!r}")
    local, held = held_choices(idx, n_held, expert_offset)
    return w, local, held


def held_choices(idx: jax.Array, n_held: int, expert_offset: int = 0):
    """``(local, held)`` of the router's choices ``idx`` for a rank that
    holds experts ``[expert_offset, expert_offset + n_held)``: each
    choice's index into the held bank (``n_held`` where it is not held)
    and whether it is held."""
    local = idx - expert_offset
    held = (local >= 0) & (local < n_held)
    return jnp.where(held, local, n_held), held


def _held_counts(held, local, n_held: int, mask=None):
    """``[assignments on held experts, distinct held experts hit]`` as
    int32: the two sums a serving step reports beside its logits, over
    the tokens of ``mask [T]`` (all of them when None)."""
    if mask is not None:
        held = held & mask[:, None]
        local = jnp.where(held, local, n_held)
    hit = jnp.zeros((n_held + 1,), jnp.int32).at[local.reshape(-1)].max(
        1)[:n_held]
    return jnp.stack([jnp.sum(held, dtype=jnp.int32),
                      jnp.sum(hit, dtype=jnp.int32)])


def _experts_masked(tokens, w, local, wg, wu, wd):
    """``sum_j w[t, j] E_local[t, j](tokens[t])`` as float32 ``[T, h]``,
    every token through every expert of the bank as one batched matmul
    and the gates (``local == E``: no expert of this bank) weighing the
    sum."""
    E = wg.shape[0]
    # [T, E] gates: a token's gate for each held expert, else 0
    dense = jnp.zeros((tokens.shape[0], E + 1), jnp.float32).at[
        jnp.arange(tokens.shape[0])[:, None], local].add(w)[:, :E]
    g = jnp.einsum("th,ehf->etf", tokens, wg)
    u = jnp.einsum("th,ehf->etf", tokens, wu)
    act = (jax.nn.silu(g) * u) * dense.T[..., None].astype(g.dtype)
    return jnp.einsum("etf,efh->th", act, wd,
                      preferred_element_type=jnp.float32)


def route_sigmoid(logits: jax.Array, bias: jax.Array, top_k: int, *,
                  n_group: int = 1, topk_group: int = 1,
                  normalize: bool = True, scale: float = 1.0):
    """The DeepSeek-V3 / GLM-4.x gate (``topk_method`` ``noaux_tc``) on
    ``logits [T, E]`` float32: scores ``s = sigmoid(logits)``; the
    CHOICE is the ``top_k`` largest of ``s + bias``
    (``e_score_correction_bias``, a load-balancing offset), taken among
    the ``topk_group`` groups of experts (of ``n_group``) whose two best
    ``s + bias`` sum highest — the others' ``s + bias`` count as 0, as
    in the public implementation; the WEIGHTS are ``s`` of the chosen,
    without the bias, over their sum (``normalize``), times ``scale``
    (``routed_scaling_factor``).  Returns ``(w [T, k] float32, idx [T,
    k] int32)``."""
    T, E = logits.shape
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    choice = s + bias.astype(jnp.float32)
    if n_group > 1:
        per = E // n_group
        best2 = jnp.sum(lax.top_k(choice.reshape(T, n_group, per), 2)[0],
                        axis=-1)
        _, gi = lax.top_k(best2, topk_group)
        keep = jnp.zeros((T, n_group), bool).at[
            jnp.arange(T)[:, None], gi].set(True)
        choice = jnp.where(jnp.repeat(keep, per, axis=1), choice, 0.0)
    _, idx = lax.top_k(choice, top_k)
    w = jnp.take_along_axis(s, idx, axis=1)
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scale, idx.astype(jnp.int32)


def expert_counts(idx: jax.Array, n_experts: int, mask=None):
    """Of the choices ``idx [T, k]`` of the tokens ``mask [T]`` names
    (all when None), int32 ``[token-expert pairs, distinct experts hit,
    the most pairs on ONE expert]``: what a serving step reports of an
    expert layer that holds every expert (the last is the straggler an
    expert-parallel deployment would wait for)."""
    if mask is not None:
        idx = jnp.where(mask[:, None], idx, n_experts)
    load = jnp.zeros((n_experts + 1,), jnp.int32).at[
        idx.reshape(-1)].add(1)[:n_experts]
    return jnp.stack([jnp.sum(load), jnp.sum(load > 0, dtype=jnp.int32),
                      jnp.max(load)])


def _experts_grouped(tokens, w, idx, wg, wu, wd, E, base, share=1.0):
    """``sum_j w[t, j] E_idx[t, j](tokens[t])`` as float32 ``[T, h]``
    and the rows multiplied for it (int32).  ``wg/wu [G, h, f]``, ``wd
    [G, f, h]``: EVERY layer's experts, this layer's ``E`` from ``base``
    on; ``idx == E`` names no expert of the bank (a choice another rank
    holds: it multiplies nothing and counts no row); ``share`` the part
    of the ``T k`` pairs expected to land here.  The pairs are sorted by
    expert and laid out in tiles
    of ``tm`` rows, an expert's rows padded up to whole tiles (gathers
    only, no scatter); gate and up with the SwiGLU are one grouped
    matmul over the tiles that hold a row, down another
    (``ops/pallas/moe_grouped_matmul.py``); each pair's row is gathered
    back and the gates weigh the float32 sum."""
    from ..ops.pallas.moe_grouped_matmul import (grouped_tiles,
                                                 moe_grouped_matmul)
    T, k = idx.shape
    h, f = wg.shape[-2:]
    item = tokens.dtype.itemsize
    expected = max(1, int(T * k * share))
    tm, tn_up = grouped_tiles(expected, E, h, f, 2, item)
    _, tn_down = grouped_tiles(expected, E, f, h, 1, item)
    flat = idx.reshape(-1)                               # [T k] pairs
    order = jnp.argsort(flat)                 # stable; the unheld last
    load = jnp.zeros((E + 1,), jnp.int32).at[flat].add(1)[:E]
    first = jnp.cumsum(load) - load                      # in sorted order
    tiles = -(-load // tm)                               # [E] tiles each
    tile_end = jnp.cumsum(tiles)
    tile0 = tile_end - tiles
    visited = tile_end[-1]
    # the static bound: no routing needs more tiles than this
    n_tiles = (T * k + E * (tm - 1)) // tm
    # tile t is expert e's: the steps past the last real tile name it again
    t = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32),
                    jnp.maximum(visited - 1, 0))
    e = jnp.minimum(jnp.searchsorted(tile_end, t, side="right"),
                    E - 1).astype(jnp.int32)
    # row r of tile t is the expert's ((t - tile0) tm + r)-th pair; the
    # padding rows read some token and are multiplied for nothing
    at = first[e][:, None] + (t - tile0[e])[:, None] * tm \
        + jnp.arange(tm, dtype=jnp.int32)[None]
    src = order[jnp.minimum(at, T * k - 1)] // k         # [tiles, tm]
    x = tokens[src.reshape(-1)]                          # [tiles tm, h]
    act = moe_grouped_matmul(x, (wg, wu), base + e, t, visited, tm, tn_up)
    out = moe_grouped_matmul(act, (wd,), base + e, t, visited, tm, tn_down)
    # a pair's row: its expert's first tile, then its place among the
    # expert's pairs; an unheld pair has none (and what lies past the
    # visited tiles is not even zeros: chosen, not multiplied, away)
    held = flat < E
    mine = jnp.minimum(flat, E - 1)
    row = tile0[mine] * tm + jnp.argsort(order) - first[mine]
    got = jnp.where(held[:, None], out[jnp.where(held, row, 0)],
                    0).reshape(T, k, h).astype(jnp.float32)
    return jnp.sum(w[..., None] * got, axis=1), visited * tm


def moe_swiglu_ffn_routed(x: jax.Array, w: jax.Array, idx: jax.Array,
                          wg: jax.Array, wu: jax.Array, wd: jax.Array, *,
                          layer=None, expert_offset: int = 0,
                          router_experts: Optional[int] = None):
    """Exact SwiGLU MoE for routing done by the caller: ``sum_j w[t, j]
    E_idx[t, j](x[t])`` over the bank ``wg/wu [E, h, f]``, ``wd [E, f,
    h]``; no token is dropped for any routing.  The bank holds experts
    ``[expert_offset, expert_offset + E)`` of the ``router_experts``
    (``E`` when None) that ``idx`` ranges over: a rank that holds a
    SHARE computes the terms of the pairs that chose its experts, gates
    unchanged, and a pair that chose another rank's multiplies nothing
    and counts no row (expert parallelism's partial sum; with every
    expert held this is the whole sum, on the same code).  With
    ``layer`` (a traced index) the three are STACKS ``[n,
    E, ...]`` of which that layer's bank is meant.  Returns ``(out, rows)``,
    ``rows`` (int32) the rows the experts' matmuls multiplied for the
    held token-expert pairs.

    ONE form whatever the rows, :func:`_experts_grouped`: each routed
    row is multiplied once, but for the padding of each expert's last
    tile, and an expert's weights are read once IF a row chose it, where
    they lie: the kernel is handed the WHOLE stacks (viewed ``[n E,
    ...]``, a bitcast) and finds the layer's experts by index, so
    nothing is cut out of them.  A decode step's few rows take it too:
    every row through every expert as one batched matmul
    (:func:`moe_swiglu_ffn_masked`'s form) reads the whole bank at the
    same rate, so it is level only where every expert is hit, which the
    routers served here are far from (alone on a TPU v5e, a layer of a
    6-layer stack, PERF.md PR 37: 128 rows choosing 8 of 512 with 128
    held hit 49% of them, 1.17 ms against 2.03; 64 rows choosing 4 of
    64, all held, hit 85%, 1.46 against 1.63; with every expert hit the
    arithmetic says 4% behind).  ``lax.ragged_dot`` is handed one
    layer's bank, which the compiler first copies out of the stack (1.2
    GB a layer at GLM-4.7-Flash's sizes; PERF.md, PR 30), and a fixed
    capacity an expert multiplies its padding and needs rounds where the
    routing is uneven (PERF.md, PR 35)."""
    shape = x.shape
    tokens = x.reshape(-1, shape[-1])
    E = wg.shape[-3]
    idx, _ = held_choices(idx, E, expert_offset)

    def whole(a):
        return a.reshape((-1,) + a.shape[-2:])

    res, rows = _experts_grouped(
        tokens, w, idx, whole(wg), whole(wu), whole(wd), E,
        0 if layer is None else layer * E,
        share=E / (router_experts or E))
    return res.astype(x.dtype).reshape(shape), rows


def moe_swiglu_ffn_masked(x: jax.Array, router_w: jax.Array,
                          wg: jax.Array, wu: jax.Array, wd: jax.Array, *,
                          top_k: int = 2, normalize: bool = True,
                          gate: str = "softmax_topk",
                          expert_offset: int = 0,
                          with_counts: bool = False, count_mask=None):
    """Exact SwiGLU MoE for a rank that holds a SHARE of the router's
    experts, ``wg/wu/wd [n_held, ...]`` from ``expert_offset`` on: every
    token is routed over all of ``router_w``'s experts
    (:func:`route_held`), runs through every HELD expert as one batched
    matmul, and the gates (zero where an expert was not chosen or is not
    held) weigh the sum — the partial sum that expert parallelism's
    ranks add up, the gates unchanged by what a rank holds.  No sort, no
    gather, no copy of the bank: ``E / top_k`` times the FLOPs a token's
    own choices require, the same weight bytes.

    Why not the sorted form (:func:`moe_swiglu_ffn_grouped`) for a
    share: on a TPU v5e, 36 of 72 experts of 4096 x 768 held, top 10,
    this form serves twice the tokens of ``ragged_dot`` in the 128- and
    512-token chunk fills and in the 64-row decode step alike (PERF.md
    §6, PR 30: each grouped matmul first copies the layer's bank out of
    the stacked weights).  ``with_counts`` also returns the int32 pair
    ``[assignments on held experts, distinct held experts hit]`` over
    the tokens of ``count_mask``."""
    shape = x.shape
    tokens = x.reshape(-1, shape[-1])
    E = wg.shape[0]
    logits = tokens.astype(jnp.float32) @ router_w.astype(jnp.float32)
    w, local, held = route_held(logits, top_k, E, normalize=normalize,
                                gate=gate, expert_offset=expert_offset)
    res = _experts_masked(tokens, w, local, wg, wu, wd)
    res = res.astype(x.dtype).reshape(shape)
    if with_counts:
        return res, _held_counts(held, local, E, count_mask)
    return res


def moe_swiglu_ffn_grouped(x: jax.Array, router_w: jax.Array,
                           wg: jax.Array, wu: jax.Array, wd: jax.Array, *,
                           top_k: int = 2, normalize: bool = True,
                           with_aux: bool = False):
    """Exact SwiGLU MoE via sorted grouped GEMM (`lax.ragged_dot`) — the
    SERVING formulation: assignments are sorted by expert and each expert
    multiplies only its own contiguous row block, so there is no capacity
    padding (top_k*T slot cost, vs E*C for the dispatch-buffer path) and
    no token is ever dropped.  On TPU ragged_dot lowers to the Mosaic
    grouped-matmul; this is the MegaBlocks-style dropless MoE.

    Single-device only (no ep/mp axes).  ragged_dot differentiates, so
    this serves AND trains (the ``dropless`` mode of the ffn wrappers);
    EP/TP layouts keep the fixed-capacity dispatch buffers whose static
    shapes the all_to_alls need.
    """
    shape = x.shape
    h = shape[-1]
    tokens = x.reshape(-1, h)
    T = tokens.shape[0]
    E = wg.shape[0]
    logits = tokens.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = lax.top_k(probs, top_k)                     # [T, k]
    if normalize and top_k > 1:
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)

    e_flat = idx.reshape(-1)                             # [T*k]
    order = jnp.argsort(e_flat)
    tok_rep = jnp.broadcast_to(tokens[:, None, :],
                               (T, top_k, h)).reshape(T * top_k, h)
    sorted_tok = tok_rep[order]
    gs = jnp.bincount(e_flat, length=E).astype(jnp.int32)
    gate = lax.ragged_dot(sorted_tok, wg, gs)
    up = lax.ragged_dot(sorted_tok, wu, gs)
    out_sorted = lax.ragged_dot(jax.nn.silu(gate) * up, wd, gs)
    inv = jnp.argsort(order)
    out = out_sorted[inv].reshape(T, top_k, h)
    res = jnp.sum(w[..., None] * out.astype(jnp.float32), axis=1)
    res = res.astype(x.dtype).reshape(shape)
    if with_aux:
        return res, gshard_aux_loss(probs, jnp.argmax(probs, axis=-1))
    return res


def moe_gelu_ffn_grouped(x: jax.Array, gate_w: jax.Array, w1: jax.Array,
                         b1: jax.Array, w2: jax.Array, b2: jax.Array, *,
                         top_k: int = 2, normalize: bool = True,
                         activation: Callable = functools.partial(
                             jax.nn.gelu, approximate=True),
                         with_aux: bool = False):
    """GELU-MLP counterpart of :func:`moe_swiglu_ffn_grouped` (the GPT
    expert bank with per-expert biases): per-assignment biases come from
    a gather on the sorted expert ids, everything else is the same
    sorted ragged_dot pipeline.  Serving path — single device, no
    ep/mp axes, dropless by construction."""
    shape = x.shape
    h = shape[-1]
    tokens = x.reshape(-1, h)
    T = tokens.shape[0]
    E = w1.shape[0]
    logits = tokens.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = lax.top_k(probs, top_k)
    if normalize and top_k > 1:
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)

    e_flat = idx.reshape(-1)
    order = jnp.argsort(e_flat)
    e_sorted = e_flat[order]
    tok_rep = jnp.broadcast_to(tokens[:, None, :],
                               (T, top_k, h)).reshape(T * top_k, h)
    sorted_tok = tok_rep[order]
    gs = jnp.bincount(e_flat, length=E).astype(jnp.int32)
    hdn = lax.ragged_dot(sorted_tok, w1, gs) + b1[e_sorted]
    out_sorted = lax.ragged_dot(activation(hdn), w2, gs) + b2[e_sorted]
    inv = jnp.argsort(order)
    out = out_sorted[inv].reshape(T, top_k, h)
    res = jnp.sum(w[..., None] * out.astype(jnp.float32), axis=1)
    res = res.astype(x.dtype).reshape(shape)
    if with_aux:
        return res, gshard_aux_loss(probs, jnp.argmax(probs, axis=-1))
    return res


def _run_dropless(grouped_fn, ep_axis, mp_axis, aux_coef):
    """Shared dropless-branch contract for the ffn wrappers: require
    degree-1 ep/mp (capacity buffers carry the static shapes collectives
    need), then run the grouped fn and inject the aux loss it already
    computed.  (The expert_choice x dropless conflict is rejected in the
    wrappers' expert_choice branches, which return before this runs.)"""
    ep_d = 1 if ep_axis is None else lax.axis_size(ep_axis)
    mp_d = 1 if mp_axis is None else lax.axis_size(mp_axis)
    if ep_d > 1 or mp_d > 1:
        raise ValueError("dropless=True requires local expert banks "
                         "(ep/mp degree 1) — capacity buffers carry "
                         "the static shapes collectives need")
    if aux_coef:
        out, aux = grouped_fn(True)
        return inject_aux_grad(out, aux, aux_coef)
    return grouped_fn(False)


def moe_dispatch_combine(x: jax.Array, gate_w: jax.Array,
                         expert_apply: Callable, n_experts_local: int, *,
                         top_k: int = 2, capacity_factor: float = 1.25,
                         ep_axis: Optional[str] = None,
                         aux_coef: float = 0.0,
                         normalize: bool = True,
                         capacity: Optional[int] = None) -> jax.Array:
    """Shared routing + EP transport around any expert function.

    Routes device-local tokens into fixed-capacity per-expert buffers,
    moves them to the owning expert rank with one ``lax.all_to_all``
    (global_scatter parity, reference moe_utils.py), applies
    ``expert_apply(buf [E_local, slots, h]) -> [E_local, slots, h]``
    (which embeds its own mp collectives), brings the slots home with the
    inverse all_to_all, and combines with the routing weights.

    Args:
      x: [..., h] device-local tokens (the FULL gathered sequence when
         the caller runs Megatron sequence parallelism).
      gate_w: [h, E] router weights (math in fp32).
      n_experts_local: experts held by THIS rank (E/ep).
      ep_axis: mesh axis the expert dim is sharded over (the hybrid step
         passes ``dp``); None = experts all local.
      aux_coef: weight on the GShard balance loss, injected via
         :func:`inject_aux_grad` (0 = off).
      capacity: explicit per-expert slot count overriding the GShard
         formula — inference paths pass the token count so NO token is
         ever dropped (capacity truncation is a training regularizer;
         at decode time a drop silently corrupts the output).
    """
    shape = x.shape
    h = shape[-1]
    tokens = x.reshape(-1, h)
    T = tokens.shape[0]
    ep = 1 if ep_axis is None else lax.axis_size(ep_axis)
    E = n_experts_local * ep
    if gate_w.shape[1] != E:
        raise ValueError(f"gate_w experts {gate_w.shape[1]} != "
                         f"{n_experts_local}x{ep} sharded expert bank")
    C = capacity if capacity is not None \
        else compute_capacity(T, E, top_k, capacity_factor)

    logits = tokens.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    idx, pos, w, aux = topk_scatter_routing(logits, top_k, C, normalize)

    # dispatch: scatter each kept assignment's token into its expert slot
    tok_rep = jnp.broadcast_to(tokens[:, None, :],
                               (T, top_k, h)).reshape(T * top_k, h)
    buf = jnp.zeros((E, C, h), x.dtype)
    buf = buf.at[idx.reshape(-1), pos.reshape(-1)].set(tok_rep, mode="drop")

    if ep_axis is not None:
        # [E, C, h] -> [E/ep, ep*C, h]: every rank's slots for MY experts
        buf = lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1,
                             tiled=True)
    out = expert_apply(buf)
    if ep_axis is not None:
        # inverse all_to_all: my slots come home from every expert rank
        out = lax.all_to_all(out, ep_axis, split_axis=1, concat_axis=0,
                             tiled=True)

    got = out.at[idx, pos].get(mode="fill", fill_value=0)   # [T, k, h]
    res = jnp.sum(w[..., None].astype(jnp.float32)
                  * got.astype(jnp.float32), axis=1)
    res = res.astype(x.dtype).reshape(shape)
    if aux_coef:
        res = inject_aux_grad(res, aux, aux_coef)
    return res


def moe_ffn_ep(x: jax.Array, gate_w: jax.Array, w1: jax.Array,
               b1: jax.Array, w2: jax.Array, b2: jax.Array, *,
               top_k: int = 2, capacity_factor: float = 1.25,
               ep_axis: Optional[str] = None,
               mp_axis: Optional[str] = None,
               sequence_parallel: bool = False,
               aux_coef: float = 0.0,
               activation: Callable = functools.partial(jax.nn.gelu,
                                                        approximate=True),
               normalize: bool = True,
               router: str = "topk",
               dropless: bool = False) -> jax.Array:
    """GELU-MLP mixture of experts (the GPT block's FFN), expert-parallel
    over ``ep_axis``.

    w1/b1/w2/b2: LOCAL expert shards — [E/ep, h, f/mp], [E/ep, f/mp],
    [E/ep, f/mp, h], [E/ep, h].  With no mesh axes these are the full
    [E, ...] banks and the function is a plain jit MoE FFN.

    mp_axis: Megatron TP inside each expert — w1 column-split (mp_copy
    on the input: identity fwd / psum bwd), w2 row-split (fwd_psum on
    the output).  Under ``sequence_parallel`` the caller gathered the
    sequence over mp, so the input reduction lives in that all_gather's
    transpose and no mp_copy is inserted; the caller scatters after
    (outputs here are replicated over mp post-psum, biases included —
    hence full, not mp-partial, bias grads)."""
    def expert_apply(buf):
        y = buf
        if mp_axis is not None and not sequence_parallel:
            y = mp_copy(y, mp_axis)       # identity fwd / psum bwd (col in)
        hdn = jnp.einsum("gch,ghf->gcf", y, w1) + b1[:, None, :]
        hdn = activation(hdn)
        out = jnp.einsum("gcf,gfh->gch", hdn, w2)
        if mp_axis is not None:
            out = fwd_psum(out, mp_axis)  # row out: sum the f/mp partials
        return out + b2[:, None, :]

    if router == "expert_choice":
        if dropless:
            raise ValueError(
                "moe_dropless applies to token-choice routing only; "
                "expert_choice is capacity-shaped by construction")
        return moe_expert_choice_ffn(
            x, gate_w, expert_apply, w1.shape[0],
            capacity_factor=capacity_factor, ep_axis=ep_axis)
    if dropless:
        return _run_dropless(
            lambda wa: moe_gelu_ffn_grouped(
                x, gate_w, w1, b1, w2, b2, top_k=top_k,
                normalize=normalize, activation=activation, with_aux=wa),
            ep_axis, mp_axis, aux_coef)
    return moe_dispatch_combine(
        x, gate_w, expert_apply, w1.shape[0], top_k=top_k,
        capacity_factor=capacity_factor, ep_axis=ep_axis,
        aux_coef=aux_coef, normalize=normalize)


def moe_swiglu_ffn_ep(x: jax.Array, router_w: jax.Array, wg: jax.Array,
                      wu: jax.Array, wd: jax.Array, *,
                      top_k: int = 2, capacity_factor: float = 1.25,
                      ep_axis: Optional[str] = None,
                      mp_axis: Optional[str] = None,
                      sequence_parallel: bool = False,
                      aux_coef: float = 0.0,
                      normalize: bool = True,
                      capacity: Optional[int] = None,
                      router: str = "topk",
                      dropless: bool = False) -> jax.Array:
    """SwiGLU mixture of experts (Mixtral-style Llama FFN): per-expert
    gate/up column-split + down row-split over ``mp_axis``, biasless.

    wg/wu: [E/ep, h, f/mp]; wd: [E/ep, f/mp, h].  Routing normalization
    follows the GShard convention (renormalize kept top-k weights) —
    numerically equivalent to Mixtral's softmax-over-top-k when no token
    overflows capacity."""
    def expert_apply(buf):
        y = buf
        if mp_axis is not None and not sequence_parallel:
            y = mp_copy(y, mp_axis)
        g = jnp.einsum("gch,ghf->gcf", y, wg)
        u = jnp.einsum("gch,ghf->gcf", y, wu)
        out = jnp.einsum("gcf,gfh->gch", jax.nn.silu(g) * u, wd)
        if mp_axis is not None:
            out = fwd_psum(out, mp_axis)
        return out

    if router == "expert_choice":
        if dropless:
            raise ValueError(
                "moe_dropless applies to token-choice routing only; "
                "expert_choice is capacity-shaped by construction")
        if capacity is not None:
            raise ValueError(
                "capacity override is a token-choice (no-drop) contract; "
                "expert_choice routing sizes its own buffers and can "
                "leave tokens unrouted — use router='topk' for serving")
        return moe_expert_choice_ffn(
            x, router_w, expert_apply, wg.shape[0],
            capacity_factor=capacity_factor, ep_axis=ep_axis)
    if dropless:
        # MegaBlocks-style dropless training (ragged_dot differentiates)
        return _run_dropless(
            lambda wa: moe_swiglu_ffn_grouped(
                x, router_w, wg, wu, wd, top_k=top_k,
                normalize=normalize, with_aux=wa),
            ep_axis, mp_axis, aux_coef)
    return moe_dispatch_combine(
        x, router_w, expert_apply, wg.shape[0], top_k=top_k,
        capacity_factor=capacity_factor, ep_axis=ep_axis,
        aux_coef=aux_coef, normalize=normalize, capacity=capacity)
