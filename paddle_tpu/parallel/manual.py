"""Manual-SPMD building blocks for fully-compiled hybrid parallel steps.

Everything here is meant to run INSIDE a ``jax.shard_map`` whose mesh makes
ALL hybrid axes (pp, dp, sharding, sep, mp) manual.  Round-1 mixed GSPMD
tensor-parallel sharding with a partial-manual shard_map pipeline, which
blew up SPMD partitioning / compile time on mp×pp meshes; the cure is to
express tensor parallelism the Megatron way — local shards + explicit
collectives — so XLA never has to propagate shardings through the pipeline.

Reference semantics being matched (cited per function):
* ``mp_copy``   — the Megatron "f" operator ``_c_identity``
  (/root/reference/python/paddle/distributed/fleet/layers/mpu/mp_ops.py:91):
  identity forward, all-reduce backward.
* ``vocab_parallel_embedding`` — masked local lookup + all-reduce
  (mp_layers.py:47 ``VocabParallelEmbedding`` / ``c_embedding`` op).
* ``vocab_parallel_nll`` — ``ParallelCrossEntropy`` (mp_layers.py:742,
  ``c_softmax_with_cross_entropy`` kernel): max/psum over the vocab-sharded
  logits, never materializing the full softmax.
* ``zero_adam_leaf_update`` — sharding stage-1/2 semantics
  (fleet/meta_optimizers/dygraph_optimizer/dygraph_sharding_optimizer.py:44,
  sharding/group_sharded_stage2.py:46): grads reduce-scattered to the owner
  shard, optimizer moments stored 1/shard per device, updated params
  all-gathered — expressed per-leaf on the leaf's (padded) ROWS.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .remat import remat_wrap
from .topology import (DP_AXIS, MP_AXIS, PP_AXIS, SEP_AXIS, SHARDING_AXIS,
                       HybridTopology)

__all__ = ["mp_copy", "fwd_psum", "vocab_parallel_embedding",
           "vocab_parallel_nll", "vocab_parallel_linear_nll",
           "zero_adam_leaf_update", "local_shape", "moment_shape",
           "MOMENT_SPEC", "tree_map_with_spec"]

# Optimizer-moment layout: [pp, mp, shard * rows, cols] — one fp32 chunk of
# the leaf's rows per (pp, mp, sharding) mesh coordinate, replicated over
# dp/sep (see moment_shape).
MOMENT_SPEC = P(PP_AXIS, MP_AXIS, SHARDING_AXIS)
# Expert-parallel leaves (param spec carries the dp axis — MoE expert
# banks): every (dp, sharding) coordinate owns distinct state, so the
# rows dim is sharded over both and NOT replicated over dp.
MOMENT_SPEC_EP = P(PP_AXIS, MP_AXIS, (DP_AXIS, SHARDING_AXIS))


def spec_has_axis(spec: P, axis: str) -> bool:
    """True if the PartitionSpec mentions ``axis`` (incl. tuple entries)."""
    for ax in tuple(spec):
        if ax is None:
            continue
        if axis in (ax if isinstance(ax, tuple) else (ax,)):
            return True
    return False


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def mp_copy(x, axis_name: str = MP_AXIS):
    """Identity forward / psum backward over the tensor-parallel axis.

    Insert before every column-parallel matmul whose input is replicated
    over mp: each rank's backward contribution through its weight shard is
    partial, and this operator's VJP all-reduces them (Megatron "f",
    reference mp_ops.py:91 ``_c_identity``)."""
    return x


def _mp_copy_fwd(x, axis_name):
    return x, None


def _mp_copy_bwd(axis_name, _, g):
    return (lax.psum(g, axis_name),)


mp_copy.defvjp(_mp_copy_fwd, _mp_copy_bwd)


def vocab_parallel_embedding(ids, wte_local, axis_name: str = MP_AXIS):
    """Vocab-parallel embedding lookup (reference mp_layers.py:47).

    ``wte_local``: [vocab/mp, h] local shard; ``ids``: global token ids.
    Masked local gather + psum over mp.  Returns [..., h].
    """
    vpr = wte_local.shape[0]
    off = lax.axis_index(axis_name) * vpr
    mask = (ids >= off) & (ids < off + vpr)
    x = jnp.take(wte_local, jnp.where(mask, ids - off, 0), axis=0)
    x = jnp.where(mask[..., None], x, jnp.zeros((), x.dtype))
    return fwd_psum(x, axis_name)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def fwd_psum(x, axis_name):
    """All-reduce forward / IDENTITY backward (the Megatron "g" operator,
    reference mp_ops.py:293 ``_mp_allreduce``).

    Use this — not raw ``lax.psum`` — for every forward-path all-reduce
    that autodiff will flow through inside a ``check_vma=False`` shard_map:
    there JAX transposes ``psum`` to another ``psum``, which multiplies the
    (replicated) cotangent by the axis size and silently scales gradients.
    Each device's summand has unit Jacobian w.r.t. the replicated output,
    so the correct VJP is the identity."""
    return lax.psum(x, axis_name)


fwd_psum.defvjp(lambda x, a: (lax.psum(x, a), None),
                lambda a, _, g: (g,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _pmax_stop(x, axis_name):
    """pmax with zero gradient (lax.pmax has no differentiation rule;
    the softmax max-subtraction is a constant shift mathematically)."""
    return lax.pmax(x, axis_name)


_pmax_stop.defvjp(lambda x, a: (lax.pmax(x, a), None),
                  lambda a, _, g: (jnp.zeros_like(g),))


def vocab_parallel_nll(logits_local, labels, axis_name: str = MP_AXIS):
    """Per-token negative log-likelihood over vocab-sharded logits.

    ``logits_local``: [..., vocab/mp] (fp32 recommended); ``labels``: global
    ids with the same leading shape.  Equivalent to the reference's
    ``ParallelCrossEntropy`` (mp_layers.py:742): global max via pmax, global
    sum-exp and label logit via psum — no full-vocab materialization.
    """
    vpr = logits_local.shape[-1]
    off = lax.axis_index(axis_name) * vpr
    lmax = _pmax_stop(jnp.max(lax.stop_gradient(logits_local), axis=-1),
                      axis_name)
    z = logits_local - lmax[..., None]
    sumexp = fwd_psum(jnp.sum(jnp.exp(z), axis=-1), axis_name)
    lse = jnp.log(sumexp)
    mask = (labels >= off) & (labels < off + vpr)
    li = jnp.where(mask, labels - off, 0)
    lab = jnp.take_along_axis(z, li[..., None], axis=-1)[..., 0]
    lab = fwd_psum(jnp.where(mask, lab, jnp.zeros((), z.dtype)), axis_name)
    return lse - lab


def vocab_parallel_linear_nll(x, w_local, labels, *, w_layout: str = "vh",
                              chunk=None, axis_name: str = MP_AXIS,
                              ignore_index=None, label_smoothing: float = 0.0):
    """Logits-free fused head for mp-sharded vocab: per-token NLL of the
    column-parallel ``x @ head`` computed by streaming vocab chunks —
    replaces the ``mp_copy`` → full-logits einsum → :func:`vocab_parallel_nll`
    pipeline.  The reference's two all-reduce passes (max, then sum-exp +
    label pick) fuse into one pmax + one stacked psum inside the chunk
    loop, and the backward's dx psum subsumes ``mp_copy``'s VJP.

    ``w_local``: [V/mp, h] (``w_layout="vh"``, tied-embedding layout) or
    [h, V/mp] (``"hv"``, Linear layout).  Must run inside the all-manual
    ``shard_map`` (``axis_name`` collectives); grads are meant to be taken
    INSIDE the shard_map (the ``fwd_psum`` convention).
    """
    from ..ops.fused_cross_entropy import linear_cross_entropy
    return linear_cross_entropy(
        x, w_local, labels, w_layout=w_layout, chunk=chunk,
        ignore_index=ignore_index, label_smoothing=label_smoothing,
        axis_name=axis_name, backend="xla")


def zero_adam_leaf_update(p, g, m, v, tf, *, lr, b1=0.9, b2=0.95,
                          eps=1e-8, weight_decay=0.0,
                          axis_name: str = SHARDING_AXIS):
    """ZeRO-sharded Adam step for one (local) parameter leaf.

    ``p``/``g``: the device-local shard of the param and its grad (grads
    must already be reduced over data axes; the sharding-axis reduction
    happens HERE via psum_scatter).  ``m``/``v``: this device's fp32
    moment chunk ``[rows, cols]`` — the leaf viewed as ``[R, cols]``
    (:func:`moment_shape`) with its rows split over the sharding axis,
    so each device owns 1/shard of the optimizer state (stage-1/2
    memory behavior, reference group_sharded_stage2.py:46).  Returns
    (p_new, m_new, v_new).
    """
    shard = lax.axis_size(axis_name)
    shape = p.shape
    rows, cols = m.shape
    R = p.size // cols
    pad = shard * rows - R

    def chunks(a):                       # leaf -> [shard, rows, cols] fp32
        a = a.astype(jnp.float32).reshape(R, cols)
        return jnp.pad(a, ((0, pad), (0, 0))).reshape(shard, rows, cols)

    # reduce-scatter: sum over the sharding axis, keep only our chunk
    g_loc = lax.psum_scatter(chunks(g), axis_name, scatter_dimension=0,
                             tiled=False)
    idx = lax.axis_index(axis_name)
    p_loc = lax.dynamic_index_in_dim(chunks(p), idx, 0, keepdims=False)
    m2 = b1 * m + (1 - b1) * g_loc
    v2 = b2 * v + (1 - b2) * g_loc * g_loc
    mh = m2 / (1 - b1 ** tf)
    vh = v2 / (1 - b2 ** tf)
    upd = mh / (jnp.sqrt(vh) + eps)
    if weight_decay:
        upd = upd + weight_decay * p_loc
    p_loc = p_loc - lr * upd
    p_new = lax.all_gather(p_loc, axis_name, tiled=False)
    p_new = p_new.reshape(shard * rows, cols)[:R]
    return p_new.reshape(shape).astype(p.dtype), m2, v2


def vpp_block_layout(blk_specs, S: int, vpp: int, num_layers: int):
    """Interleaved-schedule block layout shared by the model builders:
    validates divisibility, inserts the chunk axis into each block spec
    ([S, v, per_v, ...]), and returns a restacker mapping a
    [S*v, per_v, ...] vs-major stack to [S, v, per_v, ...] where element
    [s, c] holds virtual stage s + S*c (the layout
    spmd_pipeline_interleaved expects)."""
    if vpp <= 1:
        return blk_specs, None
    if num_layers % (S * vpp) != 0:
        raise ValueError(
            f"num_layers {num_layers} not divisible by pp*chunks "
            f"{S}*{vpp}")
    specs = {k: P(*(tuple(sp)[:1] + (None,) + tuple(sp)[1:]))
             for k, sp in blk_specs.items()}

    def restack(stacked):
        return {n: jnp.transpose(
                    val.reshape((vpp, S) + val.shape[1:]),
                    (1, 0) + tuple(range(2, val.ndim + 1)))
                for n, val in stacked.items()}

    return specs, restack


def pack_leaf(p_local, chunk: int, axis_name: str = SHARDING_AXIS):
    """Flat-shard a device-local param leaf over the sharding axis:
    keep only this device's ``chunk`` of the padded flat view (ZeRO
    stage-3 at-rest layout, reference group_sharded_stage3.py:85
    _param_storage)."""
    shard = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    flat = jnp.pad(p_local.reshape(-1), (0, shard * chunk - p_local.size))
    return lax.dynamic_index_in_dim(flat.reshape(shard, chunk), idx, 0,
                                    keepdims=False)


def unpack_leaf(p_flat, shape, dtype=None, axis_name: str = SHARDING_AXIS):
    """Gather-at-use: reassemble the full local leaf from the per-device
    flat shards (stage-3 ``_gather`` before forward use).  Differentiating
    through this all_gather transposes into exactly the stage-3
    reduce-scatter of the gradient — no separate grad plumbing."""
    full = lax.all_gather(p_flat, axis_name, tiled=False).reshape(-1)
    n = int(np.prod(shape))
    out = full[:n].reshape(shape)
    return out.astype(dtype) if dtype is not None else out


def zero3_adam_leaf_update(p_flat, g_flat, m, v, tf, *, lr, b1=0.9, b2=0.95,
                           eps=1e-8, weight_decay=0.0):
    """Adam on the flat-sharded stage-3 layout: everything device-local
    elementwise (the sharding-axis grad reduction already happened in the
    all_gather transpose), params stay sharded — no post-update gather."""
    g32 = g_flat.astype(jnp.float32)
    p32 = p_flat.astype(jnp.float32)
    m2 = b1 * m + (1 - b1) * g32
    v2 = b2 * v + (1 - b2) * g32 * g32
    mh = m2 / (1 - b1 ** tf)
    vh = v2 / (1 - b2 ** tf)
    upd = mh / (jnp.sqrt(vh) + eps)
    if weight_decay:
        upd = upd + weight_decay * p32
    return (p32 - lr * upd).astype(p_flat.dtype), m2, v2


def build_hybrid_train_step(*, topo: HybridTopology, param_specs,
                            init_params_fn, embed_fn, block_fn, head_nll_fn,
                            step_ctx_fn=None,
                            num_microbatches: int = 1,
                            learning_rate: float = 1e-4,
                            adam_betas=(0.9, 0.95), adam_eps: float = 1e-8,
                            weight_decay: float = 0.0, remat: bool = True,
                            remat_policy=None,
                            schedule: str = "1f1b",
                            num_model_chunks: int = 1,
                            sharding_stage: int = 2,
                            offload_optimizer: bool = False,
                            mp_reduce_block_leaves=frozenset()):
    """Generic fully-manual hybrid dp×mp×pp×sharding×sep train step.

    The caller provides the model as three per-device closures (all called
    INSIDE the all-axes-manual shard_map, so they may use mp/sep
    collectives from this module):

    * ``init_params_fn(seed) -> params`` — global arrays placed per
      ``param_specs``; structure must be ``{"blocks": {...stacked
      [pp, per, ...] leaves...}, <other leaves replicated over pp>}``.
    * ``embed_fn(params_local, ids_local) -> x [b_l, s_l, h]``
    * ``block_fn(layer_params_local, x, ctx) -> x`` — one transformer block
      (tensor-parallel via mp_copy/fwd_psum, cp attention inside).
    * ``head_nll_fn(params_local, x, labels_local) -> nll [b_l, s_l]`` —
      model builders pass the logits-free fused head here
      (:func:`vocab_parallel_linear_nll` /
      ``ops.fused_cross_entropy.linear_cross_entropy``); being a
      ``custom_vjp`` closure it flows unchanged through every schedule
      (gpipe scan, 1f1b/zbh1, interleave) and under remat, so no
      pipeline path ever materializes ``[b, s, V]`` logits.
    * ``step_ctx_fn(s_l) -> ctx`` (optional) — per-step loop invariants
      (e.g. rope cos/sin tables) computed ONCE outside the layer scan and
      passed to every ``block_fn`` call; ``ctx`` is None when omitted.

    The step runs the block stack through the pipeline over ``pp``
    (parallel/pipeline.py), reduces the masked last-stage loss over
    (pp, dp, sharding, sep), reduces grads over the data axes (plus pp for
    the non-block leaves, never mp — Megatron invariant), and applies
    ZeRO stage-2 Adam over the ``sharding`` axis
    (:func:`zero_adam_leaf_update`).

    ``schedule`` (pp>1 only): ``"1f1b"`` (default), ``"gpipe"``,
    ``"interleave"`` (virtual-pipeline chunks via ``num_model_chunks``),
    or ``"zbh1"`` (zero-bubble: weight-grad deferred into the drain
    bubble).  ``"1f1b"`` interleaves forward and
    recompute-backward per tick with O(pp) activation memory
    (:func:`~paddle_tpu.parallel.pipeline.spmd_pipeline_1f1b`, matching the
    reference's production 1F1B pipeline_parallel.py:547); ``"gpipe"`` is
    the fill-drain scan differentiated end-to-end (O(M) memory,
    reference FThenB).

    ``mp_reduce_block_leaves``: block-param leaf names whose grads are
    PARTIAL over mp and need a psum — used by Megatron sequence
    parallelism, where LayerNorms/biases run on the mp-sharded sequence
    (the compiled-step analog of the reference's
    register_sequence_parallel_allreduce_hooks).

    Returns ``(step_fn, init_fn)`` with
    ``step_fn(state, ids, labels) -> (state, loss)``.
    """
    import jax.numpy as _jnp
    from jax.sharding import NamedSharding
    from .pipeline import (spmd_pipeline, spmd_pipeline_1f1b,
                           spmd_pipeline_zbh1)

    if schedule not in ("1f1b", "gpipe", "interleave", "zbh1"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    if sharding_stage not in (2, 3):
        raise ValueError(f"sharding_stage must be 2 or 3, got "
                         f"{sharding_stage}")
    mesh = topo.mesh
    S = topo.axis_size(PP_AXIS)
    dp = topo.axis_size(DP_AXIS)
    shard = topo.axis_size(SHARDING_AXIS)
    sep = topo.axis_size(SEP_AXIS)
    mp_deg = topo.axis_size(MP_AXIS)
    b1, b2 = adam_betas
    data_spec = P((DP_AXIS, SHARDING_AXIS), SEP_AXIS)

    # stage-3: params live flat-sharded at rest (same chunk layout as the
    # moments) and are all_gather'ed AT USE — per layer inside the scan,
    # so off-layer weights cost 1/shard of their size.  The AD transpose
    # of that gather is the stage-3 grad reduce-scatter for free.
    # Under the interleaved schedule blocks carry an extra chunk axis
    # ([S, v, per, ...] — vpp_block_layout), so the flat at-rest layout
    # keeps ALL leading axes between pp and the layer dims ("lead").
    vpp_deg = num_model_chunks if schedule == "interleave" else 1
    n_lead = 2 if vpp_deg > 1 else 1
    BLOCK_FLAT_SPEC = P(PP_AXIS, *((None,) * n_lead + (MP_AXIS,)),
                        SHARDING_AXIS)
    BLOCK_FLAT_SPEC_EP = P(PP_AXIS, *((None,) * n_lead + (MP_AXIS,)),
                           (DP_AXIS, SHARDING_AXIS))
    # expert-parallel leaves: the param spec shards them over dp, so their
    # grads are NOT reduced over dp (each data rank owns distinct experts)
    # and their moments/flat storage carry a dp dimension
    ep_leaves = {k for k, s in param_specs.get("blocks", {}).items()
                 if spec_has_axis(s, DP_AXIS)}
    stage3 = sharding_stage == 3
    if stage3:
        p_abs = jax.eval_shape(init_params_fn, 0)

        def _leaf_info(leaf, spec, is_block):
            ls = local_shape(leaf.shape, spec, topo)
            if is_block:
                layer = tuple(ls[1 + n_lead:])
                n = int(np.prod(layer)) or 1
                return {"local": layer, "lead": tuple(ls[1:1 + n_lead]),
                        "chunk": -(-n // shard), "dtype": leaf.dtype}
            n = int(np.prod(ls)) or 1
            return {"local": tuple(ls), "chunk": -(-n // shard),
                    "dtype": leaf.dtype}

        info = {k: _leaf_info(p_abs[k], param_specs[k], False)
                for k in p_abs if k != "blocks"}
        info["blocks"] = {k: _leaf_info(p_abs["blocks"][k],
                                        param_specs["blocks"][k], True)
                          for k in p_abs["blocks"]}
        flat_specs = {k: MOMENT_SPEC for k in p_abs if k != "blocks"}
        flat_specs["blocks"] = {k: BLOCK_FLAT_SPEC_EP if k in ep_leaves
                                else BLOCK_FLAT_SPEC
                                for k in p_abs["blocks"]}
        store_specs = flat_specs
        mom_specs = flat_specs
    else:
        store_specs = param_specs
        mom_specs = tree_map_with_spec(
            lambda _p, s: (MOMENT_SPEC_EP if spec_has_axis(s, DP_AXIS)
                           else MOMENT_SPEC),
            param_specs, param_specs)

    def sh(spec):
        return NamedSharding(mesh, spec)

    def _flat_shape(k, k2=None):
        if k2 is None:
            return (S, mp_deg, shard * info[k]["chunk"])
        dpf = dp if k2 in ep_leaves else 1
        return (S,) + info["blocks"][k2]["lead"] + (
            mp_deg, dpf * shard * info["blocks"][k2]["chunk"])

    def init_fn(seed: int = 0):
        params = init_params_fn(seed)
        if stage3:
            def pack_local(prm):
                out = {"blocks": {}}
                for k in prm:
                    if k == "blocks":
                        continue
                    out[k] = pack_leaf(prm[k], info[k]["chunk"])[None, None]
                for k, val in prm["blocks"].items():
                    inf = info["blocks"][k]
                    c = inf["chunk"]
                    lv = val[0].reshape((-1,) + inf["local"])
                    packed = jax.vmap(lambda l, c=c: pack_leaf(l, c))(lv)
                    out["blocks"][k] = packed.reshape(
                        (1,) + inf["lead"] + (1, c))
                return out

            pack = jax.jit(jax.shard_map(
                pack_local, mesh=mesh, in_specs=(param_specs,),
                out_specs=flat_specs, check_vma=False))
            params = pack(params)
            mom_shapes = {k: _flat_shape(k) for k in info if k != "blocks"}
            mom_shapes["blocks"] = {k: _flat_shape("blocks", k)
                                    for k in info["blocks"]}
        else:
            mom_shapes = tree_map_with_spec(
                lambda p, spec: moment_shape(p.shape, spec, topo),
                params, param_specs)
        zinit = jax.jit(
            lambda: tree_map_with_spec(
                lambda shp, _: _jnp.zeros(shp, _jnp.float32),
                mom_shapes, mom_specs),
            out_shardings=tree_map_with_spec(
                lambda _s, sp: sh(sp), mom_shapes, mom_specs))
        m0, v0 = zinit(), zinit()
        # t is committed to the mesh (replicated) like every other leaf:
        # a checkpoint load preserves leaf shardings, and a state whose
        # leaves mix mesh-committed and single-device-committed arrays is
        # rejected by jit
        t0 = jax.device_put(_jnp.zeros((), _jnp.int32), sh(P()))
        return {"params": params,
                "opt": {"m": m0, "v": v0, "t": t0}}

    def local_step(params, m, v, t, ids, labels):
        b_l, s_l = ids.shape
        # per-step loop invariants + the one-layer scan body, shared by
        # both schedules (ctx never depends on params, so it can live
        # outside the differentiated region)
        ctx = step_ctx_fn(s_l) if step_ctx_fn is not None else None

        def _unpack_other(prm):
            return {k: unpack_leaf(v[0, 0], info[k]["local"],
                                   info[k]["dtype"])
                    for k, v in prm.items() if k != "blocks"}

        def body(carry, layer_params):
            if stage3:
                layer_params = {
                    k: unpack_leaf(v.reshape(-1),
                                   info["blocks"][k]["local"],
                                   info["blocks"][k]["dtype"])
                    for k, v in layer_params.items()}
            return block_fn(layer_params, carry, ctx), None

        def run_stack(x, blk, use_remat):
            """The per-stage layer stack.  Stage 2 scans (one traced
            block); stage 3 UNROLLS so each layer's weight all_gather is a
            distinct collective — a scanned gather is one HLO op executed
            per iteration with no cross-iteration data dependence, which
            XLA overlaps: on TPU that just prefetches weights early, but
            XLA:CPU's in-process rendezvous aborts on the repeated joins.
            Unrolling also lets the TPU scheduler hide each gather behind
            the previous layer's compute (the stage-3 prefetch pattern,
            reference group_sharded_stage3 _prefetch)."""
            if stage3:
                def one(c, lp):
                    return body(c, lp)[0]

                fn = remat_wrap(one, use_remat, remat_policy)
                per = next(iter(blk.values())).shape[0]
                for i in range(per):
                    x = fn(x, {k: lax.index_in_dim(v, i, 0, keepdims=False)
                               for k, v in blk.items()})
                return x
            sbody = remat_wrap(body, use_remat, remat_policy)
            x, _ = lax.scan(sbody, x, blk)
            return x

        def loss_fn(params):
            if stage3:
                params = dict(_unpack_other(params),
                              blocks=params["blocks"])
            x = embed_fn(params, ids)
            hdim = x.shape[-1]
            blk = {k: val[0] for k, val in params["blocks"].items()}

            if S > 1:
                M = num_microbatches
                mbs = x.reshape(M, b_l // M, s_l, hdim)

                def stage_fn(blk_local, hcarry):
                    # spmd_pipeline applies its own remat around the stage
                    return run_stack(hcarry, blk_local,
                                     use_remat=stage3 and remat)

                outs = spmd_pipeline(stage_fn, blk, mbs, S, remat=remat,
                                     remat_policy=remat_policy)
                x = outs.reshape(b_l, s_l, hdim)
            else:
                x = run_stack(x, blk, use_remat=remat)

            nll = head_nll_fn(params, x, labels)
            # loss lives on the LAST pp stage only (other stages computed
            # the head on zeros); psum with the mask so grads flow to
            # exactly one stage's head and the scalar is replicated.
            is_last = (lax.axis_index(PP_AXIS) == S - 1)
            total = fwd_psum(
                jnp.sum(nll) * is_last.astype(nll.dtype),
                (PP_AXIS, DP_AXIS, SHARDING_AXIS, SEP_AXIS))
            return total / (b_l * s_l * dp * shard * sep)

        norm = b_l * s_l * dp * shard * sep
        if S > 1 and schedule == "interleave":
            from .pipeline import spmd_pipeline_interleaved
            M = num_microbatches
            n_chunks = num_model_chunks
            other = {k: val for k, val in params.items() if k != "blocks"}
            blk = {k: val[0] for k, val in params["blocks"].items()}
            ids_mb = ids.reshape(M, b_l // M, s_l)
            labels_mb = labels.reshape(M, b_l // M, s_l)

            def mb_fn_v(other_p, blk_c, x_in, ids1, labels1, first, last):
                if stage3:
                    other_p = _unpack_other(other_p)
                p = dict(other_p, blocks=None)
                x0 = embed_fn(p, ids1)
                x = jnp.where(first, x0, x_in)
                y = run_stack(x, blk_c, use_remat=remat)
                nll = head_nll_fn(p, y, labels1)
                return y, jnp.sum(nll) * last.astype(nll.dtype)

            def _embed_probe_v(o, i):
                if stage3:
                    o = _unpack_other(o)
                return embed_fn(dict(o, blocks=None), i)

            xa = jax.eval_shape(_embed_probe_v, other, ids_mb[0])
            nll_sum, d_other, d_blk = spmd_pipeline_interleaved(
                mb_fn_v, other, blk, ids_mb, labels_mb, xa.shape, xa.dtype,
                S, n_chunks)
            loss = fwd_psum(nll_sum,
                            (PP_AXIS, DP_AXIS, SHARDING_AXIS, SEP_AXIS))                 / norm
            grads = {k: g / norm for k, g in d_other.items()}
            grads["blocks"] = {k: g[None] / norm for k, g in d_blk.items()}
        elif S > 1 and schedule in ("1f1b", "zbh1"):
            M = num_microbatches
            other = {k: v for k, v in params.items() if k != "blocks"}
            blk = {k: v[0] for k, v in params["blocks"].items()}
            ids_mb = ids.reshape(M, b_l // M, s_l)
            labels_mb = labels.reshape(M, b_l // M, s_l)

            def mb_fn(other_p, blk_p, x_in, ids1, labels1):
                if stage3:
                    other_p = _unpack_other(other_p)
                p = dict(other_p, blocks=None)
                x0 = embed_fn(p, ids1)
                x = jnp.where(lax.axis_index(PP_AXIS) == 0, x0, x_in)
                y = run_stack(x, blk_p, use_remat=remat)
                nll = head_nll_fn(p, y, labels1)
                last = (lax.axis_index(PP_AXIS) == S - 1)
                return y, jnp.sum(nll) * last.astype(nll.dtype)

            def _embed_probe(o, i):
                if stage3:
                    o = _unpack_other(o)
                return embed_fn(dict(o, blocks=None), i)

            xa = jax.eval_shape(_embed_probe, other, ids_mb[0])
            sched_fn = spmd_pipeline_1f1b if schedule == "1f1b" \
                else spmd_pipeline_zbh1
            nll_sum, d_other, d_blk = sched_fn(
                mb_fn, other, blk, ids_mb, labels_mb,
                xa.shape, xa.dtype, S)
            loss = fwd_psum(nll_sum,
                            (PP_AXIS, DP_AXIS, SHARDING_AXIS, SEP_AXIS)) \
                / norm
            grads = {k: v / norm for k, v in d_other.items()}
            grads["blocks"] = {k: v[None] / norm for k, v in d_blk.items()}
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params)
        t2 = t + 1
        tf = t2.astype(_jnp.float32)

        def upd(is_blocks, p, g, m_leaf, v_leaf, mp_partial=False,
                ep=False):
            # data-axis grad reduction; non-block leaves are replicated
            # over pp (stage0 embeds, last stage heads) so sum over pp
            # too.  NEVER over mp (mp-replicated params get full grads
            # via mp_copy's bwd psum, mp-sharded ones are local) — except
            # sequence-parallel leaves, whose activations were mp-sharded
            # along seq so each rank saw only its tokens.  Expert leaves
            # (``ep``) skip the dp reduction: each data rank's expert
            # grads are complete after the all_to_all routing round-trip.
            red = ((SEP_AXIS,) if ep else (DP_AXIS, SEP_AXIS)) \
                if is_blocks else (PP_AXIS, DP_AXIS, SEP_AXIS)
            if mp_partial:
                red = red + (MP_AXIS,)
            g = lax.psum(g, red)
            if stage3:
                # flat layout end to end: the sharding-axis reduction
                # already happened in the unpack_leaf transpose
                return zero3_adam_leaf_update(
                    p, g, m_leaf, v_leaf, tf, lr=learning_rate, b1=b1,
                    b2=b2, eps=adam_eps, weight_decay=weight_decay)
            p2, m2, v2 = zero_adam_leaf_update(
                p, g, m_leaf.reshape(m_leaf.shape[-2:]),
                v_leaf.reshape(v_leaf.shape[-2:]), tf,
                lr=learning_rate, b1=b1, b2=b2, eps=adam_eps,
                weight_decay=weight_decay)
            return p2, m2.reshape(m_leaf.shape), v2.reshape(v_leaf.shape)

        new_p = dict(blocks={})
        new_m = dict(blocks={})
        new_v = dict(blocks={})
        for k in params:
            if k == "blocks":
                continue
            new_p[k], new_m[k], new_v[k] = upd(
                False, params[k], grads[k], m[k], v[k])
        for k in params["blocks"]:
            (new_p["blocks"][k], new_m["blocks"][k],
             new_v["blocks"][k]) = upd(
                True, params["blocks"][k], grads["blocks"][k],
                m["blocks"][k], v["blocks"][k],
                mp_partial=k in mp_reduce_block_leaves,
                ep=k in ep_leaves)
        return new_p, new_m, new_v, t2, loss

    shd = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(store_specs, mom_specs, mom_specs, P(), data_spec,
                  data_spec),
        out_specs=(store_specs, mom_specs, mom_specs, P(), P()),
        check_vma=False)

    def step(state, ids, labels):
        p2, m2, v2, t2, loss = shd(state["params"], state["opt"]["m"],
                                   state["opt"]["v"], state["opt"]["t"],
                                   ids, labels)
        return {"params": p2, "opt": {"m": m2, "v": v2, "t": t2}}, loss

    step_fn = jax.jit(step, donate_argnums=(0,))
    if offload_optimizer:
        mom_shardings = tree_map_with_spec(lambda _s, sp: sh(sp),
                                           mom_specs, mom_specs)
        return _offload_opt_state(step_fn, init_fn,
                                  {"m": mom_shardings, "v": mom_shardings})
    return step_fn, init_fn


def _offload_opt_state(step_fn, init_fn, mom_shardings):
    """Optimizer-state host offload (reference group_sharded offload=True /
    sharding_offload: fp32 moments live in HOST RAM between steps and are
    shipped to the device around each update).  The explicit
    device_put/device_get pair outside jit is the backend-portable form of
    the reference's pinned-memory optimizer; the per-step transfer is the
    price of the HBM savings, exactly as in the reference."""
    import numpy as _np

    def init2(seed: int = 0):
        state = init_fn(seed)
        opt = state["opt"]
        host = {"m": jax.tree.map(lambda a: _np.asarray(a), opt["m"]),
                "v": jax.tree.map(lambda a: _np.asarray(a), opt["v"])}
        state["opt"] = {"m": host["m"], "v": host["v"], "t": opt["t"]}
        return state

    def step2(state, ids, labels):
        # shardings come from the builder's moment specs, so a state
        # restored from a checkpoint (no init_fn call) steps fine
        sh = mom_shardings
        dev_state = {
            "params": state["params"],
            "opt": {"m": jax.tree.map(jax.device_put, state["opt"]["m"],
                                      sh["m"]),
                    "v": jax.tree.map(jax.device_put, state["opt"]["v"],
                                      sh["v"]),
                    "t": state["opt"]["t"]},
        }
        new_state, loss = step_fn(dev_state, ids, labels)
        new_state["opt"] = {
            "m": jax.tree.map(lambda a: _np.asarray(a),
                              new_state["opt"]["m"]),
            "v": jax.tree.map(lambda a: _np.asarray(a),
                              new_state["opt"]["v"]),
            "t": new_state["opt"]["t"]}
        return new_state, loss

    return step2, init2


def local_shape(shape: Tuple[int, ...], spec: P,
                topo: HybridTopology) -> Tuple[int, ...]:
    """Device-local shape of a global array laid out with ``spec``."""
    out = list(shape)
    for i, ax in enumerate(tuple(spec)[:len(out)]):
        if ax is None:
            continue
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            size = topo.axis_size(a)
            if out[i] % size != 0:
                raise ValueError(
                    f"dim {i} of {shape} not divisible by {a}={size}")
            out[i] //= size
    return tuple(out)


def train_state_bytes(params, param_specs, topo: HybridTopology) -> int:
    """Per-device HBM a ZeRO stage-2 Adam step pins: the local param
    shards, their gradients (same dtype) and the two fp32 moments, which
    are split over the sharding axis.  ``params`` may be abstract
    (``jax.eval_shape`` of the init).  Stage 3 pins less (params are
    sharded at rest too), so for it this is an upper bound."""
    shard = topo.axis_size(SHARDING_AXIS)
    total = 0

    def add(leaf, spec):
        nonlocal total
        n = int(np.prod(local_shape(leaf.shape, spec, topo)))
        total += 2 * n * leaf.dtype.itemsize + 8 * -(-n // shard)

    tree_map_with_spec(add, params, param_specs)
    return total


def moment_shape(param_shape: Tuple[int, ...], spec: P,
                 topo: HybridTopology) -> Tuple[int, int, int, int]:
    """Global shape of the ZeRO moment buffer for one param leaf:
    ``[pp, mp, shard*rows, cols]``.  The device-local leaf is viewed as
    ``[R, cols]`` — ``cols`` its last dim, ``R`` the product of the
    others — and its ROWS are chunked over the sharding axis,
    ``rows = ceil(R/shard)``.  Expert (dp-sharded) leaves get a dp
    factor on the rows dim to match MOMENT_SPEC_EP — each data rank's
    experts carry their own moments.

    Rows, not a flat 1-D chunk: merging leading dims keeps the minor
    dim and its tiling whole, so the update reads params and grads
    where they lie.  A flat chunk made the TPU re-tile every param and
    grad each step into 1-D fp32 copies: compiled for a v5e, ONE
    [4, 4096, 11008] leaf took 281 s, 145 MiB of code and 2 GiB of
    temporaries against 1.6 s, 0.2 MiB and none for this layout."""
    ls = local_shape(param_shape, spec, topo)
    cols = ls[-1] if ls else 1
    R = int(np.prod(ls[:-1])) or 1
    shard = topo.axis_size(SHARDING_AXIS)
    dpf = topo.axis_size(DP_AXIS) if spec_has_axis(spec, DP_AXIS) else 1
    return (topo.axis_size(PP_AXIS), topo.axis_size(MP_AXIS),
            dpf * shard * -(-R // shard), cols)


def tree_map_with_spec(fn, tree, specs):
    """tree_map over a nested dict whose spec tree has PartitionSpec leaves
    (PartitionSpec is tuple-like, so jax.tree.map can't be trusted here)."""
    if isinstance(tree, dict):
        return {k: tree_map_with_spec(fn, tree[k], specs[k]) for k in tree}
    return fn(tree, specs)
