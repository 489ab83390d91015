"""AOT export/load for the continuous-batching serving engine.

A fleet restart constructs thousands of ``ContinuousBatchingEngine``
instances over the same weights and geometry; per-process tracing of
the decode step plus one chunk-fill per bucket is pure waste.  One
process exports once::

    eng = ContinuousBatchingEngine(cfg, params, prefill_buckets=(16, 64))
    aot.export_engine(eng, "artifacts/serve")

and every other process warm-starts::

    eng = ContinuousBatchingEngine(cfg, params, aot_dir="artifacts/serve")

with ZERO backend compiles (pinned by the compile-budget ratchet's
``serve_aot_warm`` scenario).  The manifest's config hash covers the
model config, batch/pool geometry, and the parameter tree signature,
so a mismatched engine falls back to fresh compiles instead of running
a wrong program.

Like the fresh engine, the exported steps donate the KV pools.

Sampler coverage (ISSUE 7): the engine samples every sub-batch at the
FIXED decode width ``max_batch`` (rows padded; vmap keeps real rows
independent of padding), so exactly one sampler program exists per
engine geometry and it is exported here next to the decode step — a
warm-started engine with per-request sampling enabled performs zero
backend compiles (pinned by the ``serve_aot_warm_sampled`` budget row).

Speculative decoding (ISSUE 8): a speculating engine
(``spec_config=``) owns exactly two more fixed geometries — the
``[max_batch, window]`` draft and the ``[max_batch, k+1]`` verify —
exported as ``spec_draft`` / ``spec_verify`` with the spec geometry in
the config hash, so warm speculative serving is also zero backend
compiles (``serve_spec_warm`` budget row).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .artifact import (ArtifactStore, AotManifestMismatchError,
                       args_signature, fresh_backend_compile)
from .buckets import DEFAULT_CHUNK_BUCKETS, ShapeBucketRegistry

__all__ = ["export_engine", "load_engine_artifacts", "engine_config",
           "warm_engine_factory"]

_DECODE = "decode"
_FILL = "chunk_fill_{c}"
_SAMPLER = "sampler"
_DRAFT = "spec_draft"
_VERIFY = "spec_verify"


def engine_config(engine) -> Dict[str, Any]:
    """Everything the compiled serve programs are specialized to:
    model config, batch/pool geometry, the weight-tree signature, and
    (when speculating) the draft/verify geometry — an artifact exported
    without speculation can never half-warm-start a speculating engine,
    it is a config mismatch and a clean fallback."""
    from ..ops.paged_kv import is_quantized_pool
    params_td, params_leaves = args_signature((engine.params,))
    pool_k = engine.pool_k
    pool_dtype = (f"{pool_k.data.dtype}+{pool_k.scale.dtype}-scale"
                  if is_quantized_pool(pool_k) else str(pool_k.dtype))
    qc = getattr(engine, "quant_config", None)
    cfg = {
        "kind": "continuous_batching_engine",
        "model": dataclasses.asdict(engine.cfg),
        "max_batch": engine.B,
        "block_size": engine.BS,
        "max_blocks_per_seq": engine.MB,
        "num_blocks": engine.alloc.num_blocks,
        "pool_dtype": pool_dtype,
        # the quantization config changes the compiled programs (weight
        # leaf layout, dequant matmuls, pool pytree) AND the params
        # signature — hash it explicitly so an artifact exported at one
        # quantization can never half-warm-start another (ISSUE 16)
        "quant": qc.describe() if qc is not None else None,
        # the cross-request prefix cache (ISSUE 14) never changes a
        # compiled program, so its POLICY knobs (offload capacity,
        # enabled flag) stay out of the hash — but the block-key SCHEME
        # defines what a cached chain means, and a scheme bump must
        # invalidate warm starts rather than let two generations
        # disagree about prefix identity
        "prefix_scheme": type(engine.prefix_cache).SCHEME
        if hasattr(engine, "prefix_cache") else None,
        "params_treedef": params_td,
        "params_leaves": params_leaves,
    }
    if engine.spec_config is not None:
        spec = dict(engine.spec_config.manifest())
        dtd, dleaves = args_signature((engine.spec_config.draft_params,))
        spec["draft_params_treedef"] = dtd
        spec["draft_params_leaves"] = dleaves
        cfg["spec"] = spec
    return cfg


def _decode_args(engine) -> Tuple:
    """The exact decode-step call signature ``Engine.step`` uses."""
    return (engine.params, engine.pool_k, engine.pool_v,
            jnp.asarray(engine.block_table), jnp.asarray(engine.lengths),
            jnp.asarray(engine.tokens))


def _fill_args(engine, size: int) -> Tuple:
    """The exact bucketed chunk-fill call signature the scheduler uses."""
    return (engine.params, engine.pool_k, engine.pool_v,
            jnp.asarray(engine.block_table[0]), jnp.int32(0),
            jnp.asarray(np.zeros((size,), np.int32)), jnp.int32(1))


def _draft_args(engine) -> Tuple:
    """The fixed [max_batch, window] draft call signature."""
    sc = engine.spec_config
    return (sc.draft_params,
            jnp.asarray(np.zeros((engine.B, sc.window), np.int32)),
            jnp.asarray(np.zeros((engine.B,), np.int32)))


def _verify_args(engine) -> Tuple:
    """The fixed [max_batch, k+1] verify call signature (pools + page
    table exactly as the decode step takes them)."""
    sc = engine.spec_config
    return (engine.params, engine.pool_k, engine.pool_v,
            jnp.asarray(engine.block_table), jnp.asarray(engine.lengths),
            jnp.asarray(np.zeros((engine.B, sc.k + 1), np.int32)))


def _sampler_args(engine) -> Tuple:
    """The fixed-width sampler call signature (``_sample_rows`` pads
    every sub-batch to ``max_batch`` rows)."""
    B = engine.B
    V = int(engine.params["head"].shape[-1])
    return (jnp.asarray(np.zeros((B, V), np.float32)),
            jnp.asarray(np.zeros((B,), np.int32)),
            jnp.asarray(np.zeros((B,), np.int32)),
            jnp.asarray(np.ones((B,), np.float32)),
            jnp.asarray(np.zeros((B,), np.int32)),
            jnp.asarray(np.zeros((B,), np.float32)))


def export_engine(engine, directory: str, *,
                  buckets: Optional[ShapeBucketRegistry] = None,
                  rotate: bool = False, keep_last: Optional[int] = None,
                  registry=None) -> ArtifactStore:
    """Trace, lower, compile, and serialize the engine's decode step
    plus one bucketed chunk-fill per declared prefill bucket (and, for
    a speculating engine, the draft + verify programs).

    With ``rotate=True``, ``directory`` is a rotation ROOT: the export
    lands in a fresh ``gen-NNNN`` subdirectory and is published through
    the atomic ``latest`` pointer once complete (``keep_last`` prunes
    older generations) — loaders passing the root as ``aot_dir`` follow
    the pointer."""
    if getattr(engine, "ssm_state", None) is not None:
        raise NotImplementedError(
            "AOT export is not supported for a model with per-slot "
            "recurrent state: the manifest hashes neither the state's "
            "geometry nor the layer pattern, so a warm start could load "
            "programs for another model")
    if getattr(engine, "pool_v", True) is None:
        raise NotImplementedError(
            "AOT export is not supported for a model with a latent "
            "cache: the manifest does not hash a latent pool's geometry")
    breg = buckets or getattr(engine, "_buckets", None) or \
        ShapeBucketRegistry(DEFAULT_CHUNK_BUCKETS)
    if breg.max_batch is None:
        breg = ShapeBucketRegistry(breg.chunk_sizes, max_batch=engine.B)
    donate = (1, 2)
    if rotate:
        from .artifact import new_generation
        store = new_generation(directory, registry=registry)
    else:
        store = ArtifactStore(directory, registry=registry)
    store.begin(config=engine_config(engine),
                buckets=breg.to_manifest())

    with fresh_backend_compile():
        args = _decode_args(engine)
        compiled = jax.jit(engine._build_step(),
                           donate_argnums=donate).lower(*args).compile()
        store.put(_DECODE, compiled, args, donate_argnums=donate)

        for c in breg.chunk_sizes:
            args = _fill_args(engine, c)
            compiled = jax.jit(engine._build_chunk_fill(c),
                               donate_argnums=donate
                               ).lower(*args).compile()
            store.put(_FILL.format(c=c), compiled, args,
                      donate_argnums=donate)

        # per-request sampling runs at the fixed decode width, so ONE
        # program covers every sampled sub-batch (never donated — the
        # sampler owns no buffers)
        from ..inference.serving import build_sampler
        args = _sampler_args(engine)
        compiled = jax.jit(build_sampler()).lower(*args).compile()
        store.put(_SAMPLER, compiled, args)

        # speculative decode (ISSUE 8): the windowed draft and the
        # fixed-width K+1 verify are one program each per engine
        # geometry — exported so a speculating warm start is zero
        # backend compiles (serve_spec_warm budget row)
        if engine.spec_config is not None:
            from ..spec_decode import (build_draft_program,
                                       build_verify_program)
            sc = engine.spec_config
            args = _draft_args(engine)
            compiled = jax.jit(build_draft_program(
                sc.draft_cfg, sc.window)).lower(*args).compile()
            store.put(_DRAFT, compiled, args)
            args = _verify_args(engine)
            compiled = jax.jit(
                build_verify_program(engine._build_step()),
                donate_argnums=donate).lower(*args).compile()
            store.put(_VERIFY, compiled, args, donate_argnums=donate)
    if rotate:
        store.publish(keep_last=keep_last)
    return store


def warm_engine_factory(cfg, params, *, aot_dir: str,
                        require_warm: bool = True, **engine_kwargs):
    """Zero-arg engine factory for the resilience supervisor
    (``serving.SupervisedEngine``): every call constructs a
    ``ContinuousBatchingEngine`` warm-started from ``aot_dir``, so a
    crash-recovery rebuild deserializes every compiled program instead
    of tracing — the ``serve_recovery_warm`` compile-budget row pins
    that rebuild at ZERO backend compiles.

    With ``require_warm`` (the default for a factory whose whole point
    is compile-free rebuilds), a fallback to fresh compiles raises
    instead of silently re-tracing under traffic."""
    def factory():
        from ..inference.serving import ContinuousBatchingEngine
        eng = ContinuousBatchingEngine(cfg, params, aot_dir=aot_dir,
                                       **engine_kwargs)
        if require_warm and not eng.aot_loaded:
            raise RuntimeError(
                f"warm engine factory fell back to fresh compiles: "
                f"{eng.aot_error}")
        return eng

    return factory


def load_engine_artifacts(engine, directory: str, *, registry=None):
    """Verify + deserialize the serve executables for ``engine``.

    Returns ``(decode_step, {bucket: fill}, ShapeBucketRegistry,
    sampler, spec_programs)`` — ``spec_programs`` is ``{}`` for a
    non-speculating engine, else ``{"draft": ..., "verify": ...}``;
    raises an :class:`~paddle_tpu.aot.artifact.AotError` subclass on
    version skew, geometry mismatch, corruption, or a donation-unsafe
    artifact — the engine falls back to fresh compiles.  An artifact
    directory from before the sampler (or, for a speculating engine,
    spec-program) export is a manifest mismatch (re-export), not a
    silent half-warm start."""
    from .artifact import resolve_artifact_dir
    directory = resolve_artifact_dir(directory)
    store = ArtifactStore(directory, registry=registry)
    store.check_env()
    store.check_config(engine_config(engine))
    bm = store.buckets()
    if not bm:
        raise AotManifestMismatchError(
            f"{directory}: manifest declares no serve buckets")
    breg = ShapeBucketRegistry.from_manifest(bm)
    if breg.max_batch is not None and breg.max_batch != engine.B:
        raise AotManifestMismatchError(
            f"{directory}: exported for max_batch={breg.max_batch}, "
            f"engine has {engine.B}")
    if not store.matches_signature(_DECODE, _decode_args(engine)):
        raise AotManifestMismatchError(
            f"{directory}: decode-step signature drifted from this "
            "engine's call shapes — re-export")
    if not store.matches_signature(_SAMPLER, _sampler_args(engine)):
        raise AotManifestMismatchError(
            f"{directory}: sampler signature drifted from this engine's "
            "fixed decode width — re-export")
    decode = store.get(_DECODE)
    fills = {c: store.get(_FILL.format(c=c)) for c in breg.chunk_sizes}
    sampler = store.get(_SAMPLER)
    spec = {}
    if engine.spec_config is not None:
        # the config hash already pinned the spec geometry; still match
        # the call signatures so a drifted draft-param tree fails here
        # (typed) rather than at first dispatch
        if not store.matches_signature(_DRAFT, _draft_args(engine)):
            raise AotManifestMismatchError(
                f"{directory}: draft signature drifted from this "
                "engine's spec geometry — re-export")
        if not store.matches_signature(_VERIFY, _verify_args(engine)):
            raise AotManifestMismatchError(
                f"{directory}: verify signature drifted from this "
                "engine's spec geometry — re-export")
        spec = {"draft": store.get(_DRAFT), "verify": store.get(_VERIFY)}
    return decode, fills, breg, sampler, spec
