"""AOT export/load for jitted train steps.

Two entry points, mirroring the two ways the tree builds train steps:

* :func:`export_train_step` / :func:`load_train_step` — the hapi
  ``Model`` path (``Model._build_jit_step``): forward+backward+fused
  optimizer in one donated XLA program.  The step has TWO signatures
  over its life — the first call takes per-name optimizer state and
  returns it in fused (flat-bucket) form; every later call threads the
  fused form — so the exporter serializes BOTH programs
  (``train_step_init`` / ``train_step``) and the loader dispatches per
  call on the recorded input signature, falling back to a fresh
  ``jax.jit`` (with a telemetry event) for anything else, e.g. a
  restored checkpoint with exotic slot state.

* :func:`export_jit_apply` — the raw ``Optimizer.build_jit_apply``
  fused-apply program, for callers that run their own step loop.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax

from .artifact import (ArtifactStore, _sig_matches, args_signature,
                       fresh_backend_compile)

__all__ = ["export_train_step", "load_train_step", "AotTrainStep",
           "export_jit_apply", "engine_topology_key", "export_engine_step",
           "load_engine_step", "AotEngineStep"]

_INIT = "train_step_init"
_STEADY = "train_step"


def _example_rng():
    """Same aval as ``core.rng.next_rng_key()`` (a folded typed key)
    without advancing the process generator — exporting must not shift
    the training run's RNG stream."""
    return jax.random.fold_in(jax.random.key(0), 0)


def _example_args(model, inputs, labels) -> Tuple:
    """Reconstruct ``Model.train_batch``'s exact jit-step call
    signature from one example batch (first-step form: per-name
    optimizer state)."""
    from ..hapi.model import _np
    inputs = _np(inputs)
    labels = _np(labels)
    params, buffers = model._split_state()
    trainable = {n: params[n]
                 for n, p in model.network.named_parameters()
                 if p.trainable}
    opt_state = model._optimizer.init_state(trainable)
    lr = model._optimizer.get_lr()
    scale = (model._scaler.get_loss_scaling()
             if model._scaler is not None and model._scaler.is_enable()
             else 1.0)
    return (params, buffers, opt_state, model._step_count + 1, lr,
            _example_rng(), scale, inputs, labels)


def train_config(model, args: Tuple) -> Dict[str, Any]:
    td, leaves = args_signature(args)
    return {
        "kind": "hapi_train_step",
        "network": type(model.network).__name__,
        "optimizer": type(model._optimizer).__name__,
        "loss": type(model._loss).__name__ if model._loss else None,
        "skip_nonfinite": bool(model._skip_nonfinite),
        "amp": bool(model._scaler is not None
                    and model._scaler.is_enable()),
        "args_treedef": td,
        "args_leaves": leaves,
    }


def export_train_step(model, inputs, labels, directory: str, *,
                      donate: bool = True,
                      rotate: bool = False,
                      keep_last: Optional[int] = None,
                      registry=None) -> ArtifactStore:
    """Trace, lower, compile, and serialize the prepared ``model``'s
    jitted train step for one example batch shape — both the first-step
    (per-name optimizer state) and steady-state (fused state)
    programs.  ``rotate=True`` exports into a fresh generation under a
    rotation ROOT and publishes the atomic ``latest`` pointer
    (``keep_last`` prunes old generations); ``Model.prepare(aot_dir=
    root)`` then follows the pointer."""
    if model._optimizer is None:
        raise ValueError("export_train_step needs a prepared Model "
                         "(call prepare(optimizer=..., loss=...) first)")
    donate_argnums = (0, 1, 2) if donate else ()
    jit_step = model._build_jit_step(donate=donate)
    args_init = _example_args(model, inputs, labels)
    if rotate:
        from .artifact import new_generation
        store = new_generation(directory, registry=registry)
    else:
        store = ArtifactStore(directory, registry=registry)
    store.begin(config=train_config(model, args_init))

    with fresh_backend_compile():
        compiled = jit_step.lower(*args_init).compile()
        store.put(_INIT, compiled, args_init,
                  donate_argnums=donate_argnums)

        # steady state: the fused opt-state layout is whatever the
        # first step RETURNS — take its avals abstractly and compile
        # that program
        fused_sds = jax.eval_shape(jit_step, *args_init)[2]
        args_steady = args_init[:2] + (fused_sds,) + args_init[3:]
        compiled = jit_step.lower(*args_steady).compile()
        store.put(_STEADY, compiled, args_steady,
                  donate_argnums=donate_argnums)
    if rotate:
        store.publish(keep_last=keep_last)
    return store


class AotTrainStep:
    """Drop-in for ``Model._jit_step``: dispatches each call to the
    deserialized executable whose recorded input signature matches,
    fresh-compiling (once, with a telemetry event) for anything the
    artifacts don't cover."""

    def __init__(self, model, store: ArtifactStore):
        self._model = model
        self._store = store
        self._entries = []
        for name in (_INIT, _STEADY):
            self._entries.append((store.entry(name)["in_sig"],
                                  store.get(name)))
        self._fresh = None

    def __call__(self, *args):
        for sig, fn in self._entries:
            if _sig_matches(sig, args):
                return fn(*args)
        if self._fresh is None:
            self._store._event("signature_fallback",
                               name="train_step")
            self._fresh = self._model._build_jit_step()
        return self._fresh(*args)


def load_train_step(model, directory: str, *, registry=None
                    ) -> AotTrainStep:
    """Verify + deserialize the train-step artifacts for ``model``
    (``directory`` may be a rotation root — the ``latest`` pointer is
    followed).  Raises an AotError subclass (skew/corrupt/donation-
    refused) — the Model falls back to a fresh ``jax.jit``."""
    from .artifact import resolve_artifact_dir
    store = ArtifactStore(resolve_artifact_dir(directory),
                          registry=registry)
    store.check_env()
    return AotTrainStep(model, store)


# ----------------------------------------------------------------------
# per-topology DistributedEngine steps (ISSUE 17 elastic training)
# ----------------------------------------------------------------------
_ENGINE_PREFIX = "engine_step@"


def engine_topology_key(topo) -> str:
    """Stable artifact-entry key for a mesh, e.g.
    ``pp1-dp4-sharding1-sep1-mp1@d0.1.2.3`` — one AOT store holds one
    entry per mesh the elastic trainer has ever run at, so a resume at
    ANY previously-seen mesh is a pure deserialize.  The key includes
    the device ids, not just the axis degrees: a serialized executable
    bakes in its device assignment, and a dp3 mesh over survivors
    {0,1,3} cannot serve a dp3 mesh over {0,1,2}."""
    from ..parallel.topology import AXIS_ORDER
    degrees = "-".join(f"{a}{topo.axis_size(a)}" for a in AXIS_ORDER)
    devs = ".".join(str(d.id) for d in topo.mesh.devices.flat)
    return f"{degrees}@d{devs}"


def engine_config(engine) -> Dict[str, Any]:
    """Store-level config for an engine-step artifact store.  Deliberately
    topology-free: topologies live in the per-entry names, so a reshape
    EXTENDS the store instead of invalidating it."""
    return {
        "kind": "engine_train_step",
        "network": type(engine.network).__name__,
        "optimizer": type(engine.optimizer).__name__,
        "loss": (type(engine.loss_fn).__name__
                 if engine.loss_fn is not None else None),
        "sharding_stage": engine.sharding_stage,
        "amp": engine.amp_dtype,
        "skip_nonfinite": bool(engine.skip_nonfinite),
    }


def _engine_example_args(engine, inputs, labels) -> Tuple:
    """The exact ``DistributedEngine._step_fn`` call signature for one
    example batch (state must already be sharded)."""
    params, buffers, opt_state = engine._state
    inputs_p, labels_p = engine.place_batch(inputs, labels)
    lr = engine.optimizer.get_lr()
    return (params, buffers, opt_state, engine._step_count + 1, lr,
            _example_rng(), inputs_p, labels_p)


def export_engine_step(engine, inputs, labels, directory: str, *,
                       donate: bool = True,
                       registry=None):
    """Compile + serialize ``engine``'s SPMD train step under its
    topology's entry name.  An existing store is EXTENDED (other
    topologies' entries are kept), so the elastic trainer accumulates
    one entry per mesh it reshapes through.  Returns ``(store,
    compiled)`` — the freshly compiled executable is handed back so the
    caller can install it directly and the export costs no second
    compile."""
    if engine._state is None:
        engine.shard_state()
    jitted = engine.build_train_step(donate=donate)
    args = _engine_example_args(engine, inputs, labels)
    store = ArtifactStore(directory, registry=registry)
    if store.exists():
        store.extend()
    else:
        store.begin(config=engine_config(engine))
    name = _ENGINE_PREFIX + engine_topology_key(engine.topo)
    with fresh_backend_compile():
        compiled = jitted.lower(*args).compile()
    store.put(name, compiled, args,
              donate_argnums=(0, 1, 2) if donate else ())
    return store, compiled


class AotEngineStep:
    """Drop-in for ``DistributedEngine._step_fn``: runs the deserialized
    executable while the call signature matches the recorded one,
    fresh-jitting (once, with a telemetry event) on divergence — e.g. a
    batch-shape change the artifacts don't cover."""

    def __init__(self, engine, store: ArtifactStore, sig, fn):
        self._engine = engine
        self._store = store
        self._sig = sig
        self._fn = fn
        self._fresh = None

    def __call__(self, *args):
        if self._fresh is None and _sig_matches(self._sig, args):
            return self._fn(*args)
        if self._fresh is None:
            self._store._event("signature_fallback", name="engine_step")
            # build_train_step re-points engine._step_fn at the fresh
            # jit, so later train_batch calls skip this dispatch
            self._fresh = self._engine.build_train_step()
        return self._fresh(*args)


def load_engine_step(engine, directory: str, *, registry=None
                     ) -> AotEngineStep:
    """Verify + deserialize the engine-step entry matching ``engine``'s
    CURRENT topology.  Raises an AotError subclass when the store, this
    environment, or this topology's entry is unusable — callers fall
    back to a fresh jit (one bounded compile)."""
    from .artifact import resolve_artifact_dir
    store = ArtifactStore(resolve_artifact_dir(directory),
                          registry=registry)
    store.check_env()
    store.check_config(engine_config(engine))
    name = _ENGINE_PREFIX + engine_topology_key(engine.topo)
    entry = store.entry(name)
    return AotEngineStep(engine, store, entry["in_sig"], store.get(name))


def export_jit_apply(opt, params, grads, state, directory: str, *,
                     lr=1e-3, step: int = 1,
                     donate: bool = True,
                     registry=None) -> ArtifactStore:
    """Serialize ``Optimizer.build_jit_apply``'s fused-apply program at
    the given (params, grads, state) signature — the raw-step-loop
    analog of :func:`export_train_step`."""
    fused = opt.build_jit_apply(donate=donate)
    args = (params, grads, state, lr, step)
    store = ArtifactStore(directory, registry=registry)
    td, leaves = args_signature(args)
    store.begin(config={"kind": "fused_jit_apply",
                        "optimizer": type(opt).__name__,
                        "args_treedef": td, "args_leaves": leaves})
    with fresh_backend_compile():
        compiled = fused.lower(*args).compile()
    store.put("jit_apply", compiled, args,
              donate_argnums=(0, 1, 2) if donate else ())
    return store
