"""``paddle_tpu.jit`` — traced whole-graph execution.

The reference needed two dynamic-to-static routes (SOT bytecode tracing,
jit/sot/translate.py:30, and an AST transpiler, dy2static/program_translator
.py) because its eager ops were opaque C++ calls.  Here every op is a jnp
function, so ``to_static`` is ``jax.jit`` plus Tensor boxing: inside the
trace, dispatch sees tracers and falls through to direct calls (SURVEY §3.3
collapses into one XLA program — the PirInterpreter replacement)."""

from __future__ import annotations

import functools
import threading
from typing import Any, Callable, Optional

import jax

from ..core.tensor import Tensor

__all__ = ["to_static", "jit_compile", "in_to_static_mode", "not_to_static",
           "ignore_module", "save", "load"]


class _TraceState(threading.local):
    def __init__(self):
        self.depth = 0


_trace_state = _TraceState()


def in_to_static_mode() -> bool:
    return _trace_state.depth > 0


def _unwrap(x):
    return x._value if isinstance(x, Tensor) else x


def _wrap(x):
    return Tensor(x) if isinstance(x, jax.Array) else x


class StaticFunction:
    """Callable produced by ``to_static``; holds the jitted program cache
    (the analog of the reference's per-spec Program cache,
    program_translator.py)."""

    def __init__(self, fn: Callable, input_spec=None, full_graph=True,
                 backend=None, donate_argnums=(), static_argnums=()):
        self._fn = fn
        self._input_spec = input_spec
        self._full_graph = full_graph
        self._fell_back = False
        self._hybrid = None        # lazy graph-break segmentation
        functools.update_wrapper(self, fn)
        if not full_graph:
            # try-handlers can swallow tracer errors MID-TRACE and make a
            # broken trace look successful (wrong branch, wrong result) —
            # those functions graph-break up front (jit/graph_break.py)
            from .graph_break import build_hybrid, needs_proactive_break
            if needs_proactive_break(fn):
                self._hybrid = build_hybrid(fn)
                self._fell_back = self._hybrid is not None
                if self._fell_back:
                    import warnings
                    warnings.warn(
                        f"to_static: {getattr(fn, '__qualname__', '?')} "
                        "has a try-handler broad enough to swallow tracer "
                        "errors mid-trace; running as compiled subgraphs "
                        "with the try interpreted (graph break). Narrow "
                        "the except clause or pass full_graph=True to "
                        "compile whole-graph.", stacklevel=3)

        # dy2static: rewrite tensor-dependent if/while/for into
        # lax.cond/while_loop/fori_loop via runtime-dispatched helpers
        from .dy2static import convert_control_flow
        conv_fn = convert_control_flow(fn)
        self._conv_fn = conv_fn

        def traced(*args, **kwargs):
            _trace_state.depth += 1
            try:
                targs = jax.tree.map(_wrap, args)
                tkwargs = jax.tree.map(_wrap, kwargs)
                out = conv_fn(*targs, **tkwargs)
                return jax.tree.map(_unwrap, out,
                                    is_leaf=lambda x: isinstance(x, Tensor))
            finally:
                _trace_state.depth -= 1

        self._jitted = jax.jit(traced, donate_argnums=donate_argnums,
                               static_argnums=static_argnums)

    def __call__(self, *args, **kwargs):
        if not _TO_STATIC_ENABLED:
            return self._fn(*args, **kwargs)   # eager fallback (debug)
        if self._fell_back:
            # memoized graph break: don't re-pay a failing whole-graph
            # trace every call; segments stay jitted inside the hybrid
            if self._hybrid is not None:
                return self._hybrid(*args, **kwargs)
            return self._fn(*args, **kwargs)
        vargs = jax.tree.map(_unwrap, args,
                             is_leaf=lambda x: isinstance(x, Tensor))
        vkwargs = jax.tree.map(_unwrap, kwargs,
                               is_leaf=lambda x: isinstance(x, Tensor))
        from .dy2static import ConversionFallback
        try:
            out = self._jitted(*vargs, **vkwargs)
        except (jax.errors.TracerBoolConversionError,
                jax.errors.TracerArrayConversionError,
                jax.errors.TracerIntegerConversionError,
                jax.errors.ConcretizationTypeError,
                ConversionFallback) as e:
            # SOT graph-break semantics (reference jit/sot/translate.py:30):
            # a construct the AST pass left unconverted concretized a
            # tracer.  With full_graph=True that's an error; otherwise
            # split the function at the break and keep the compilable
            # segments jitted (jit/graph_break.py); whole-call eager only
            # when the function cannot be segmented at all.
            if self._full_graph:
                raise
            if self._hybrid is None and not self._fell_back:
                from .graph_break import build_hybrid
                self._hybrid = build_hybrid(self._fn)
            if not self._fell_back:
                self._fell_back = True
                import warnings
                mode = ("subgraph (graph break: compilable segments stay "
                        "jitted)") if self._hybrid is not None else \
                    "whole-call eager (graph break)"
                warnings.warn(
                    f"to_static: {getattr(self._fn, '__qualname__', '?')} "
                    f"uses untraceable control flow ({type(e).__name__}); "
                    f"falling back to {mode} execution. Pass "
                    "full_graph=True to make this an error.",
                    stacklevel=2)
            if self._hybrid is not None:
                return self._hybrid(*args, **kwargs)
            return self._fn(*args, **kwargs)
        return jax.tree.map(_wrap, out)

    def __get__(self, instance, owner):
        if instance is None:
            return self
        return functools.partial(self.__call__, instance)

    @property
    def code(self) -> str:
        import inspect
        return inspect.getsource(self._fn)

    def concrete_program(self, *args, **kwargs):
        vargs = jax.tree.map(_unwrap, args,
                             is_leaf=lambda x: isinstance(x, Tensor))
        return self._jitted.lower(*vargs, **kwargs)


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=False, **kwargs):
    """``@paddle.jit.to_static`` parity (reference: jit/api.py:195).

    Tensor-dependent ``if``/``while``/``for`` are AST-converted to
    ``lax.cond``/``while_loop``/``fori_loop`` (jit/dy2static.py); anything
    unconvertible triggers a graph-break eager fallback unless
    ``full_graph=True`` (reference SOT vs AST route split)."""

    def deco(fn):
        if isinstance(fn, StaticFunction):
            return fn
        # Layers: wrap forward
        from ..nn.layer.layers import Layer
        if isinstance(fn, Layer):
            layer = fn
            orig_forward = layer.forward
            layer.forward = StaticFunction(
                lambda *a, **k: orig_forward(*a, **k), input_spec, full_graph)
            return layer
        return StaticFunction(fn, input_spec, full_graph)

    if function is not None:
        return deco(function)
    return deco


def jit_compile(fn: Callable, donate_argnums=(), static_argnums=()):
    """Lower-level helper: jit a Tensor-level function."""
    return StaticFunction(fn, donate_argnums=donate_argnums,
                          static_argnums=static_argnums)


def not_to_static(fn=None):
    return fn


def ignore_module(modules):
    return None


# ONE InputSpec across jit and static (the reference exposes a single
# paddle.static.InputSpec) — duplicated classes broke isinstance checks
# when users imported the "other" one
from ..static import InputSpec  # noqa: E402,F401


class TranslatedLayer:
    """Callable returned by :func:`load` — the analog of the reference's
    ``TranslatedLayer`` (jit/translated_layer.py): a deserialized program
    plus its parameters, executable without the original Python class.

    ``aot_call`` (when the archive embeds a compile artifact and it
    passed the environment/CRC gates) is the READY XLA executable —
    calls run with zero trace/lower/backend-compile work."""

    def __init__(self, exported, params, aot_call=None):
        self._exported = exported
        self._params = params
        self._aot_call = aot_call

    @property
    def aot_loaded(self) -> bool:
        return self._aot_call is not None

    def __call__(self, *args):
        vals = [a._value if isinstance(a, Tensor) else jax.numpy.asarray(a)
                for a in args]
        if self._aot_call is not None:
            out = self._aot_call(self._params, *vals)
        else:
            out = self._exported.call(self._params, *vals)
        return jax.tree.map(_wrap, out)

    def state_dict(self):
        return dict(self._params)

    eval = train = lambda self: self


def save(layer, path, input_spec=None, aot=False, **config):
    """``paddle.jit.save`` analog (reference jit/api.py).

    TPU-native format: instead of the reference's Program protobuf +
    TranslatedLayer, the traced computation is serialized as STABLEHLO via
    ``jax.export`` (path.pdmodel) next to the parameters (path.pdparams) —
    loadable by :func:`load` in a fresh process with no access to the
    original Python class.

    ``aot=True`` additionally embeds the fully COMPILED executable
    (serialized via ``paddle_tpu.aot``, CRC'd, with an environment
    fingerprint): :func:`load` on a matching jax/jaxlib/platform runs it
    with zero compile work, and transparently falls back to the portable
    STABLEHLO program anywhere else.  Requires a fully static
    ``input_spec`` (an XLA executable is shape-specialized; use the
    plain STABLEHLO path for dynamic batch dims).  This is the
    deployment-export story — the reference's onnx/inference-model path
    is out of scope on the TPU build (see NOTIMPL.md)."""
    import pickle
    import zlib

    import numpy as np

    from ..framework.io import save as _save
    from ..nn.layer.layers import functional_call, state_arrays

    if input_spec is None:
        raise ValueError("jit.save needs input_spec (list of InputSpec or "
                         "example Tensors) to trace the layer")
    params = state_arrays(layer)   # params + buffers, the traced pytree
    _save({k: np.asarray(v) for k, v in params.items()}, path + ".pdparams")

    scope = jax.export.SymbolicScope()
    counter = [0]

    def spec_to_sds(s):
        if isinstance(s, InputSpec):
            from ..core.dtypes import canonical_dtype
            if any(d is None for d in s.shape):
                # None dims (paddle's dynamic-batch idiom) become jax.export
                # symbolic dimensions — the exported program accepts any
                # concrete size at call time
                parts = []
                for d in s.shape:
                    if d is None:
                        parts.append(f"_dyn{counter[0]}")
                        counter[0] += 1
                    else:
                        parts.append(str(d))
                shape = jax.export.symbolic_shape(",".join(parts),
                                                  scope=scope)
                return jax.ShapeDtypeStruct(shape, canonical_dtype(s.dtype))
            return jax.ShapeDtypeStruct(s.shape, canonical_dtype(s.dtype))
        v = s._value if isinstance(s, Tensor) else jax.numpy.asarray(s)
        return jax.ShapeDtypeStruct(v.shape, v.dtype)

    def pure(params, *xs):
        out = functional_call(layer, params, *[Tensor(x) for x in xs])
        return jax.tree.map(_unwrap, out,
                            is_leaf=lambda x: isinstance(x, Tensor))

    sds = [spec_to_sds(s) for s in input_spec]
    params_sds = jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), params)
    exported = jax.export.export(jax.jit(pure))(params_sds, *sds)
    blob = {"stablehlo": exported.serialize(),
            "param_keys": sorted(params.keys())}
    if aot:
        if counter[0]:
            raise ValueError(
                "jit.save(aot=True): input_spec has dynamic (None) dims; "
                "an XLA executable is shape-specialized — pass concrete "
                "shapes, or drop aot=True for the symbolic-shape "
                "STABLEHLO export")
        from jax.experimental import serialize_executable as se
        from ..aot.artifact import (environment_fingerprint,
                                    executable_device_ids,
                                    fresh_backend_compile)
        with fresh_backend_compile():
            compiled = jax.jit(pure).lower(params_sds, *sds).compile()
        payload = pickle.dumps(se.serialize(compiled))
        blob["aot"] = {"env": environment_fingerprint(),
                       "device_ids": executable_device_ids(compiled),
                       "crc32": zlib.crc32(payload),
                       "payload": payload}
    with open(path + ".pdmodel", "wb") as f:
        pickle.dump(blob, f)


def load(path, **config):
    """``paddle.jit.load`` analog: deserialize the STABLEHLO program +
    params saved by :func:`save`; returns a :class:`TranslatedLayer`.
    An embedded ``aot=True`` executable is used when its environment
    fingerprint matches, its devices exist here and its CRC verifies —
    otherwise the portable STABLEHLO program is used (version skew is a
    fallback, corruption of the aot payload raises)."""
    import pickle
    import zlib

    from ..framework.io import load as _load

    with open(path + ".pdmodel", "rb") as f:
        blob = pickle.load(f)
    exported = jax.export.deserialize(blob["stablehlo"])
    state = _load(path + ".pdparams")
    params = {k: jax.numpy.asarray(v) for k, v in state.items()}
    expected = blob.get("param_keys")
    if expected is not None and sorted(params.keys()) != expected:
        missing = set(expected) - set(params)
        extra = set(params) - set(expected)
        raise ValueError(
            f"jit.load: {path}.pdparams does not match the exported "
            f"program (missing={sorted(missing)}, extra={sorted(extra)})")
    aot_call = None
    aot_blob = blob.get("aot")
    if aot_blob is not None:
        from ..aot.artifact import (AotArtifactCorruptError,
                                    devices_for_ids,
                                    environment_fingerprint)
        if zlib.crc32(aot_blob["payload"]) != aot_blob["crc32"]:
            raise AotArtifactCorruptError(
                f"{path}.pdmodel: embedded AOT executable fails its CRC "
                "— archive is corrupt (the STABLEHLO program shares the "
                "same file; re-export)")
        devices = devices_for_ids(aot_blob.get("device_ids"))
        if aot_blob.get("env") == environment_fingerprint() \
                and devices is not None:
            from jax.experimental import serialize_executable as se
            aot_call = se.deserialize_and_load(
                *pickle.loads(aot_blob["payload"]),
                execution_devices=devices)
    return TranslatedLayer(exported, params, aot_call=aot_call)


_TO_STATIC_ENABLED = True


def enable_to_static(enable: bool = True):
    """Globally toggle to_static conversion (reference jit/api.py
    enable_to_static): when off, StaticFunction calls run the original
    eager function (no tracing) for debugging."""
    global _TO_STATIC_ENABLED
    _TO_STATIC_ENABLED = bool(enable)


def set_code_level(level: int = 100, also_to_stdout: bool = False):
    """Reference sot/dy2static logging knob; our single-route to_static
    has no transformed-code dump, so this only records the level."""
    global _CODE_LEVEL
    _CODE_LEVEL = level


def set_verbosity(level: int = 0, also_to_stdout: bool = False):
    global _VERBOSITY
    _VERBOSITY = level


_CODE_LEVEL = 0
_VERBOSITY = 0
