"""The Tensor façade over ``jax.Array``.

Analog of the reference's ``phi::DenseTensor`` + Python ``Tensor``
(/root/reference/paddle/phi/core/dense_tensor.h:37 and the eager tensor
patched methods, python/paddle/base/dygraph/tensor_patch_methods.py).
Storage, layout, strides and allocators collapse into ``jax.Array``; what
remains is the imperative-API state the reference keeps on the C++ side:
``stop_gradient``, ``.grad``, hooks, name, and the autograd linkage.

Tensor is registered as a jax pytree node, so Tensors pass transparently
through ``jax.jit`` / ``jax.grad`` / shard_map — the bridge between the
Paddle-style imperative shell and functional JAX.
"""

from __future__ import annotations

import itertools
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import dtypes as _dt
from .autograd import backward as _backward

__all__ = ["Tensor", "to_tensor", "Parameter"]

_name_counter = itertools.count()


class Tensor:
    __slots__ = ("_value", "stop_gradient", "grad", "name", "persistable",
                 "_node", "_out_index", "_retain_grads", "_grad_hooks",
                 "trainable", "process_mesh", "placements", "param_spec",
                 "optimize_attr", "__weakref__")

    def __init__(self, value, stop_gradient: bool = True,
                 name: Optional[str] = None, persistable: bool = False):
        if isinstance(value, Tensor):
            value = value._value
        elif not isinstance(value, (jax.Array, np.ndarray)) or isinstance(
                value, np.generic):
            value = jnp.asarray(value)
        self._value = value
        self.stop_gradient = stop_gradient
        self.grad: Optional["Tensor"] = None
        self.name = name or f"tensor_{next(_name_counter)}"
        self.persistable = persistable
        self.trainable = True
        self._node = None
        self._out_index = 0
        self._retain_grads = False
        self._grad_hooks: List[Callable] = []

    # ------------------------------------------------------------------
    # properties
    # ------------------------------------------------------------------
    @property
    def value(self):
        return self._value

    @property
    def shape(self):
        return list(jnp.shape(self._value))

    @property
    def ndim(self) -> int:
        return jnp.ndim(self._value)

    @property
    def dtype(self):
        return jnp.asarray(self._value).dtype

    @property
    def size(self) -> int:
        return int(np.prod(jnp.shape(self._value), dtype=np.int64))

    @property
    def place(self):
        from .device import Place
        v = self._value
        if isinstance(v, jax.Array) and not isinstance(v, jax.core.Tracer):
            try:
                d = list(v.devices())[0]
                return Place(d.platform, d.id)
            except (IndexError, RuntimeError):
                pass    # deleted/donated array: fall to default_place
        from .device import default_place
        return default_place()

    @property
    def is_leaf(self) -> bool:
        return self._node is None

    # ------------------------------------------------------------------
    # conversion
    # ------------------------------------------------------------------
    def numpy(self) -> np.ndarray:
        return np.asarray(self._value)

    def __array__(self, dtype=None):
        a = np.asarray(self._value)
        return a.astype(dtype) if dtype is not None else a

    def item(self, *args):
        return np.asarray(self._value).item(*args)

    def tolist(self):
        return np.asarray(self._value).tolist()

    def __float__(self):
        return float(np.asarray(self._value))

    def __int__(self):
        return int(np.asarray(self._value))

    def __index__(self):
        # lets range(t)/list[t] work eagerly; under trace the jax tracer
        # raises TracerIntegerConversionError (dy2static fallback catches)
        if isinstance(self._value, jax.core.Tracer):
            return self._value.__index__()
        v = np.asarray(self._value)
        if not (np.issubdtype(v.dtype, np.integer)
                or v.dtype == np.bool_):
            raise TypeError(
                f"'{v.dtype}' tensor cannot be interpreted as an integer")
        return int(v)

    def __bool__(self):
        return bool(np.asarray(self._value))

    def __len__(self):
        s = jnp.shape(self._value)
        if not s:
            raise TypeError("len() of a 0-d tensor")
        return s[0]

    def __repr__(self):
        v = self._value
        if isinstance(v, jax.core.Tracer):
            body = repr(v)
        else:
            body = np.array2string(np.asarray(v), precision=6, threshold=64)
        return (f"Tensor(shape={self.shape}, dtype={self.dtype}, "
                f"stop_gradient={self.stop_gradient},\n       {body})")

    # ------------------------------------------------------------------
    # autograd
    # ------------------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph: bool = False) -> None:
        _backward(self, grad_tensor, retain_graph=retain_graph)

    def retain_grads(self) -> None:
        self._retain_grads = True

    def register_hook(self, hook: Callable) -> Callable:
        """Hook ``hook(grad) -> grad|None`` applied when this tensor's grad is
        accumulated (reference: eager/hooks.h; used by DP reducers)."""
        self._grad_hooks.append(hook)

        def remove():
            self._grad_hooks.remove(hook)

        remove.remove = remove
        return remove

    def _accumulate_grad(self, g) -> None:
        if isinstance(g, Tensor) and g._node is not None:
            # create_graph path: keep the graph-linked grad Tensor so the
            # grad itself stays differentiable (double grad)
            for hook in self._grad_hooks:
                out = hook(g)
                if out is not None:
                    g = out
            self.grad = g if self.grad is None else self.grad + g
            return
        if isinstance(g, Tensor):
            g = g._value
        for hook in self._grad_hooks:
            out = hook(Tensor(g))
            if out is not None:
                g = out._value if isinstance(out, Tensor) else out
        if self.grad is None:
            self.grad = Tensor(g)
        else:
            self.grad = Tensor(self.grad._value + g)

    def clear_grad(self) -> None:
        self.grad = None

    clear_gradient = clear_grad

    def detach(self) -> "Tensor":
        t = Tensor(self._value, stop_gradient=True, name=self.name + ".detach")
        return t

    def detach_(self) -> "Tensor":
        self._node = None
        self.stop_gradient = True
        return self

    def clone(self) -> "Tensor":
        from ..ops import api as _api
        return _api.assign(self)

    # ------------------------------------------------------------------
    # mutation (functional under the hood; jax arrays are immutable)
    # ------------------------------------------------------------------
    def copy_(self, other) -> "Tensor":
        self._value = jnp.asarray(other._value if isinstance(other, Tensor)
                                  else other, self.dtype)
        return self

    def set_value(self, value) -> "Tensor":
        return self.copy_(value)

    def _replace_(self, value) -> "Tensor":
        """In-place value swap used by optimizers/in-place ops."""
        self._value = value if not isinstance(value, Tensor) else value._value
        return self

    def __setitem__(self, idx, value) -> None:
        idx = _unwrap_index(idx)
        if isinstance(value, Tensor):
            value = value._value
        self._value = jnp.asarray(self._value).at[idx].set(value)

    def __getitem__(self, idx):
        from ..ops import api as _api
        return _api._getitem(self, _unwrap_index(idx))

    # ------------------------------------------------------------------
    # dtype / device movement
    # ------------------------------------------------------------------
    def astype(self, dtype) -> "Tensor":
        from ..ops import api as _api
        return _api.cast(self, dtype)

    cast = astype

    def to(self, *args, **kwargs) -> "Tensor":
        dtype = kwargs.get("dtype")
        device = kwargs.get("device")
        for a in args:
            if isinstance(a, str) and a.startswith(("cpu", "tpu", "gpu")):
                device = a
            else:
                dtype = a
        t = self
        if dtype is not None:
            t = t.astype(dtype)
        if device is not None:
            from .device import Place
            if isinstance(device, str):
                ty, _, idx = device.partition(":")
                device = Place(ty, int(idx or 0))
            v = jax.device_put(t._value, device.jax_device())
            t = Tensor(v, stop_gradient=t.stop_gradient, name=t.name)
        return t

    def cpu(self) -> "Tensor":
        return self.to("cpu")

    def pin_memory(self) -> "Tensor":
        return self

    def contiguous(self) -> "Tensor":
        return self

    def is_contiguous(self) -> bool:
        return True

    # ------------------------------------------------------------------
    # misc paddle-compat
    # ------------------------------------------------------------------
    def numel(self) -> int:
        return self.size

    def dim(self) -> int:
        return self.ndim

    def element_size(self) -> int:
        return jnp.asarray(self._value).dtype.itemsize

    def block_until_ready(self) -> "Tensor":
        if isinstance(self._value, jax.Array):
            self._value.block_until_ready()
        return self


def _unwrap_index(idx):
    if isinstance(idx, Tensor):
        return idx._value
    if isinstance(idx, tuple):
        return tuple(_unwrap_index(i) for i in idx)
    if isinstance(idx, list):
        return [_unwrap_index(i) for i in idx]
    return idx


# ---------------------------------------------------------------------------
# pytree registration: Tensors flow through jax transforms
# ---------------------------------------------------------------------------
def _tensor_flatten(t: Tensor):
    # aux must NOT carry identity data (e.g. the auto name): treedef
    # equality gates lax.cond/while_loop branch matching, and two Tensors
    # computed on different branches must flatten identically
    return (t._value,), (t.stop_gradient,)


def _tensor_unflatten(aux, children):
    # well-behaved pytree: jax unflattens with sentinel/placeholder
    # children (error rendering, transposes) — no asarray validation here
    t = object.__new__(Tensor)
    t._value = children[0]
    t.stop_gradient = aux[0]
    t.grad = None
    t.name = f"tensor_{next(_name_counter)}"
    t.persistable = False
    t.trainable = True
    t._node = None
    t._out_index = 0
    t._retain_grads = False
    t._grad_hooks = []
    return t


jax.tree_util.register_pytree_node(Tensor, _tensor_flatten, _tensor_unflatten)


class Parameter(Tensor):
    """Trainable tensor (``stop_gradient=False``, ``persistable=True``).
    Analog of paddle's EagerParamBase."""

    def __init__(self, value, name: Optional[str] = None, trainable: bool = True):
        super().__init__(value, stop_gradient=not trainable, name=name,
                         persistable=True)
        self.trainable = trainable


def to_tensor(data, dtype=None, place=None, stop_gradient: bool = True) -> Tensor:
    """Paddle-compatible ``paddle.to_tensor``."""
    if isinstance(data, Tensor):
        v = data._value
    else:
        v = data
    if dtype is not None:
        v = jnp.asarray(v, _dt.canonical_dtype(dtype))
    else:
        v = jnp.asarray(v)
        if v.dtype == jnp.float64 and _dt.default_float_dtype() == jnp.float32:
            v = v.astype(jnp.float32)
    if place is not None:
        from .device import Place
        if isinstance(place, str):
            ty, _, idx = place.partition(":")
            place = Place(ty, int(idx or 0))
        v = jax.device_put(v, place.jax_device())
    return Tensor(v, stop_gradient=stop_gradient)


def inplace_rebind(x: "Tensor", out: "Tensor") -> "Tensor":
    """Make ``x`` observe in-place op result ``out`` (reference: inplace ops
    + eager/tensor_wrapper.h inplace-version semantics).

    The autograd node of ``out`` recorded ``x`` as an input box; rebinding
    ``x`` to ``out`` would alias that input to the node's own output and
    create a self-cycle in backward.  Snapshot the producer link into a
    fresh box first, then rebind."""
    node = getattr(out, "_node", None)
    if node is not None and x._node is None and not x.stop_gradient:
        # reference parity: in-place on a grad-requiring leaf is an error
        # (the leaf's gradient would silently accumulate into the hidden
        # pre-inplace snapshot and be dropped)
        raise RuntimeError(
            "a leaf Tensor with stop_gradient=False cannot be used in an "
            "in-place operation; detach() it or wrap in no_grad()")
    if node is not None and node.in_tensors is not None:
        for i, t in enumerate(node.in_tensors):
            if t is x:
                snap = Tensor(x._value, stop_gradient=x.stop_gradient,
                              name=x.name + ".preinplace")
                snap._node = x._node
                snap._out_index = x._out_index
                snap._retain_grads = False
                node.in_tensors[i] = snap
    x._value = out._value
    x._node = out._node
    x._out_index = out._out_index
    if not out.stop_gradient:
        x.stop_gradient = False
    return x
