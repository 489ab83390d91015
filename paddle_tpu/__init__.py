"""paddle_tpu — a TPU-native deep-learning framework.

Brand-new JAX/XLA/Pallas implementation with the capability surface of the
reference framework (PaddlePaddle, /root/reference — see SURVEY.md): an
imperative ``Tensor`` / ``nn.Layer`` / ``Optimizer`` / ``loss.backward()``
API with eager + traced dual execution, a single-source YAML op registry,
AMP, data loading, sharded checkpointing, and Fleet-style hybrid parallelism
(dp / tp / pp / sharding / sp / cp / ep) over ``jax.sharding`` meshes with
XLA collectives on ICI/DCN, plus Pallas fused kernels.
"""

from __future__ import annotations

__version__ = "0.1.0"

from .core import dtypes as _dtypes_mod
from .core.dtypes import (  # noqa: F401
    bfloat16, bool_, complex128, complex64, float16, float32, float64,
    float8_e4m3fn, float8_e5m2, int16, int32, int64, int8, uint8,
    finfo, iinfo, promote_types,
)
from .core.dtypes import bool_ as bool  # noqa: F401
from .core.device import (  # noqa: F401
    CPUPlace, Place, TPUPlace, device_count, get_device, is_compiled_with_tpu,
    max_memory_allocated, memory_allocated, memory_stats, set_device,
)
from .core.flags import FLAGS, get_flags, set_flags  # noqa: F401
from .core.rng import get_rng_state, seed, set_rng_state  # noqa: F401
from .core.tensor import Parameter, Tensor, to_tensor  # noqa: F401
from .core.autograd import grad, no_grad, enable_grad, set_grad_enabled, is_grad_enabled  # noqa: F401
from .core.dtypes import get_default_dtype, set_default_dtype  # noqa: F401

# functional op namespace (generated from ops.yaml) — both
# `paddle_tpu.add(x, y)` and `paddle_tpu.tensor.add(x, y)` work.
from .ops import api as tensor  # noqa: F401
from .ops.api import *  # noqa: F401,F403

from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import amp  # noqa: F401
from . import checkpoint  # noqa: F401
from . import observability  # noqa: F401
from . import io  # noqa: F401
from . import jit  # noqa: F401
from . import metric  # noqa: F401
from . import vision  # noqa: F401
from .framework import io as _framework_io
from .framework.io import CheckpointCorruptError, load, save  # noqa: F401
from .hapi.model import Model  # noqa: F401
from .core.autograd import backward as _backward  # noqa: F401

from . import autograd  # noqa: F401


def is_grad_enabled_():  # pragma: no cover - paddle compat shim
    return is_grad_enabled()


def ones_like(x, dtype=None):
    return tensor.ones_like(x, dtype)


def rank(x):
    return to_tensor(len(x.shape))


def numel(x):
    return to_tensor(x.size)


def shape(x):
    return to_tensor(x.shape)


def in_dynamic_mode() -> bool:
    from .jit.api import in_to_static_mode
    return not in_to_static_mode() and not _static_mode


def disable_static(place=None):
    global _static_mode
    if _static_mode:
        from . import static as _st
        _st._bind_recording(False)
    _static_mode = False
    return None


_static_mode = False


def enable_static():
    """Switch to static-graph building (reference paddle.enable_static).
    Ops touching ``static.data`` Variables record into the active Program;
    ``static.Executor.run`` jits the recording (see paddle_tpu/static)."""
    global _static_mode
    from . import static as _st
    _st._bind_recording(True)
    _static_mode = True


def in_static_mode():
    return _static_mode

from . import models  # noqa: F401
from . import inference  # noqa: F401
from . import static  # noqa: F401
from . import device  # noqa: F401
from . import regularizer  # noqa: F401
from . import hub  # noqa: F401
from . import sysconfig  # noqa: F401
from . import onnx  # noqa: F401
from . import version  # noqa: F401
from . import callbacks  # noqa: F401
from .core.string_tensor import StringTensor, to_string_tensor  # noqa: F401
import jax.numpy as _jnp
dtype = _jnp.dtype    # paddle.dtype: the dtype constructor/type alias
del _jnp
from .framework_misc import (  # noqa: F401
    ParamAttr, CUDAPlace, CUDAPinnedPlace, LazyGuard, DataParallel,
    is_tensor, is_complex, is_integer, is_floating_point, clone, tolist,
    floor_mod, set_printoptions, check_shape, disable_signal_handler,
    get_cuda_rng_state, set_cuda_rng_state, create_parameter, summary,
    flops, batch)
from . import framework_misc as _fm
import sys as _sys
_fm.install_inplace_api(_sys.modules[__name__])
del _fm, _sys
from .tensor_array import (  # noqa: F401
    TensorArray, create_array, array_write, array_read, array_length)
from . import utils  # noqa: F401
from . import parallel  # noqa: F401
from . import distributed  # noqa: F401
import importlib as _importlib

# ops.api star-import may have bound same-named functions (e.g. `fft`) on the
# package; import_module + explicit rebind makes the namespace modules win,
# matching the reference where paddle.fft / paddle.signal are modules.
linalg = _importlib.import_module(".linalg", __name__)
fft = _importlib.import_module(".fft", __name__)
signal = _importlib.import_module(".signal", __name__)
from . import distribution  # noqa: F401,E402
from . import profiler  # noqa: F401,E402
from . import sparse  # noqa: F401,E402
from . import geometric  # noqa: F401,E402
from . import quantization  # noqa: F401,E402
from . import text  # noqa: F401,E402
from . import audio  # noqa: F401,E402
from . import native  # noqa: F401,E402
