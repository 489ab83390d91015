"""Device ("place") management.

Analog of the reference's Place/Backend identity layer
(/root/reference/paddle/phi/common/place.h:58, backend.h:40) and the
DeviceContext pool (phi/core/device_context.h:37).  On TPU, streams/contexts
dissolve into XLA; a Place here is a thin wrapper over a ``jax.Device``.
"""

from __future__ import annotations

import os
from typing import Optional, Union

import jax

__all__ = [
    "Place", "CPUPlace", "TPUPlace", "set_device", "get_device",
    "device_count", "is_compiled_with_tpu", "memory_stats",
    "memory_allocated", "max_memory_allocated", "on_tpu",
    "enable_compile_cache",
]


def on_tpu() -> bool:
    """Is the default backend a TPU?  The ONE platform check every
    kernel dispatch reads.  A backend that fails to initialise raises
    here — it never quietly means "not a TPU"."""
    return jax.devices()[0].platform == "tpu"


def enable_compile_cache() -> str:
    """Place jax's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set (jax reads it itself,
    nothing is set in code).  Otherwise the cache lives at the fixed
    path ``<checkout>/.jax_cache`` — the path is part of the cache key,
    so it is never a temp name, pid or time.  Called at the top of the
    entry points (``chip_smoke.py``, ``serving/http.py:main``), never
    at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class Place:
    """Device identity: ``Place('tpu', 0)`` / ``Place('cpu')``."""

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def jax_device(self) -> jax.Device:
        # jax.devices(platform) raises when that backend does not exist
        # here: a Place never hands back a device of another platform
        devs = jax.devices(self.device_type.lower())
        if self.device_id >= len(devs):
            raise RuntimeError(f"{self!r}: this process has {len(devs)} "
                               f"{self.device_type!r} device(s)")
        return devs[self.device_id]

    def __repr__(self) -> str:
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other) -> bool:
        return (isinstance(other, Place) and other.device_type == self.device_type
                and other.device_id == self.device_id)

    def __hash__(self) -> int:
        return hash((self.device_type, self.device_id))


def CPUPlace() -> Place:
    return Place("cpu")


def TPUPlace(device_id: int = 0) -> Place:
    return Place("tpu", device_id)


_current_place: Optional[Place] = None


def set_device(device: Union[str, Place]) -> Place:
    """``set_device('tpu:0')`` — sets the default placement for new tensors."""
    global _current_place
    if isinstance(device, str):
        if ":" in device:
            ty, idx = device.split(":", 1)
            device = Place(ty, int(idx))
        else:
            device = Place(device)
    _current_place = device
    jax.config.update("jax_default_device", device.jax_device())
    return device


def get_device() -> str:
    if _current_place is not None:
        return f"{_current_place.device_type}:{_current_place.device_id}"
    d = jax.devices()[0]
    return f"{d.platform}:{d.id}"


def default_place() -> Place:
    if _current_place is not None:
        return _current_place
    d = jax.devices()[0]
    return Place(d.platform, d.id)


def device_count(device_type: Optional[str] = None) -> int:
    if device_type is None:
        return jax.device_count()
    return len([d for d in jax.devices()
                if d.platform == device_type.lower()])


def is_compiled_with_tpu() -> bool:
    return on_tpu()


def memory_stats(device: Optional[Union[str, "Place"]] = None) -> dict:
    """Device memory statistics (reference: phi/core/memory/stats.cc
    DEVICE_MEMORY_STAT / paddle.device.cuda.memory_* APIs).

    TPU-native: surfaces the PJRT allocator's live counters
    (``jax.Device.memory_stats()``) under the reference's key names.
    ``device`` accepts a Place or a 'tpu:1'-style string; default is the
    current ``set_device`` place."""
    if isinstance(device, str):
        if ":" in device:
            ty, idx = device.split(":", 1)
            device = Place(ty, int(idx))
        else:
            device = Place(device)
    elif device is None:
        device = default_place()
    dev = device.jax_device()
    raw = dev.memory_stats() or {}
    return {
        "memory.allocated.current": raw.get("bytes_in_use", 0),
        "memory.allocated.peak": raw.get("peak_bytes_in_use", 0),
        "memory.reserved.current": raw.get("bytes_reserved",
                                           raw.get("bytes_in_use", 0)),
        "memory.limit": raw.get("bytes_limit", 0),
        "raw": dict(raw),
    }


def max_memory_allocated(device=None) -> int:
    """Peak bytes allocated (reference paddle.device.cuda
    .max_memory_allocated)."""
    return int(memory_stats(device)["memory.allocated.peak"])


def memory_allocated(device=None) -> int:
    """Current bytes allocated (reference memory_allocated)."""
    return int(memory_stats(device)["memory.allocated.current"])
