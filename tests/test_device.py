"""core/device.py: the ONE platform check, places that never hand back
another platform's device, and the compile cache placed from outside."""

import os

import jax
import pytest

from paddle_tpu.core import device as dev
from paddle_tpu.core.device import Place, enable_compile_cache, on_tpu


def test_on_tpu_reads_the_backend():
    assert on_tpu() is False            # conftest pins the CPU
    assert dev.is_compiled_with_tpu() is False


def test_a_failed_backend_is_not_a_quiet_answer(monkeypatch):
    """A backend that cannot initialise raises through every kernel
    dispatch — it never silently means 'interpret mode' or 'no TPU'."""
    from paddle_tpu.ops.fused_cross_entropy import _pallas_auto
    from paddle_tpu.ops.pallas.common import use_interpret

    def dead_backend(*a, **k):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", dead_backend)
    for ask in (on_tpu, use_interpret, _pallas_auto):
        with pytest.raises(RuntimeError, match="initialize backend"):
            ask()


def test_place_never_hands_back_another_platform():
    assert Place("cpu", 1).jax_device() == jax.devices("cpu")[1]
    with pytest.raises(RuntimeError):
        Place("tpu").jax_device()        # no TPU here: raise, no CPU
    with pytest.raises(RuntimeError, match="device"):
        Place("cpu", 99).jax_device()
    assert dev.device_count("tpu") == 0


def test_compile_cache_is_placed_from_outside(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == prev   # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert enable_compile_cache() == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir \
            == os.path.join(repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
