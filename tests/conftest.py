"""Test config: force a virtual 8-device CPU mesh so multi-chip sharding
paths run without TPU hardware (SURVEY §4 'multi-node without a cluster' —
the reference simulates multi-node as multi-process on one host; we simulate
multi-chip as multi-device on one process)."""

import os

# Sequential thunk order: XLA:CPU's concurrency-optimized scheduler can run
# independent collectives in different orders on different virtual devices
# and deadlock the in-process rendezvous (see __graft_entry__.py).
# The collective stuck/terminate watchdogs widen the rendezvous fuse for
# hosts where the virtual devices' threads progress unevenly.
_FLAGS = ("--xla_force_host_platform_device_count=8 "
          "--xla_cpu_enable_concurrency_optimized_scheduler=false "
          "--xla_cpu_collective_call_warn_stuck_timeout_seconds=120 "
          "--xla_cpu_collective_call_terminate_timeout_seconds=480")

if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               + _FLAGS).strip()

import jax

# the hardware lane (tests/test_pallas_hw.py) names its platform through
# JAX_PLATFORMS; everything else runs on the CPU
jax.config.update("jax_platforms", os.environ.get("JAX_PLATFORMS") or "cpu")

# Persistent XLA compilation cache: the slow lane is dominated by
# whole-model compiles on one CPU core; caching executables across test
# processes/runs makes warm reruns minutes instead of ~an hour.  Keyed by
# computation fingerprint, so code changes invalidate naturally — but
# the fingerprint does NOT cover the HOST CPU: XLA:CPU AOT executables
# compiled on a different machine load with missing ISA features and
# SIGSEGV/SIGILL at run time (observed: resnet conv compile crashed the
# slow lane after the round migrated hosts).  Namespace the cache by a
# machine fingerprint so each host keeps its own executables.
# JAX_COMPILATION_CACHE_DIR, when set, places the cache instead and no
# directory is set here.
import hashlib as _hashlib
import platform as _platform


def _machine_tag() -> str:
    try:
        with open("/proc/cpuinfo") as f:
            flags = next((ln for ln in f
                          if ln.startswith(("flags", "Features"))),
                         "")
    except OSError:
        flags = ""
    raw = _platform.machine() + _platform.processor() + flags
    return _hashlib.sha1(raw.encode()).hexdigest()[:12]


_cache_base = os.environ.get("PT_TEST_COMPILE_CACHE",
                             "/tmp/paddle_tpu_xla_cache")
# the machine tag applies to overrides too — a shared persistent path
# would otherwise reintroduce the cross-host crash
# "v2": entries written before LRU sizing lack the -atime companions
# the eviction scan needs — a stale dir breaks every new cache write
_cache_dir = f"{_cache_base}_{_machine_tag()}_v2"
try:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(_cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _cache_dir)
    # 0.0: with per-module clear_caches() below, sub-second jits must
    # persist too or every module pays their recompiles from scratch
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # LRU-bound the directory: with the 0.0 threshold every tiny jit
    # persists, and nothing else ever prunes /tmp caches
    jax.config.update("jax_compilation_cache_max_size",
                      8 * 1024 ** 3)
except Exception:
    pass


import pytest


def disable_persistent_compile_cache():
    """Opt the calling module out of the persistent XLA compilation
    cache; returns a restore callable.

    Modules whose tests pin bit-for-bit results of donating programs
    use a module-scoped autouse fixture built on this helper, so every
    compile is fresh: XLA:CPU programs with donated buffers that were
    DESERIALIZED from the persistent cache drifted nondeterministically
    on warm reruns (ISSUE 2, the PR 8 test_parallel.py deflake — seen
    under jax 0.4.37 and not re-proven safe since, so the opt-out
    stays).

    The switch is ``aot.artifact.fresh_backend_compile`` (the cache's
    enable flag plus the is-cache-used memo reset, so a directory that
    ``JAX_COMPILATION_CACHE_DIR`` set stays as it was found)."""
    from paddle_tpu.aot.artifact import fresh_backend_compile

    guard = fresh_backend_compile()
    guard.__enter__()
    jax.clear_caches()        # drop executables already deserialized
    return lambda: guard.__exit__(None, None, None)


@pytest.fixture
def tpu():
    """For tests that need the chip: ask THIS process's backend (one
    process holds a chip, so no child may probe it) and skip without
    one.  This file pins the CPU unless ``JAX_PLATFORMS`` says
    otherwise: on the chip run ``JAX_PLATFORMS=tpu pytest
    tests/test_pallas_hw.py -m tpu``."""
    if jax.devices()[0].platform != "tpu":
        pytest.skip("this process's JAX backend is not a TPU")


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Bound in-process compiled-executable accumulation: a full slow-lane
    run compiles hundreds of whole-model programs in one process, and the
    native allocator state eventually SIGSEGVs inside a later XLA:CPU
    compile (observed twice at test_vision's resnet conv, which passes in
    isolation).  Dropping jit caches per module keeps the process bounded;
    the persistent disk cache keeps cross-module recompiles cheap."""
    yield
    try:
        jax.clear_caches()
    except Exception:
        pass
