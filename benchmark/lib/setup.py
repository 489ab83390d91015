"""What a run pays before its window: the phases on the host's clock,
and what JAX compiled or found in its persistent cache (with the names
of the programs that missed)."""

from __future__ import annotations

import logging
import os
import re
import time
from typing import Dict, List


class Phases:
    """Consecutive phases from the command's first line on."""

    def __init__(self, t0: float):
        self.t0 = t0
        self._last = t0
        self.seconds: Dict[str, float] = {}

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self._last
        self._last = now

    def total(self) -> float:
        return self._last - self.t0


_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_MISS_LOG = re.compile(r"PERSISTENT COMPILATION CACHE MISS for '([^']+)'")


class CompileWatch(logging.Handler):
    """Listens to ``jax.monitoring`` for trace / lower / backend-compile
    seconds and cache hits and misses, and to the compiler's logger for
    the NAME of every program that missed."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.secs = {"trace": 0.0, "lower": 0.0, "backend_compile": 0.0}
        self.hits = 0
        self.misses = 0
        self.compiles = 0
        self.missed: List[str] = []

    def install(self) -> "CompileWatch":
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)
        log = logging.getLogger("jax._src.compiler")
        log.addHandler(self)
        if log.getEffectiveLevel() > logging.DEBUG:
            log.setLevel(logging.DEBUG)
        # the records are ours alone: without this they would also go
        # to whatever handler the root logger has
        log.propagate = False
        return self

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event == _TRACE:
            self.secs["trace"] += duration
        elif event == _LOWER:
            self.secs["lower"] += duration
        elif event == _COMPILE:
            self.secs["backend_compile"] += duration
            self.compiles += 1

    def _event(self, event: str, **kw) -> None:
        if event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1

    def emit(self, record: logging.LogRecord) -> None:
        m = _MISS_LOG.search(record.getMessage()) \
            if "CACHE MISS" in str(record.msg) else None
        if m:
            self.missed.append(m.group(1))

    def snapshot(self) -> Dict:
        return {"trace_s": self.secs["trace"],
                "lower_s": self.secs["lower"],
                "backend_compile_s": self.secs["backend_compile"],
                "backend_compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses,
                "missed": list(self.missed)}


def since(now: Dict, then: Dict) -> Dict:
    out = {k: now[k] - then[k] for k in now if k != "missed"}
    out["missed"] = now["missed"][len(then["missed"]):]
    return out


def apply_runtime_env(config: Dict) -> Dict[str, str]:
    """The environment the configuration states for the accelerator's
    runtime (``assumed.runtime_env``), set in this process before the
    runtime starts; a variable the environment already has is left as
    it is.  Returns what is in force, for the set-up line."""
    wanted = config.get("assumed", {}).get("runtime_env", {})
    for name, value in wanted.items():
        os.environ.setdefault(name, str(value))
    return {name: os.environ[name] for name in wanted}


def place_compile_cache() -> str:
    """Every program goes into the persistent cache, however quickly it
    compiled and however small it is: with JAX's defaults (1 s, and a
    size floor) the small programs of set-up are compiled again in every
    run, at whatever speed the host has that second.  The directory is
    the program's own choice (``JAX_COMPILATION_CACHE_DIR`` when set,
    else ``<checkout>/.jax_cache``).  Set only in this process."""
    import jax
    from paddle_tpu.core.device import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return os.path.abspath(path)
