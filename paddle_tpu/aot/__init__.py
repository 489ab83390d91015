"""AOT compile-artifact subsystem (ISSUE 6).

Serialize traced+lowered+compiled XLA executables once, warm-start
every other process from the artifact directory:

* :mod:`~paddle_tpu.aot.artifact` — the versioned, CRC'd store with an
  environment/config manifest;
* :mod:`~paddle_tpu.aot.buckets` — declared serve shape buckets, so
  variable prefill load lands on precompiled programs;
* :mod:`~paddle_tpu.aot.serve` — export/load for the continuous-
  batching engine (``ContinuousBatchingEngine(aot_dir=...)``);
* :mod:`~paddle_tpu.aot.train` — export/load for the hapi jitted train
  step (``Model.prepare(aot_dir=...)``) and the raw fused
  ``build_jit_apply`` program.

The recompile-budget ratchet over this subsystem lives in
``tools/compile_budget.py`` + ``COMPILE_BUDGET.md``; see
``docs/aot.md`` for the artifact layout and policies.
"""

from .artifact import (LATEST_POINTER, AotArtifactCorruptError,
                       AotError, AotManifestMismatchError, ArtifactStore,
                       args_signature, config_hash,
                       environment_fingerprint, export_compiled,
                       new_generation, resolve_artifact_dir)
from .buckets import DEFAULT_CHUNK_BUCKETS, ShapeBucketRegistry
from .serve import engine_config, export_engine, load_engine_artifacts
from .train import (AotTrainStep, export_jit_apply, export_train_step,
                    load_train_step)

__all__ = [
    "AotError", "AotArtifactCorruptError", "AotManifestMismatchError",
    "ArtifactStore", "args_signature", "config_hash",
    "environment_fingerprint",
    "export_compiled", "new_generation", "resolve_artifact_dir",
    "LATEST_POINTER",
    "DEFAULT_CHUNK_BUCKETS", "ShapeBucketRegistry",
    "engine_config", "export_engine", "load_engine_artifacts",
    "AotTrainStep", "export_jit_apply", "export_train_step",
    "load_train_step",
]
