"""Versioned compile-artifact store (ISSUE 6).

An artifact directory holds XLA executables serialized AHEAD of time —
the jitted train step and the serving engine's decode / chunked-prefill
steps — so a fleet restart deserializes a ready-to-run program instead
of paying trace+lower+backend-compile per process.  Layout:

    <dir>/manifest.json      versioned manifest (atomic publish)
    <dir>/<name>.xbin        one pickled (payload, in_tree, out_tree)
                             per executable, CRC32'd in the manifest

The manifest records everything that makes an executable UNSAFE to
reuse somewhere else: jax/jaxlib versions and backend platform (XLA
executables are not portable across either), a caller-supplied config
hash (model/engine geometry), each executable's input signature, its
donation signature, and the declared shape buckets.  ``load`` verifies
all of it and raises a typed :class:`AotError` subclass on any
mismatch — callers fall back to a fresh compile (with a telemetry
event) rather than run a wrong or corrupt program.

Devices: an executable is compiled for a device assignment (one device
for a serving engine, a mesh's devices for an SPMD train step).  ``put``
records the ids in assignment order and ``get`` loads the program onto
exactly those devices of this process — never onto "every local
device", which is what the loader assumes when it is not told.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import pickle
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

__all__ = [
    "AotError", "AotArtifactCorruptError", "AotManifestMismatchError",
    "ArtifactStore", "environment_fingerprint",
    "config_hash", "args_signature", "executable_device_ids",
    "devices_for_ids",
    "fresh_backend_compile", "MANIFEST_MAGIC", "LATEST_POINTER",
    "new_generation", "resolve_artifact_dir",
]

MANIFEST_MAGIC = "paddle_tpu.aot.v1"
_MANIFEST = "manifest.json"
#: rotation-root pointer file naming the live generation subdirectory
LATEST_POINTER = "latest"
_GEN_PREFIX = "gen-"


class AotError(RuntimeError):
    """Base: an AOT artifact cannot be used; fall back to fresh compile."""


class AotArtifactCorruptError(AotError):
    """Artifact payload or manifest is truncated, unreadable, or fails
    its CRC — the directory should be re-exported."""


class AotManifestMismatchError(AotError):
    """The artifact was built for a different environment/config
    (jax/jaxlib version skew, different platform, changed model geometry,
    missing executable).  Not corruption — just not OURS."""


def environment_fingerprint() -> Dict[str, str]:
    """Everything an XLA executable is specialized to besides its
    inputs: jax/jaxlib versions and the backend platform."""
    import jaxlib
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": jax.default_backend(),
    }


@contextlib.contextmanager
def fresh_backend_compile():
    """Disable jax's persistent compilation cache for the duration.

    Serializing an executable that ``compile()`` LOADED from the
    persistent cache (rather than freshly built) yields a payload that
    fails to deserialize on XLA:CPU with ``Symbols not found: [...]``
    — the round-trip through the cache drops the jitted aux functions.
    Every export path compiles inside this guard so the serialized
    artifact always comes from a fresh backend compile; the in-memory
    jit caches are untouched.

    The cache is switched off by its enable flag, not by clearing its
    directory: the directory may have come from
    ``JAX_COMPILATION_CACHE_DIR`` and is left as it was found.
    ``compilation_cache.is_cache_used`` memoizes its decision at the
    first compile of the process, so ``reset_cache()`` (which drops only
    that in-memory memo; the disk cache is untouched) runs on entry so
    the flag is re-read, and on exit so later compiles use the cache
    again."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    try:
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def executable_device_ids(compiled) -> List[int]:
    """Ids of the devices ``compiled`` runs on, in assignment order."""
    return [d.id for d in compiled.runtime_executable().local_devices()]


def devices_for_ids(ids) -> Optional[List[jax.Device]]:
    """This process's devices with those ids, in that order — what
    ``deserialize_and_load`` must be told as ``execution_devices`` — or
    None when ``ids`` is empty or names a device this process lacks."""
    by_id = {d.id: d for d in jax.devices()}
    if not ids or any(i not in by_id for i in ids):
        return None
    return [by_id[i] for i in ids]


def config_hash(config: Dict[str, Any]) -> str:
    """Stable digest of a JSON-able config dict."""
    blob = json.dumps(config, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _leaf_sig(x) -> List:
    shape = getattr(x, "shape", None)
    if shape is None:
        return [[], type(x).__name__]
    return [list(shape), str(getattr(x, "dtype", "?"))]


def args_signature(args: Tuple) -> Tuple[str, List]:
    """(treedef-str, per-leaf [shape, dtype]) for a call-args tuple —
    cheap (no tracing), used both at export time (recorded in the
    manifest) and at load/dispatch time (matched against it)."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return str(treedef), [_leaf_sig(v) for v in leaves]


def _sig_matches(entry_sig, args) -> bool:
    td, leaves = args_signature(args)
    return entry_sig == [td, leaves] or tuple(entry_sig) == (td, leaves)


# ---------------------------------------------------------------------
# rotation roots (ISSUE 8): long-lived fleets re-export artifacts on
# every jax upgrade / geometry change; a ROOT directory holds numbered
# generation subdirs plus a LATEST pointer published atomically through
# framework.io, and gc() prunes old generations without ever touching
# the one the pointer names
# ---------------------------------------------------------------------
def _generation_dirs(root: str) -> List[str]:
    """Generation subdirectory names under ``root``, oldest first."""
    try:
        names = os.listdir(root)
    except FileNotFoundError:
        return []
    gens = []
    for n in names:
        if n.startswith(_GEN_PREFIX) and os.path.isdir(
                os.path.join(root, n)):
            try:
                gens.append((int(n[len(_GEN_PREFIX):]), n))
            except ValueError:
                continue
    return [n for _, n in sorted(gens)]


def new_generation(root: str, registry=None) -> "ArtifactStore":
    """Create the next ``gen-NNNN`` subdirectory under a rotation root
    and return an :class:`ArtifactStore` for it.  The generation is
    INVISIBLE to loaders until :meth:`ArtifactStore.publish` moves the
    ``latest`` pointer (write -> verify-by-construction -> publish, the
    checkpoint-manager recipe)."""
    gens = _generation_dirs(root)
    nxt = 1 + (int(gens[-1][len(_GEN_PREFIX):]) if gens else 0)
    d = os.path.join(root, f"{_GEN_PREFIX}{nxt:04d}")
    os.makedirs(d, exist_ok=True)
    return ArtifactStore(d, registry=registry)


def read_latest(root: str) -> Optional[str]:
    """The generation directory the ``latest`` pointer names, or None
    when ``root`` is not a rotation root."""
    try:
        with open(os.path.join(root, LATEST_POINTER),
                  encoding="utf-8") as f:
            name = f.read().strip()
    except (FileNotFoundError, NotADirectoryError):
        return None
    return os.path.join(root, os.path.basename(name)) if name else None


def resolve_artifact_dir(path: str) -> str:
    """Loader-side rotation awareness: a plain artifact directory
    resolves to itself; a rotation root resolves through its ``latest``
    pointer.  A pointer naming a missing generation is corruption (the
    pointer is published atomically AFTER the generation's manifest, so
    this can only mean someone deleted the live generation)."""
    if os.path.exists(os.path.join(path, _MANIFEST)):
        return path
    pointed = read_latest(path)
    if pointed is None:
        return path
    if not os.path.exists(os.path.join(pointed, _MANIFEST)):
        raise AotArtifactCorruptError(
            f"{path}: latest pointer names {os.path.basename(pointed)!r}"
            " but that generation has no manifest — the live generation "
            "was deleted out from under the pointer; re-export")
    return pointed


class ArtifactStore:
    """One artifact directory: a CRC'd manifest plus serialized
    executables, written atomically (framework.io durability seams) and
    verified on read.

    ``registry`` (an observability MetricsRegistry; defaults to the
    process-wide REGISTRY) receives ``aot`` events for loads and
    refusals so warm-start behavior shows up in the same stream as
    compile telemetry."""

    def __init__(self, directory: str, registry=None):
        self.directory = directory
        if registry is None:
            from ..observability import REGISTRY
            registry = REGISTRY
        self._registry = registry
        self._manifest: Optional[Dict[str, Any]] = None

    # -- telemetry -----------------------------------------------------
    def _event(self, action: str, **kw) -> None:
        reg = self._registry
        if reg is not None and reg.enabled:
            reg.counter(f"aot.{action}_total").inc()
            reg.event("aot", action=action, dir=self.directory, **kw)

    # -- write side ----------------------------------------------------
    def begin(self, *, config: Dict[str, Any],
              buckets: Optional[Dict[str, Any]] = None) -> "ArtifactStore":
        """Start a fresh manifest for this export run."""
        self._manifest = {
            "magic": MANIFEST_MAGIC,
            "version": 1,
            "env": environment_fingerprint(),
            "config": config,
            "config_hash": config_hash(config),
            "buckets": buckets,
            "executables": {},
        }
        return self

    def extend(self) -> "ArtifactStore":
        """Reopen this store's ON-DISK manifest for appending — the
        per-topology elastic exports grow one store incrementally (a
        reshape adds the new mesh's programs next to the old ones)
        instead of `begin()`-resetting it."""
        if self._manifest is None:
            self._manifest = self.manifest()
        return self

    def put(self, name: str, compiled, example_args: Tuple, *,
            donate_argnums: Tuple[int, ...] = ()) -> None:
        """Serialize one compiled executable (``jax.jit(f).lower(*args)
        .compile()``) under ``name``.  ``example_args`` must be the
        exact call signature the executable was compiled for — its
        signature is recorded so loaders can dispatch without a failed
        call."""
        if self._manifest is None:
            raise AotError("ArtifactStore.put before begin()")
        from jax.experimental import serialize_executable as se
        payload, in_tree, out_tree = se.serialize(compiled)
        blob = pickle.dumps((payload, in_tree, out_tree))
        from ..framework.io import atomic_write_bytes
        os.makedirs(self.directory, exist_ok=True)
        fname = f"{name}.xbin"
        atomic_write_bytes(blob, os.path.join(self.directory, fname))
        td, leaves = args_signature(example_args)
        self._manifest["executables"][name] = {
            "file": fname,
            "crc32": zlib.crc32(blob),
            "size": len(blob),
            "donate_argnums": list(donate_argnums),
            "device_ids": executable_device_ids(compiled),
            "in_sig": [td, leaves],
        }
        self._flush()

    def _flush(self) -> None:
        from ..framework.io import atomic_write_bytes
        os.makedirs(self.directory, exist_ok=True)
        atomic_write_bytes(
            json.dumps(self._manifest, indent=1, default=str).encode(),
            os.path.join(self.directory, _MANIFEST))

    # -- rotation ------------------------------------------------------
    def publish(self, keep_last: Optional[int] = None) -> str:
        """Point the parent rotation root's ``latest`` at THIS
        (fully written) generation — atomically, via the same
        ``framework.io`` seam as checkpoint publishes, so a crash
        mid-publish leaves the previous pointer intact and loadable.
        With ``keep_last``, old generations are pruned afterwards
        (pointer FIRST, then gc: the window where both generations
        exist is the safe direction).  Returns the root."""
        if not self.exists():
            raise AotError(f"{self.directory}: publish() before any "
                           "executable was put — nothing to point at")
        root = os.path.dirname(os.path.abspath(self.directory))
        from ..framework.io import atomic_write_bytes
        atomic_write_bytes(
            os.path.basename(self.directory).encode(),
            os.path.join(root, LATEST_POINTER))
        self._event("publish", generation=os.path.basename(
            self.directory))
        if keep_last is not None:
            ArtifactStore(root, registry=self._registry).gc(
                keep_last=keep_last)
        return root

    def gc(self, keep_last: int) -> List[str]:
        """Prune old generations under this ROOT directory, keeping the
        ``keep_last`` newest — and, unconditionally, whichever one the
        ``latest`` pointer names (pointer-last semantics: the pointer is
        the source of truth, age is not).  Returns removed paths."""
        if keep_last < 1:
            raise ValueError(f"keep_last must be >= 1, got {keep_last}")
        import shutil
        root = self.directory
        gens = _generation_dirs(root)
        pointed = read_latest(root)
        keep = set(gens[-keep_last:])
        if pointed is not None:
            keep.add(os.path.basename(pointed))
        removed = []
        for name in gens:
            if name in keep:
                continue
            path = os.path.join(root, name)
            shutil.rmtree(path, ignore_errors=True)
            removed.append(path)
        if removed:
            self._event("gc", removed=len(removed),
                        kept=sorted(keep))
        return removed

    # -- read side -----------------------------------------------------
    def exists(self) -> bool:
        return os.path.exists(os.path.join(self.directory, _MANIFEST))

    def manifest(self) -> Dict[str, Any]:
        """Parse + structurally validate the manifest (cached)."""
        if self._manifest is not None:
            return self._manifest
        path = os.path.join(self.directory, _MANIFEST)
        try:
            with open(path, "rb") as f:
                m = json.loads(f.read())
        except FileNotFoundError:
            raise AotManifestMismatchError(
                f"{self.directory}: no AOT manifest")
        except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
            raise AotArtifactCorruptError(
                f"{path}: manifest unreadable: {e}") from e
        if m.get("magic") != MANIFEST_MAGIC:
            raise AotManifestMismatchError(
                f"{path}: not a {MANIFEST_MAGIC} manifest "
                f"(magic={m.get('magic')!r})")
        if not isinstance(m.get("executables"), dict):
            raise AotArtifactCorruptError(
                f"{path}: manifest has no executables table")
        self._manifest = m
        return m

    def check_env(self) -> None:
        """Version/platform skew gate: an executable compiled by another
        jax/jaxlib or for another backend must never be deserialized."""
        want = self.manifest().get("env") or {}
        have = environment_fingerprint()
        drift = {k: (want.get(k), have[k]) for k in have
                 if want.get(k) != have[k]}
        if drift:
            raise AotManifestMismatchError(
                f"{self.directory}: environment skew {drift} — artifacts "
                "must be re-exported for this environment")

    def check_config(self, config: Dict[str, Any]) -> None:
        m = self.manifest()
        want = config_hash(config)
        if m.get("config_hash") != want:
            raise AotManifestMismatchError(
                f"{self.directory}: config hash {m.get('config_hash')!r} "
                f"!= expected {want!r} (model/engine geometry changed)")

    def buckets(self) -> Optional[Dict[str, Any]]:
        return self.manifest().get("buckets")

    def entry(self, name: str) -> Dict[str, Any]:
        entry = self.manifest()["executables"].get(name)
        if entry is None:
            raise AotManifestMismatchError(
                f"{self.directory}: no executable {name!r} in manifest")
        return entry

    def matches_signature(self, name: str, args: Tuple) -> bool:
        """Does ``name``'s recorded input signature match ``args``?"""
        return _sig_matches(self.entry(name)["in_sig"], args)

    def get(self, name: str) -> Callable:
        """CRC-verify and deserialize ``name`` onto the devices it was
        exported for; returns the loaded executable as a callable.
        Raises AotError subclasses on any reason the artifact cannot be
        used here."""
        entry = self.entry(name)
        devices = devices_for_ids(entry.get("device_ids"))
        if devices is None:
            raise AotManifestMismatchError(
                f"{self.directory}: executable {name!r} was exported for "
                f"device ids {entry.get('device_ids')}, this process has "
                f"{[d.id for d in jax.devices()]} — re-export here")
        path = os.path.join(self.directory, entry["file"])
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError as e:
            raise AotArtifactCorruptError(
                f"{path}: executable payload unreadable: {e}") from e
        if zlib.crc32(blob) != entry["crc32"]:
            self._event("crc_mismatch", name=name)
            raise AotArtifactCorruptError(
                f"{path}: CRC mismatch — artifact is corrupt (bit-rot or "
                "torn write); re-export")
        from jax.experimental import serialize_executable as se
        try:
            payload, in_tree, out_tree = pickle.loads(blob)
            loaded = se.deserialize_and_load(payload, in_tree, out_tree,
                                             execution_devices=devices)
        except AotError:
            raise
        except Exception as e:
            # the payload passed its CRC, so this is version skew inside
            # the serialized executable itself (e.g. an xla runtime that
            # no longer accepts the proto) — surface as mismatch
            raise AotManifestMismatchError(
                f"{path}: executable failed to deserialize on jax "
                f"{jax.__version__}: {type(e).__name__}: {e}") from e
        self._event("load", name=name)
        return loaded


def export_compiled(directory: str, name: str, jitted, example_args: Tuple,
                    *, config: Dict[str, Any],
                    donate_argnums: Tuple[int, ...] = (),
                    buckets: Optional[Dict[str, Any]] = None,
                    registry=None) -> ArtifactStore:
    """One-call export of a single jitted function: trace → lower →
    compile ``jitted`` at ``example_args`` and store it under ``name``.
    ``donate_argnums`` must mirror what ``jitted`` was built with — it
    is recorded in the manifest, not applied here."""
    store = ArtifactStore(directory, registry=registry)
    store.begin(config=config, buckets=buckets)
    with fresh_backend_compile():
        compiled = jitted.lower(*example_args).compile()
    store.put(name, compiled, example_args, donate_argnums=donate_argnums)
    return store
