"""Checkpoint directory manager: atomic publish, verified ``latest``
pointer, retention.

Layout (one directory per run)::

    <dir>/ckpt-00000042.pdckpt   # framework.io archive (atomic save)
    <dir>/latest                 # name of the newest VERIFIED checkpoint
    <dir>/.tmp-*                 # crash stragglers (cleaned opportunistically)

The pointer protocol makes recovery trivial: ``latest`` is only ever
rewritten (atomically) AFTER the new checkpoint file has been fully
written, renamed into place, and re-read/checksum-verified.  A process
killed at ANY byte of that sequence leaves ``latest`` naming the previous
good checkpoint; a reader that finds a corrupt or missing pointee falls
back to scanning for the newest checkpoint that passes verification.
"""

from __future__ import annotations

import os
import re
import time
from typing import Any, Dict, List, Optional, Tuple

from ..framework import io as fio
from ..framework.io import CheckpointCorruptError
from ..observability import REGISTRY as _METRICS

__all__ = ["CheckpointManager", "latest_checkpoint", "LATEST_POINTER",
           "CKPT_PREFIX", "CKPT_SUFFIX"]

LATEST_POINTER = "latest"
CKPT_PREFIX = "ckpt-"
CKPT_SUFFIX = ".pdckpt"
_CKPT_RE = re.compile(re.escape(CKPT_PREFIX) + r"(\d+)" +
                      re.escape(CKPT_SUFFIX) + r"$")


def _step_of(name: str) -> Optional[int]:
    m = _CKPT_RE.match(name)
    return int(m.group(1)) if m else None


def _list_checkpoints(directory: str) -> List[Tuple[int, str]]:
    """(step, filename) for every checkpoint file, ascending by step."""
    try:
        names = os.listdir(directory)
    except FileNotFoundError:
        return []
    out = [(s, n) for n in names
           if (s := _step_of(n)) is not None]
    out.sort()
    return out


def latest_checkpoint(directory: str) -> Optional[str]:
    """Absolute path of the newest VERIFIED checkpoint, or None.

    Follows the ``latest`` pointer first; if the pointer is missing,
    stale, or names a file that fails verification (crash between
    publish and pointer update, or on-disk corruption), falls back to
    scanning checkpoints newest-first and returns the first one that
    verifies."""
    candidates: List[str] = []
    ptr = os.path.join(directory, LATEST_POINTER)
    try:
        with open(ptr, "r") as f:
            name = f.read().strip()
        if name:
            candidates.append(name)
    except OSError:
        pass
    for _, name in reversed(_list_checkpoints(directory)):
        if name not in candidates:
            candidates.append(name)
    for name in candidates:
        path = os.path.join(directory, name)
        try:
            fio.verify(path)
        except (CheckpointCorruptError, FileNotFoundError, ValueError):
            continue
        return path
    return None


class CheckpointManager:
    """Publishes checkpoints atomically with retention and a verified
    ``latest`` pointer.

    ``save(state, step)`` is synchronous; :class:`AsyncCheckpointer`
    wraps a manager to overlap the disk write with training."""

    def __init__(self, directory: str, keep_last: int = 5):
        if keep_last < 1:
            raise ValueError(f"keep_last must be >= 1, got {keep_last}")
        self.directory = os.path.abspath(directory)
        self.keep_last = keep_last
        os.makedirs(self.directory, exist_ok=True)

    # ------------------------------------------------------------------
    def path_for(self, step: int) -> str:
        return os.path.join(self.directory,
                            f"{CKPT_PREFIX}{int(step):08d}{CKPT_SUFFIX}")

    def save(self, state: Any, step: int) -> str:
        """Write, verify, publish ``latest``, rotate.  Returns the path.

        Order matters: the pointer only moves after verification, so an
        interrupted save (even one that corrupted its own file) never
        changes what ``latest`` resolves to."""
        path = self.path_for(step)
        t0 = time.perf_counter()
        fio.save(state, path)
        t_save = time.perf_counter()
        fio.verify(path)
        t_verify = time.perf_counter()
        fio.atomic_write_bytes(os.path.basename(path).encode(),
                               os.path.join(self.directory, LATEST_POINTER))
        self._rotate(keep_name=os.path.basename(path))
        self._sweep_stragglers()
        if _METRICS.enabled:        # host-side telemetry (ISSUE 5)
            t_publish = time.perf_counter()
            _METRICS.counter("checkpoint.saves_total").inc()
            _METRICS.histogram("checkpoint.save_secs", unit="s",
                               desc="write+fsync+rename").record(
                                   t_save - t0)
            _METRICS.histogram("checkpoint.verify_secs", unit="s").record(
                t_verify - t_save)
            _METRICS.event(
                "checkpoint", phase="save", step=int(step),
                path=os.path.basename(path),
                save_secs=round(t_save - t0, 6),
                verify_secs=round(t_verify - t_save, 6),
                publish_secs=round(t_publish - t_verify, 6),
                total_secs=round(t_publish - t0, 6),
                bytes=os.path.getsize(path))
        return path

    def restore(self, path: Optional[str] = None) -> Optional[Any]:
        """Load ``path`` (default: the latest verified checkpoint).
        Returns None when the directory holds no usable checkpoint."""
        if path is None:
            path = latest_checkpoint(self.directory)
            if path is None:
                return None
        return fio.load(path)

    def all_steps(self) -> List[int]:
        return [s for s, _ in _list_checkpoints(self.directory)]

    # ------------------------------------------------------------------
    def _rotate(self, keep_name: str) -> None:
        ckpts = _list_checkpoints(self.directory)
        excess = len(ckpts) - self.keep_last
        for _, name in ckpts:
            if excess <= 0:
                break
            if name == keep_name:   # never delete what latest names
                continue
            try:
                os.unlink(os.path.join(self.directory, name))
            except OSError:
                pass
            excess -= 1

    def _sweep_stragglers(self) -> None:
        """Remove ``.tmp-*`` leftovers from crashed saves (best effort)
        — only temp files of names THIS manager writes (its checkpoints
        and the pointer; ``atomic_write_bytes`` ends a temp name with
        its target's).  Another writer may be mid-publish in the same
        directory: ``Model.fit(async_save=True)`` saves ``epoch_N.*``
        from the main thread while this runs on the writer thread, and
        unlinking its temp file fails its ``os.replace``."""
        try:
            names = os.listdir(self.directory)
        except OSError:
            return
        ours = (CKPT_SUFFIX, "-" + LATEST_POINTER)
        for n in names:
            if n.startswith(fio._TMP_PREFIX) and n.endswith(ours):
                try:
                    os.unlink(os.path.join(self.directory, n))
                except OSError:
                    pass
