"""From a profiler trace (``.xplane.pb``) to device busy time, the
operations that took most of it, and the idle gaps named by what the
host was doing: the harness's own ``bench:`` annotations and the
program's ``pt:`` spans (its engine timeline, on the same clock).  Read
with nothing but JAX's own ``ProfileData``.

What the trace of a TPU v5e holds (looked at by hand, PR 26): one plane
``/device:TPU:<n>`` a chip, with the lines ``XLA Modules`` (one event
per executed program, ``jit_<fn>(<hash>)``) and ``XLA Ops`` (one per
operation, named by its whole HLO line, ``%<name> = ...``); the host's
threads are lines of ``/host:CPU``, and ``jax.profiler.TraceAnnotation``
spans are events of its ``python`` line.  The device's clock is not the
host's (a millisecond ahead in PR 26's sessions, 0.2-3 ms behind in PR
27's), so a gap's pieces are named to within that: of two neighbouring
short spans only the sum holds (``pt:decode_dispatch`` +
``pt:logits_fetch``)."""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]          # start, end in seconds

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: host annotations that name an idle gap: the harness's own and the
#: program's timeline spans (``tracing.PROFILER_PREFIX``)
HOST_PREFIX = ("bench:", "pt:")
WINDOW_SPAN = "bench:window"


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def op_name(hlo_line: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    m = re.match(r"%?([^\s=]+)", hlo_line)
    return m.group(1) if m else hlo_line


def union_seconds(intervals: List[Interval]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def gaps(intervals: List[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The idle stretches of ``[lo, hi]`` that no interval covers."""
    out, end = [], lo
    for a, b in sorted(intervals):
        if a > end:
            out.append((end, min(a, hi)))
        end = max(end, b)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi))
    return [(a, b) for a, b in out if b > a]


def name_gap(gap: Interval, host_spans: List[Tuple[str, float, float]]
             ) -> str:
    """The innermost (shortest) host span among those that overlap the
    gap most; ``unannotated`` when none does.  (For a gap taken whole:
    ``tools/trace_report.py`` names its pieces with it.)"""
    best, best_key = "unannotated", (0.0, 0.0)
    for name, a, b in host_spans:
        ov = min(b, gap[1]) - max(a, gap[0])
        if ov <= 0:
            continue
        key = (round(ov, 6), -(b - a))
        if key > best_key:
            best, best_key = name, key
    return best


def idle_by_span(idle: List[Interval],
                 host_spans: List[Tuple[str, float, float]]
                 ) -> Dict[str, float]:
    """Idle seconds by the innermost host span at each instant: a gap
    is cut at every span boundary inside it, so each piece lies within
    or outside every span, and goes to the shortest it lies within.
    (What ``tools/trace_report.py:split_gaps`` does, kept here so that
    the program cannot move it; one sweep over both lists, since a
    trace holds a gap for every operation.)"""
    spans = sorted(host_spans, key=lambda s: s[1])
    out: Dict[str, float] = {}
    live: List[Tuple[str, float, float]] = []
    i = 0
    for a, b in sorted(idle):
        while i < len(spans) and spans[i][1] < b:
            live.append(spans[i])
            i += 1
        live = [s for s in live if s[2] > a]
        edges = sorted({a, b} | {t for _, s, e in live for t in (s, e)
                                 if a < t < b})
        for lo, hi in zip(edges, edges[1:]):
            mid = (lo + hi) / 2
            inside = [s for s in live if s[1] <= mid < s[2]]
            name = min(inside, key=lambda s: s[2] - s[1])[0] \
                if inside else "unannotated"
            out[name] = out.get(name, 0.0) + (hi - lo)
    return out


def self_seconds(events: List[Tuple[str, float, float]]
                 ) -> List[Tuple[str, float]]:
    """Each event's own time: its duration less what the events nested
    in it cover.  A ``while`` or a ``call`` holds the operations of its
    body on the same line; ranked by whole duration it would hide
    them."""
    out: List[List] = []
    stack: List[int] = []
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and out[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= min(b, out[stack[-1]][2]) - a
        out.append([name, a, b, b - a])
        stack.append(len(out) - 1)
    return [(n, max(0.0, own)) for n, _, _, own in out]


def read(path: str) -> Dict:
    """Everything the reducers need, times in seconds on the trace's own
    axis: per device its op and module events, and the host's
    ``bench:`` and ``pt:`` spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(op_name(e.name), e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9)
                           for e in line.events]
                elif line.name == "XLA Modules":
                    modules = [(e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9)
                               for e in line.events]
            devices.append({"name": plane.name, "ops": ops,
                            "modules": modules})
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.name, e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9))
    return {"devices": devices, "host": host}


def summarize(path: str, top: int = 20) -> Dict:
    """``busy_s`` (averaged over the chips), ``window_s``, the ``top``
    operations by their OWN device time, the ``top`` idle gaps by the
    innermost host span, and per-name whole durations for the pattern
    reducers.  (The result line's ``breakdown`` keeps the first ten of
    each list; the run prints all ``top`` on a line of their own.)"""
    raw = read(path)
    if not raw["devices"]:
        raise ValueError(f"{path}: no /device:TPU plane in the trace")
    window = [s for s in raw["host"] if s[0] == WINDOW_SPAN]
    spans = [s for s in raw["host"] if s[0] != WINDOW_SPAN]
    busy, op_time, mod_time, mod_count, gap_time = [], {}, {}, {}, {}
    op_self: Dict[str, float] = {}
    window_s = 0.0
    for dev in raw["devices"]:
        iv = [(a, b) for _, a, b in dev["ops"]] or \
            [(a, b) for _, a, b in dev["modules"]]
        if not iv:
            continue
        if window:
            lo, hi = window[0][1], window[0][2]
            # the device's clock is not the host's: never cut an event
            lo, hi = min(lo, min(a for a, _ in iv)), \
                max(hi, max(b for _, b in iv))
        else:
            lo, hi = min(a for a, _ in iv), max(b for _, b in iv)
        window_s = max(window_s, hi - lo)
        busy.append(union_seconds(iv))
        for name, a, b in dev["ops"]:
            op_time[name] = op_time.get(name, 0.0) + (b - a)
        for name, own in self_seconds(dev["ops"]):
            op_self[name] = op_self.get(name, 0.0) + own
        for name, a, b in dev["modules"]:
            mod_time[name] = mod_time.get(name, 0.0) + (b - a)
            mod_count[name] = mod_count.get(name, 0) + 1
        for n, secs in idle_by_span(gaps(iv, lo, hi), spans).items():
            gap_time[n] = gap_time.get(n, 0.0) + secs
    if not busy:
        raise ValueError(f"{path}: no operation ran on a device")
    rank = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    n_dev = len(raw["devices"])
    return {"busy_s": sum(busy) / len(busy), "window_s": window_s,
            "device_ops": rank({k: v / n_dev for k, v in op_self.items()}),
            "idle_gaps": rank({k: v / n_dev for k, v in gap_time.items()}),
            "op_time": op_time, "module_time": mod_time,
            "module_count": mod_count, "devices": n_dev}


def pattern_seconds(summary: Dict, pattern: str, line: str) -> float:
    """Summed device time of the events of ``line`` (``ops`` or
    ``modules``) whose name matches ``pattern``, averaged over chips."""
    table = summary["op_time" if line == "ops" else "module_time"]
    rx = re.compile(pattern)
    return sum(v for k, v in table.items() if rx.search(k)) \
        / summary["devices"]


def pattern_count(summary: Dict, pattern: str) -> int:
    rx = re.compile(pattern)
    return sum(v for k, v in summary["module_count"].items()
               if rx.search(k)) // summary["devices"]
