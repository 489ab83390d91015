"""End-to-end request tracing (ISSUE 20): span timelines across
wire → router → engine with per-phase latency-budget attribution.

The aggregate telemetry (``registry.py``) says *that* p99 TTFT
degraded; this module says *which* request and *which* phase — queue
wait vs bucketed prefill vs preempt-spill-restore vs crash replay vs
prefix-cache restore — ate the budget.  One :class:`Trace` per served
request, each a bounded list of completed :class:`Span` records
(monotonic-clock phases, parent links, attrs), indexed by the
outermost request id so the HTTP debug endpoint (``GET
/v1/trace/<request_id>``) and the loadgen's attribution report can
find it after the fact.

Design constraints (the PR 5 contract, verbatim):

* **Host-side only.**  A span call inside a traced/jit region is a
  TL001 hazard by construction; the tracelint ratchet pins this
  package at zero TL001/TL006 findings, and the ``serve_trace_warm``
  budget row pins a traced warm engine at ZERO backend compiles.
* **Thread-safe.**  The driver thread, HTTP handler threads, and the
  housekeeper all record concurrently; per-trace state mutates under a
  small lock, the ambient "current trace" is thread-local.
* **Zero cost when disabled.**  Every entry point checks
  ``TRACER.enabled`` (one boolean) and returns before allocating;
  instrumented sites additionally guard with ``if TRACER.enabled:`` so
  the disabled serve path does no per-step work at all
  (``tests/test_tracing.py`` asserts no net allocations, mirroring
  ``test_observability.py``).
* **Ring-bounded.**  Finished traces live in a ``deque(maxlen=...)``;
  each trace caps its span list (``max_spans``) and counts drops
  instead of growing without bound.

Propagation: the tracer keeps an ambient per-thread "current trace".
``ServingFrontend.submit`` begins a trace and activates it around
``engine.add_request``, so every layer underneath — router placement,
supervisor bookkeeping, engine queue entry — stamps spans onto the
same trace with no signature changes.  Replay paths (supervisor crash
replay, fleet re-placement) re-activate the original request's trace
around their inner ``add_request``/``adopt`` calls, which is exactly
why a mid-stream replica kill keeps one trace_id across the move (the
structural pin in tests/test_tracing.py).

SLO exemplars: :meth:`SpanTracer.finish` emits the full span tree as a
``trace`` event into the metrics registry when the request missed its
SLO or ended REJECTED / TIMED_OUT / replayed — those records ride the
:class:`~paddle_tpu.observability.FlightRecorder` ring, so every
flight dump is a post-mortem with timelines.

The engine timeline (ISSUE 27): beside the request traces the tracer
keeps ONE :class:`Timeline` of scheduler iterations — a ring of the
newest ``N`` span trees (``iteration`` → ``engine_step`` → ``admit`` →
``prefill`` → ...), written through :meth:`Timeline.enter` /
:meth:`Timeline.leave`.  Each span is also opened, under the same name
with the :data:`PROFILER_PREFIX`, as a ``jax.profiler`` annotation, so
with a profiler session open the engine's phases lie on the profiler's
own clock beside the device's operations (no-ops otherwise).  A new
engine phase gets its span through that one pair, nowhere else.

Exports: :func:`write_spans_jsonl` (one span per line —
``tools/trace_report.py`` renders it), :func:`attribution` (per-phase
p50/p95 contributions to TTFT/TPOT — ``LoadReport.attribution``).  The
picture is the profiler's own trace: ``tools/trace_report.py --xplane``.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Deque, Dict, List, Optional, Tuple

from jax import profiler as _profiler

__all__ = ["Span", "Trace", "Iteration", "Timeline", "SpanTracer",
           "TRACER", "PROFILER_PREFIX", "attribution",
           "write_spans_jsonl"]

#: every timeline span is ALSO a ``jax.profiler`` annotation of this
#: prefix + its name (``pt:logits_fetch``): the names a trace reader
#: looks for on the host's ``python`` line
PROFILER_PREFIX = "pt:"

#: iterations the engine timeline keeps: a 30 s window at 10
#: iterations/s thirteen times over
TIMELINE_CAPACITY = 4096


class Span:
    """One completed phase: ``[t0, t1)`` on the monotonic clock.

    Spans are recorded AFTER the phase ends (one append, no open-span
    bookkeeping on the hot path); ``parent`` is the span id of the
    enclosing phase (0 = the trace root)."""

    __slots__ = ("name", "t0", "t1", "span_id", "parent", "attrs")

    def __init__(self, name: str, t0: float, t1: float, span_id: int,
                 parent: int = 0,
                 attrs: Optional[Dict[str, Any]] = None):
        self.name = name
        self.t0 = t0
        self.t1 = t1
        self.span_id = span_id
        self.parent = parent
        self.attrs = attrs

    @property
    def duration_s(self) -> float:
        return self.t1 - self.t0

    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "name": self.name, "span_id": self.span_id,
            "parent": self.parent,
            "t0_s": round(self.t0, 6), "t1_s": round(self.t1, 6),
            "dur_s": round(self.t1 - self.t0, 6)}
        if self.attrs:
            d["attrs"] = self.attrs
        return d


class Trace:
    """One request's span timeline: a rooted tree of completed spans.

    The root (span_id 0) opens at :meth:`SpanTracer.begin` and closes
    at :meth:`SpanTracer.finish`; every other span parents to it (or
    to an explicit ``parent=``).  Span times are **relative to the
    trace's start** (``t0 == 0.0`` for the root), so trees are
    directly comparable request-to-request; ``wall_t0`` anchors them
    back to the epoch for chrome-trace export."""

    __slots__ = ("trace_id", "rid", "request_id", "name", "mono_t0",
                 "wall_t0", "state", "meta", "spans", "dropped",
                 "max_spans", "_lock", "_next_span", "_end",
                 "_marks")

    def __init__(self, trace_id: str, *, rid: Optional[int] = None,
                 request_id: Optional[str] = None,
                 name: str = "request", max_spans: int = 1024,
                 mono_t0: Optional[float] = None):
        self.trace_id = trace_id
        self.rid = rid
        self.request_id = request_id
        self.name = name
        self.mono_t0 = time.monotonic() if mono_t0 is None else mono_t0
        self.wall_t0 = time.time()
        self.state: Optional[str] = None
        self.meta: Dict[str, Any] = {}
        self.spans: List[Span] = []
        self.dropped = 0
        self.max_spans = int(max_spans)
        self._lock = threading.Lock()
        self._next_span = 1
        self._end: Optional[float] = None
        # named monotonic timestamps (queue entry, first token, ...)
        self._marks: Dict[str, float] = {}

    # -- recording -----------------------------------------------------
    def now(self) -> float:
        """Seconds since the trace began (the span clock)."""
        return time.monotonic() - self.mono_t0

    def add(self, name: str, t0: float, t1: float, *, parent: int = 0,
            **attrs) -> int:
        """Record one completed span (trace-relative seconds); returns
        its span id (0 when the span cap dropped it)."""
        with self._lock:
            if len(self.spans) >= self.max_spans:
                self.dropped += 1
                return 0
            sid = self._next_span
            self._next_span += 1
            self.spans.append(Span(name, t0, t1, sid, parent,
                                   attrs or None))
            return sid

    @contextmanager
    def span(self, name: str, *, parent: int = 0, **attrs):
        """Time a phase: ``with tr.span("prefill"): ...``."""
        t0 = self.now()
        try:
            yield
        finally:
            self.add(name, t0, self.now(), parent=parent, **attrs)

    def event(self, name: str, **attrs) -> int:
        """Zero-duration instant (placement decision, first token)."""
        t = self.now()
        return self.add(name, t, t, **attrs)

    def mark(self, name: str) -> None:
        """Stamp a named instant to subtract against later (queue
        entry → admission = queue_wait)."""
        self._marks[name] = self.now()

    def take_mark(self, name: str) -> Optional[float]:
        return self._marks.pop(name, None)

    # -- reading -------------------------------------------------------
    @property
    def finished(self) -> bool:
        return self._end is not None

    @property
    def duration_s(self) -> Optional[float]:
        return self._end

    def snapshot(self) -> List[Span]:
        with self._lock:
            return list(self.spans)

    def phase_totals(self, *, t_lo: float = 0.0,
                     t_hi: Optional[float] = None) -> Dict[str, float]:
        """Summed span seconds per phase name, clipped to the window
        ``[t_lo, t_hi]`` — the attribution primitive (TTFT window =
        [0, first_token], TPOT window = [first_token, end])."""
        hi = t_hi if t_hi is not None \
            else (self._end if self._end is not None else self.now())
        out: Dict[str, float] = {}
        for s in self.snapshot():
            lo, up = max(s.t0, t_lo), min(s.t1, hi)
            if up > lo:
                out[s.name] = out.get(s.name, 0.0) + (up - lo)
        return out

    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "trace_id": self.trace_id, "name": self.name,
            "rid": self.rid, "request_id": self.request_id,
            "state": self.state,
            "wall_t0": round(self.wall_t0, 6),
            "duration_s": (None if self._end is None
                           else round(self._end, 6)),
            "spans": [s.to_dict() for s in self.snapshot()],
            "dropped_spans": self.dropped,
        }
        if self.meta:
            d["meta"] = dict(self.meta)
        return d

    def _close(self, state: str, **meta) -> None:
        with self._lock:
            if self._end is not None:
                return
            self._end = time.monotonic() - self.mono_t0
        self.state = state
        self.meta.update(meta)

    def __repr__(self) -> str:
        return (f"Trace({self.trace_id}, rid={self.rid}, "
                f"state={self.state}, spans={len(self.spans)})")


class Iteration:
    """One scheduler iteration on the engine timeline: its number and
    its spans in the order they opened (the root first).  Span times
    are ``time.monotonic()`` seconds — a request trace's span lies at
    ``trace.mono_t0 + span.t0`` on the same axis; span ids count from 1
    within the iteration and the root's parent is 0."""

    __slots__ = ("n", "spans")

    def __init__(self, n: int):
        self.n = n
        self.spans: List[Span] = []

    def to_dict(self) -> Dict[str, Any]:
        return {"n": self.n, "spans": [s.to_dict() for s in self.spans]}


class _Open(threading.local):
    """Per-thread tree under construction: the open spans, innermost
    last, each with its profiler annotation."""

    def __init__(self):
        self.stack: List[Tuple[Span, Any]] = []
        self.cur: Optional[Iteration] = None


class Timeline:
    """The engine's own phases, one span tree a scheduler iteration, in
    a ring of the newest :data:`TIMELINE_CAPACITY` iterations (the tail
    is what a profiler session at the end of a window sees; ``dropped``
    counts what the ring let go).

    The ONE recording helper: ``sp = tl.enter(name, **attrs)`` ...
    ``tl.leave(sp, **more)`` stamps the span on the monotonic clock
    AND holds a ``jax.profiler`` annotation ``pt:<name>`` open over the
    same interval.  A span opened while none is open starts a new
    iteration (its annotation is a ``StepTraceAnnotation`` numbered
    like the iteration); any other nests under the innermost open one.
    ``leave`` also closes what an exception left open beneath ``sp``,
    so a root left in a ``finally`` keeps every tree well formed.
    Trees are built per thread and published whole under ``_lock``."""

    def __init__(self):
        self._ring: Deque[Iteration] = collections.deque(
            maxlen=TIMELINE_CAPACITY)
        self._lock = threading.Lock()
        self._open = _Open()
        self._seq = 0
        self.dropped = 0

    def enter(self, name: str, **attrs) -> Span:
        st = self._open
        stack = st.stack
        if stack:
            parent = stack[-1][0].span_id
            ann = _profiler.TraceAnnotation(PROFILER_PREFIX + name)
        else:
            with self._lock:
                self._seq += 1
                n = self._seq
            st.cur = Iteration(n)
            parent = 0
            attrs["n"] = n
            ann = _profiler.StepTraceAnnotation(PROFILER_PREFIX + name,
                                                step_num=n)
        ann.__enter__()
        t0 = time.monotonic()
        spans = st.cur.spans
        sp = Span(name, t0, t0, len(spans) + 1, parent, attrs or None)
        spans.append(sp)
        stack.append((sp, ann))
        return sp

    def leave(self, sp: Span, **attrs) -> None:
        st = self._open
        stack = st.stack
        if attrs:
            sp.attrs = {**sp.attrs, **attrs} if sp.attrs else attrs
        if not any(o is sp for o, _ in stack):
            return                  # an enclosing leave already took it
        t1 = time.monotonic()
        while True:
            top, ann = stack.pop()
            top.t1 = t1
            ann.__exit__(None, None, None)
            if top is sp:
                break
        if not stack:
            it, st.cur = st.cur, None
            with self._lock:
                if len(self._ring) == self._ring.maxlen:
                    self.dropped += 1
                self._ring.append(it)

    def iterations(self, last: Optional[int] = None) -> List[Iteration]:
        """The finished iterations, oldest first (the newest ``last``)."""
        with self._lock:
            its = list(self._ring)
        return its if last is None else its[-last:]

    def to_dict(self, last: Optional[int] = None) -> Dict[str, Any]:
        return {"dropped": self.dropped,
                "iterations": [i.to_dict() for i in self.iterations(last)]}


class _Ambient(threading.local):
    """Per-thread active-trace stack (the propagation channel)."""

    def __init__(self):
        self.stack: List[Trace] = []


class SpanTracer:
    """Process-wide trace registry + the ambient propagation channel.

    Mirrors :class:`MetricsRegistry`'s lifecycle: disabled by default,
    one boolean short-circuit at every entry point, thread-safe, and
    ring-bounded (``done_capacity`` finished traces kept for the debug
    endpoint / attribution; active traces are bounded by the serve
    stack's own admission control)."""

    def __init__(self, enabled: bool = False, *,
                 done_capacity: int = 256, max_spans: int = 1024):
        self.enabled = bool(enabled)
        self.max_spans = int(max_spans)
        # SLO thresholds for exemplar capture (None = no SLO check)
        self.slo_ttft_s: Optional[float] = None
        self.slo_tpot_s: Optional[float] = None
        self._lock = threading.Lock()
        self._seq = 0
        self._active: Dict[str, Trace] = {}          # trace_id → trace
        self._by_rid: Dict[int, Trace] = {}          # outer rid → trace
        self._done: Deque[Trace] = collections.deque(
            maxlen=int(done_capacity))
        self._ambient = _Ambient()
        self._train: Optional[Trace] = None
        self._timeline: Optional[Timeline] = None

    # -- lifecycle ------------------------------------------------------
    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def configure(self, *, slo_ttft_s: Optional[float] = None,
                  slo_tpot_s: Optional[float] = None) -> None:
        """Set the SLO thresholds exemplar capture compares against."""
        self.slo_ttft_s = slo_ttft_s
        self.slo_tpot_s = slo_tpot_s

    def reset(self) -> None:
        """Drop all trace state (test / bench isolation)."""
        with self._lock:
            self._active.clear()
            self._by_rid.clear()
            self._done.clear()
            self._train = None
            self._timeline = None
            self._seq = 0
        self.slo_ttft_s = None
        self.slo_tpot_s = None

    # -- trace lifecycle ------------------------------------------------
    def begin(self, *, rid: Optional[int] = None,
              request_id: Optional[str] = None,
              name: str = "request", **meta) -> Optional[Trace]:
        """Open a trace (None when disabled).  The root span (id 0)
        covers begin → finish."""
        if not self.enabled:
            return None
        with self._lock:
            self._seq += 1
            trace_id = f"{os.getpid():x}-{self._seq:08x}"
            tr = Trace(trace_id, rid=rid, request_id=request_id,
                       name=name, max_spans=self.max_spans)
            self._active[trace_id] = tr
            if rid is not None:
                self._by_rid[rid] = tr
        if meta:
            tr.meta.update(meta)
        return tr

    def bind(self, tr: Optional[Trace], rid: int) -> None:
        """Index ``tr`` under the outermost request id (known only
        after ``engine.add_request`` returns)."""
        if tr is None:
            return
        tr.rid = rid
        with self._lock:
            self._by_rid[rid] = tr

    def finish(self, tr: Optional[Trace], state: str, *,
               registry=None, **meta) -> None:
        """Close the root span, move the trace to the done ring, and —
        when the request missed its SLO or ended abnormally — emit the
        full tree as a ``trace`` event (the FlightRecorder ring picks
        it up, so flight dumps carry timelines).  Idempotent."""
        if tr is None or tr.finished:
            return
        tr._close(state, **meta)
        with self._lock:
            self._active.pop(tr.trace_id, None)
            if tr.rid is not None \
                    and self._by_rid.get(tr.rid) is tr:
                del self._by_rid[tr.rid]
            self._done.append(tr)
        why = self._exemplar_reason(tr, state)
        if why is not None:
            tr.meta["exemplar"] = why
            if registry is None:
                from .registry import REGISTRY as registry
            if registry.enabled:
                registry.event("trace", action="slo_exemplar",
                               reason=why, trace=tr.to_dict())

    def _exemplar_reason(self, tr: Trace, state: str) -> Optional[str]:
        if state in ("REJECTED", "TIMED_OUT"):
            return state.lower()
        if tr.meta.get("replayed"):
            return "replayed"
        if tr.meta.get("crash"):
            return "crash"
        ttft = tr.meta.get("ttft_s")
        if self.slo_ttft_s is not None and ttft is not None \
                and ttft > self.slo_ttft_s:
            return "slo_ttft"
        tpot = tr.meta.get("tpot_s")
        if self.slo_tpot_s is not None and tpot is not None \
                and tpot > self.slo_tpot_s:
            return "slo_tpot"
        return None

    # -- ambient propagation --------------------------------------------
    def current(self) -> Optional[Trace]:
        """The innermost activated trace on THIS thread (None when
        disabled or nothing is active)."""
        if not self.enabled:
            return None
        stack = self._ambient.stack
        return stack[-1] if stack else None

    @contextmanager
    def activating(self, tr: Optional[Trace]):
        """Make ``tr`` the ambient current trace for the block — the
        propagation wrapper submit/replay/re-place paths use around
        their inner ``add_request``/``adopt`` calls.  A None trace is
        a no-op (so call sites need no branching)."""
        if tr is None or not self.enabled:
            yield
            return
        stack = self._ambient.stack
        stack.append(tr)
        try:
            yield
        finally:
            stack.pop()

    # -- lookup ---------------------------------------------------------
    def lookup(self, *, rid: Optional[int] = None,
               trace_id: Optional[str] = None,
               request_id: Optional[str] = None) -> Optional[Trace]:
        """Find a live or finished trace by outer request id, trace
        id, or client request_id (newest wins in the done ring)."""
        with self._lock:
            if rid is not None:
                tr = self._by_rid.get(rid)
                if tr is not None:
                    return tr
            done = list(self._done)
            active = list(self._active.values())
        for tr in active + list(reversed(done)):
            if trace_id is not None and tr.trace_id == trace_id:
                return tr
            if rid is not None and tr.rid == rid:
                return tr
            if request_id is not None and tr.request_id == request_id:
                return tr
        return None

    def done_traces(self) -> List[Trace]:
        with self._lock:
            return list(self._done)

    # -- training twin ---------------------------------------------------
    def train_trace(self) -> Optional[Trace]:
        """The process training-loop trace (lazily created): Model.fit
        steps and ElasticTrainer reshape/recovery record here, so one
        export shows the training timeline next to serve requests."""
        if not self.enabled:
            return None
        tr = self._train
        if tr is None:
            with self._lock:
                if self._train is None:
                    self._seq += 1
                    self._train = Trace(
                        f"{os.getpid():x}-{self._seq:08x}",
                        name="training", max_spans=self.max_spans)
                tr = self._train
        return tr

    # -- engine timeline -------------------------------------------------
    def timeline(self) -> Optional[Timeline]:
        """The process engine timeline (lazily created; None when
        disabled): every scheduler — a solo engine, a fleet's replicas
        — records its iterations here.  An instrumented ``step`` calls
        this ONCE and guards its sites on the result, so the disabled
        path costs that one boolean."""
        if not self.enabled:
            return None
        tl = self._timeline
        if tl is None:
            with self._lock:
                if self._timeline is None:
                    self._timeline = Timeline()
                tl = self._timeline
        return tl


def attribution(traces: List[Trace],
                pcts: Tuple[int, ...] = (50, 95)) -> Dict[str, Any]:
    """Per-phase latency-budget attribution over finished traces.

    For each trace with a ``first_token`` mark recorded in its meta
    (``ttft_s``), split the timeline into the TTFT window
    ``[0, ttft]`` and the TPOT window ``[ttft, end]`` and sum span
    seconds per phase in each; report per-phase percentiles across
    requests plus the percentiles of UNATTRIBUTED time (the
    wall-clock the spans don't explain — scheduler slack, wire time
    outside the process)."""
    import numpy as np

    ttft_by_phase: Dict[str, List[float]] = {}
    tpot_by_phase: Dict[str, List[float]] = {}
    n = 0
    for tr in traces:
        if tr is None or not tr.finished:
            continue
        ttft = tr.meta.get("ttft_s")
        end = tr.duration_s
        if ttft is None or end is None:
            continue
        n += 1
        head = tr.phase_totals(t_lo=0.0, t_hi=ttft)
        tail = tr.phase_totals(t_lo=ttft, t_hi=end)
        head["unattributed"] = max(
            ttft - sum(v for k, v in head.items()
                       if k != "unattributed"), 0.0)
        for k, v in head.items():
            ttft_by_phase.setdefault(k, []).append(v)
        for k, v in tail.items():
            tpot_by_phase.setdefault(k, []).append(v)

    def _pct(by_phase: Dict[str, List[float]]) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for k in sorted(by_phase):
            a = np.asarray(by_phase[k], np.float64)
            out[k] = {f"p{q}": round(float(np.percentile(a, q)), 6)
                      for q in pcts}
            out[k]["sum"] = round(float(a.sum()), 6)
        return out

    return {"n_traced": n, "ttft": _pct(ttft_by_phase),
            "tpot": _pct(tpot_by_phase)}


def write_spans_jsonl(traces: List[Trace], path: str) -> str:
    """One JSON line per trace (``Trace.to_dict``) — the capture
    format ``tools/trace_report.py`` renders into a per-phase
    attribution table."""
    parent = os.path.dirname(os.path.abspath(path))
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        for tr in traces:
            if tr is not None:
                f.write(json.dumps(tr.to_dict()) + "\n")
    return path


#: process-wide tracer — disabled until a caller (bench A/B, the HTTP
#: CLI, a TelemetrySession extension, tests) enables it.  Mirrors
#: :data:`~paddle_tpu.observability.REGISTRY`.
TRACER = SpanTracer(enabled=False)
