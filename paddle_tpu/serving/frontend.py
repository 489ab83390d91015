"""Streaming serving front-end: a real request lifecycle over the
continuous-batching engine.

The engine (``inference/serving.py``) is a batch scheduler: results
appear when a request retires.  Production serving needs the opposite
shape — tokens the moment each ``engine.step()`` produces them, explicit
terminal states, deadlines, and a front door that says *no* under load
instead of queueing unboundedly.  This module adds exactly that layer,
host-side only (nothing here is traced):

Request lifecycle state machine::

    submit() ──► REJECTED                 admission control refused
       │
       ▼
    QUEUED ────► CANCELLED │ TIMED_OUT    cancel() / max_queue_time
       │
       ▼  engine schedules; prefill streams the first token
    RUNNING ───► CANCELLED │ TIMED_OUT    cancel() / deadline mid-decode
       │
       ▼
    FINISHED

* **Streaming delivery** — :meth:`ServingFrontend.submit` returns a
  :class:`RequestHandle`; iterating it yields tokens as they are
  produced.  With a ``stream_capacity`` and a background driver
  (:meth:`ServingFrontend.start`), a slow consumer backpressures the
  producer (bounded buffer, blocking push — tokens are never dropped or
  reordered); without a driver, iterating the handle drives the
  scheduler itself, so single-threaded use needs no thread at all.
* **Robust scheduling** — per-request ``deadline_s`` and
  ``max_queue_time_s`` expire requests in bounded time (a deadline hit
  mid-decode frees the engine slot and its refcounted KV pages within
  one scheduler iteration via ``engine.cancel``); ``cancel()`` works in
  both the waiting-queue and scheduled phases.
* **Admission control** — :class:`AdmissionConfig` rejects at submit
  when the waiting queue or the projected KV-block demand saturates,
  so overload degrades into fast ``REJECTED`` responses instead of
  unbounded memory growth.
* **Telemetry** — queue depth, batch occupancy, KV utilization,
  admission rejects, TTFT/per-token latency, and stream backpressure
  wait time via :class:`~paddle_tpu.serving.metrics.ServeMetrics`; the
  flight recorder (a registry sink) captures the serve event ring on
  any crash, and a driver-thread crash additionally dumps it explicitly
  and aborts every live stream so consumers never hang.
* **Speculative decoding** — construct the engine with
  ``spec_config=`` (``paddle_tpu/spec_decode``) and the front-end
  serves over the draft/verify decode loop unchanged: greedy streams
  stay bit-identical (pinned), multi-token commits arrive as ordinary
  per-step deliveries, and the ``serve.spec.*`` gauges ride
  :meth:`ServeMetrics.publish_engine`.
"""

from __future__ import annotations

import collections
import enum
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from .metrics import ServeMetrics
from ..observability.tracing import TRACER

__all__ = ["AdmissionConfig", "RequestAborted", "RequestHandle",
           "RequestRejected", "RequestState", "ServingFrontend"]


class RequestState(enum.Enum):
    """Lifecycle states; exactly one terminal state per request."""

    QUEUED = "QUEUED"
    RUNNING = "RUNNING"
    FINISHED = "FINISHED"
    CANCELLED = "CANCELLED"
    TIMED_OUT = "TIMED_OUT"
    REJECTED = "REJECTED"

    @property
    def terminal(self) -> bool:
        return self in _TERMINAL


_TERMINAL = frozenset({RequestState.FINISHED, RequestState.CANCELLED,
                       RequestState.TIMED_OUT, RequestState.REJECTED})


class RequestError(RuntimeError):
    """Base for terminal-state errors raised by handles."""


class RequestRejected(RequestError):
    """Admission control refused the request at submit."""


class RequestAborted(RequestError):
    """The request ended CANCELLED or TIMED_OUT before finishing."""

    def __init__(self, state: RequestState, reason: Optional[str]):
        super().__init__(f"request {state.value}"
                         + (f": {reason}" if reason else ""))
        self.state = state
        self.reason = reason


@dataclass(frozen=True)
class AdmissionConfig:
    """Submit-time load shedding knobs.

    max_queue_len:
        Reject when this many accepted requests are still waiting for a
        decode slot (None = unbounded queue).
    max_queue_time_s:
        Default queue-time budget for every request (overridable per
        submit); a request that waits longer is shed as TIMED_OUT.
    kv_demand_factor:
        Reject when the summed page demand of all live requests plus
        the new one would exceed ``factor * num_blocks``.  Demand beyond
        1.0x is legitimate (requests queue for pages), but unbounded
        demand is how a traffic spike turns into an unbounded queue —
        2.0 is a reasonable production default.
    """

    max_queue_len: Optional[int] = 128
    max_queue_time_s: Optional[float] = None
    kv_demand_factor: Optional[float] = None


class RequestHandle:
    """One submitted request: stream, terminal state, and timings.

    Iterate to stream tokens (raises :class:`RequestAborted` /
    :class:`RequestRejected` on abnormal terminals); call
    :meth:`result` for the engine's full ``prompt + generated`` ids.
    Token ids delivered through the stream are exactly the ids the
    batch API returns — bit-identical, pinned by tests.
    """

    def __init__(self, frontend: "ServingFrontend", prompt: np.ndarray,
                 max_new_tokens: int, stream_capacity: Optional[int],
                 submit_t: float,
                 on_token: Optional[Callable] = None):
        self._fe = frontend
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.req_id: Optional[int] = None
        self.trace = None          # the request's Trace when tracing is on
        self.submit_t = submit_t
        self.first_token_t: Optional[float] = None
        self.finish_t: Optional[float] = None
        self.reason: Optional[str] = None
        self.on_token = on_token
        self.backpressure_wait_s = 0.0
        self._cap = stream_capacity
        self._cond = threading.Condition()
        self._tokens: List[int] = []
        self._cursor = 0
        self._state = RequestState.QUEUED
        self._result: Optional[np.ndarray] = None

    # -- public surface -------------------------------------------------
    @property
    def state(self) -> RequestState:
        return self._state

    @property
    def n_streamed(self) -> int:
        return len(self._tokens)

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_t is None:
            return None
        return self.first_token_t - self.submit_t

    def tokens(self) -> List[int]:
        """Snapshot of every token streamed so far."""
        with self._cond:
            return list(self._tokens)

    def cancel(self) -> bool:
        """Abort this request (either phase).  Frees its engine slot and
        KV pages; tokens already streamed remain readable."""
        return self._fe.cancel(self)

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block (or drive the scheduler, when no driver thread runs)
        until terminal; returns the full ``prompt + generated`` ids for
        FINISHED, raises :class:`RequestRejected` / :class:`
        RequestAborted` otherwise."""
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        while True:
            with self._cond:
                # result() consumes the stream: a bounded buffer must
                # not backpressure a consumer that only wants the tail
                self._cursor = len(self._tokens)
                self._cond.notify_all()
                st = self._state
                if st is RequestState.FINISHED:
                    return self._result
                self._raise_if_aborted(st)
                if self._fe._driver_alive():
                    if deadline is not None \
                            and time.monotonic() >= deadline:
                        raise TimeoutError(
                            f"request {self.req_id} still {st.value} "
                            f"after {timeout}s")
                    self._cond.wait(0.05)
                    continue
            if deadline is not None and time.monotonic() >= deadline:
                raise TimeoutError(
                    f"request {self.req_id} still {self._state.value} "
                    f"after {timeout}s")
            self._fe.step()

    def __iter__(self) -> "RequestHandle":
        return self

    def __next__(self) -> int:
        while True:
            with self._cond:
                if self._cursor < len(self._tokens):
                    tok = self._tokens[self._cursor]
                    self._cursor += 1
                    self._cond.notify_all()    # wake a blocked producer
                    return tok
                st = self._state
                if st is RequestState.FINISHED:
                    raise StopIteration
                self._raise_if_aborted(st)
                if self._fe._driver_alive():
                    self._cond.wait(0.05)
                    continue
            # no driver thread: the consumer IS the scheduler
            self._fe.step()

    def stream_from(self, start: int = 0, *, poll_s: float = 0.05,
                    idle_cb: Optional[Callable[[], None]] = None):
        """Yield ``(index, token)`` pairs beginning at stream index
        ``start`` — the re-attachable consumer surface the HTTP/SSE wire
        is built on (``serving/http.py``): a retried connection replays
        the committed prefix from index 0 and then continues live,
        instead of double-submitting the request.

        Unlike ``__next__`` (one shared cursor), the caller owns the
        position; the shared backpressure cursor only ever advances
        (``max``), so an attached replay can never re-arm backpressure
        for tokens the producer already delivered.  Tokens are grabbed
        a chunk at a time and yielded OUTSIDE the handle lock — a slow
        socket write never blocks the delivering driver thread.

        ``idle_cb`` runs (outside the lock) roughly every ``poll_s``
        while no new token is available — the wire uses it for SSE
        heartbeats, which is also how a dead client socket is noticed
        while the stream is idle.  Terminates when the request reaches
        a terminal state (raising like ``__next__`` for abnormal
        terminals once every committed token has been yielded)."""
        i = start
        while True:
            chunk: List[int] = []
            st = None
            with self._cond:
                if i < len(self._tokens):
                    chunk = self._tokens[i:]
                    if len(self._tokens) > self._cursor:
                        self._cursor = len(self._tokens)
                        self._cond.notify_all()
                else:
                    st = self._state
                    if st is RequestState.FINISHED:
                        return
                    if st in _TERMINAL:
                        self._raise_if_aborted(st)
                    if self._fe._driver_alive():
                        self._cond.wait(poll_s)
            if chunk:
                for tok in chunk:
                    yield i, tok
                    i += 1
                continue
            if idle_cb is not None:
                idle_cb()
            if st is not None and not self._fe._driver_alive():
                # no driver thread: the consumer IS the scheduler
                self._fe.step()

    def __repr__(self) -> str:
        return (f"RequestHandle(id={self.req_id}, "
                f"state={self._state.value}, "
                f"streamed={len(self._tokens)})")

    # -- frontend-internal ----------------------------------------------
    def _raise_if_aborted(self, st: RequestState) -> None:
        if st is RequestState.REJECTED:
            raise RequestRejected(self.reason or "rejected")
        if st in (RequestState.CANCELLED, RequestState.TIMED_OUT):
            raise RequestAborted(st, self.reason)

    def _deliver_tokens(self, toks: List[int], *, block: bool,
                        timeout: float) -> float:
        """Append tokens to the stream in order.  When ``block`` (a
        driver thread is delivering), a full bounded buffer makes the
        producer WAIT for the consumer — backpressure, never dropping:
        on timeout the token is appended anyway (the buffer degrades to
        elastic rather than losing data).  Returns seconds waited."""
        waited = 0.0
        delivered: List[int] = []
        with self._cond:
            if self._state is RequestState.QUEUED:
                self._state = RequestState.RUNNING
            for t in toks:
                if block and self._cap is not None:
                    t0 = time.monotonic()
                    while (len(self._tokens) - self._cursor >= self._cap
                           and self._state is RequestState.RUNNING
                           and time.monotonic() - t0 < timeout):
                        self._cond.wait(0.02)
                    waited += time.monotonic() - t0
                if self._state is not RequestState.RUNNING:
                    break          # aborted mid-delivery: stop streaming
                self._tokens.append(t)
                delivered.append(t)
                self._cond.notify_all()
            # accumulate inside the cond: metrics_snapshot reads this
            # from whatever thread scrapes it, and += is two racy ops
            self.backpressure_wait_s += waited
        if self.on_token is not None:
            for t in delivered:
                self.on_token(self, t)
        return waited

    def _finish(self, state: RequestState, *,
                result: Optional[np.ndarray] = None,
                reason: Optional[str] = None,
                now: Optional[float] = None) -> bool:
        with self._cond:
            if self._state in _TERMINAL:
                return False
            self._state = state
            self._result = result
            self.reason = reason
            self.finish_t = now
            self._cond.notify_all()
        return True


@dataclass
class _Record:
    """Frontend-side bookkeeping for one live (non-terminal) request."""

    handle: RequestHandle
    req: object                       # engine GenRequest
    blocks: int                       # projected page demand
    deadline_t: Optional[float]
    queue_deadline_t: Optional[float]
    delivered: int = 0
    last_token_t: Optional[float] = None
    done: bool = False


@dataclass
class _Delivery:
    """Deferred handle mutation, applied OUTSIDE the scheduler lock so a
    backpressured (blocking) push can never deadlock against submit()/
    cancel() calls from consumer threads."""

    rec: _Record
    toks: List[int] = field(default_factory=list)
    state: Optional[RequestState] = None
    result: Optional[np.ndarray] = None
    reason: Optional[str] = None
    now: float = 0.0


_UNSET = object()


class ServingFrontend:
    """Request-lifecycle front door over a ``ContinuousBatchingEngine``.

    Args:
      engine: the continuous-batching engine (owned by this frontend —
        calling ``engine.step()`` elsewhere while a frontend is live
        would race the scheduler).
      admission: :class:`AdmissionConfig` load-shedding knobs.
      clock: monotonic-seconds source for deadlines/TTFT.  Injectable so
        tests and simulations control time; stream-buffer waits always
        use real ``time.monotonic``.
      default_deadline_s: deadline applied when submit passes none.
      stream_capacity: default per-handle stream buffer bound (None =
        sized by ``max_new_tokens``, i.e. no backpressure).
      backpressure_timeout_s: longest a delivery blocks on a full buffer
        before degrading to elastic buffering.
      registry: metrics registry (defaults to the process ``REGISTRY``).

    Drive it one of two ways: call :meth:`step` / :meth:`run_until_drained`
    from your own loop (deterministic, test-friendly), or
    :meth:`start` a background driver thread and consume handles from
    other threads (streaming with backpressure).
    """

    def __init__(self, engine, *, admission: Optional[AdmissionConfig] = None,
                 clock: Callable[[], float] = time.monotonic,
                 default_deadline_s: Optional[float] = None,
                 stream_capacity: Optional[int] = None,
                 backpressure_timeout_s: float = 60.0,
                 registry=None):
        self.engine = engine
        self.admission = admission or AdmissionConfig()
        self.metrics = ServeMetrics(registry)
        self.error: Optional[BaseException] = None
        self._clock = clock
        self._default_deadline = default_deadline_s
        self._cap = stream_capacity
        self._bp_timeout = backpressure_timeout_s
        self._lock = threading.RLock()
        self._recs: "collections.OrderedDict[int, _Record]" = \
            collections.OrderedDict()
        self._driver: Optional[threading.Thread] = None
        self._stop = threading.Event()

    # ------------------------------------------------------------------
    # submit / cancel
    # ------------------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens: int, *,
               eos_token_id: Optional[int] = None,
               temperature: float = 0.0, top_k: Optional[int] = None,
               top_p: Optional[float] = None, seed: int = 0, n: int = 1,
               priority: int = 0,
               deadline_s: Optional[float] = None,
               max_queue_time_s: Optional[float] = None,
               stream_capacity=_UNSET,
               on_token: Optional[Callable] = None) -> RequestHandle:
        """Admit one request.  Never raises for load reasons — an
        over-capacity submit returns a handle already in REJECTED (the
        caller's fast-fail signal); genuinely malformed requests
        (empty prompt, zero budget) still raise ``ValueError``.

        ``n > 1`` (ROADMAP 5(b)) fans the request out to n parallel
        samples sharing ONE prompt KV: every sample is an ordinary
        engine request whose prompt pages are refcount-shared through
        the cross-request prefix cache (the first sample prefills and
        registers, the rest claim the cached pages — zero new compiled
        programs, the sampler is already padded per geometry), and each
        streams on its own PRNG stream keyed (seed, sample_idx,
        absolute position) via
        :func:`~paddle_tpu.inference.serving.derive_sample_seed`.
        Returns a LIST of n handles (bit-identical to n independent
        submits carrying the derived seeds — pinned by
        tests/test_prefix_cache.py)."""
        if n < 1:
            raise ValueError("n must be >= 1")
        if n > 1:
            if temperature is None or temperature <= 0.0:
                raise ValueError(
                    "n > 1 parallel sampling needs temperature > 0 — "
                    "n greedy samples of one prompt are n identical "
                    "streams")
            from ..inference.serving import derive_sample_seed
            return [self.submit(
                prompt_ids, max_new_tokens, eos_token_id=eos_token_id,
                temperature=temperature, top_k=top_k, top_p=top_p,
                seed=derive_sample_seed(seed, i), priority=priority,
                deadline_s=deadline_s, max_queue_time_s=max_queue_time_s,
                stream_capacity=stream_capacity, on_token=on_token)
                for i in range(n)]
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        cap = self._cap if stream_capacity is _UNSET else stream_capacity
        with self._lock:
            now = self._clock()
            handle = RequestHandle(self, prompt, max_new_tokens, cap,
                                   now, on_token)
            # request tracing (ISSUE 20): open the trace here — the
            # outermost serve layer below the wire — and activate it
            # around add_request so router/supervisor/engine spans land
            # on it with no signature changes
            tr = TRACER.begin(prompt_tokens=int(len(prompt)),
                              max_new_tokens=int(max_new_tokens),
                              priority=int(priority)) \
                if TRACER.enabled else None
            reason = self._admission_reason(prompt, max_new_tokens)
            rid = None
            if reason is None:
                try:
                    with TRACER.activating(tr):
                        rid = self.engine.add_request(
                            prompt, max_new_tokens, eos_token_id,
                            temperature=temperature, top_k=top_k,
                            top_p=top_p, seed=seed, priority=priority)
                except ValueError as e:
                    if len(prompt) < 1 or max_new_tokens < 1:
                        raise                      # malformed, not load
                    reason = str(e)                # could never admit
            if reason is not None:
                handle._finish(RequestState.REJECTED, reason=reason,
                               now=now)
                self.metrics.on_reject(reason)
                if tr is not None:
                    TRACER.finish(tr, "REJECTED", reason=reason,
                                  registry=self.metrics.registry)
                return handle
            handle.req_id = rid
            if tr is not None:
                TRACER.bind(tr, rid)
                handle.trace = tr
            req = next(r for r in reversed(self.engine.queue)
                       if r.req_id == rid)
            ddl = deadline_s if deadline_s is not None \
                else self._default_deadline
            mqt = max_queue_time_s if max_queue_time_s is not None \
                else self.admission.max_queue_time_s
            self._recs[rid] = _Record(
                handle=handle, req=req,
                blocks=self.engine._blocks_needed(
                    len(prompt) + max_new_tokens),
                deadline_t=None if ddl is None else now + ddl,
                queue_deadline_t=None if mqt is None else now + mqt)
            self.metrics.on_submit(rid, len(prompt), max_new_tokens)
            self._publish()
            return handle

    def cancel(self, handle: RequestHandle,
               reason: str = "cancelled by client") -> bool:
        """Abort a live request in either phase; frees its engine slot
        and refcounted KV pages immediately.  False when already
        terminal (idempotent)."""
        with self._lock:
            rid = handle.req_id
            rec = None if rid is None else self._recs.get(rid)
            if rec is None or rec.done or handle.state.terminal:
                return False
            self.engine.cancel(rid)
            rec.done = True
            del self._recs[rid]
            now = self._clock()
            self.metrics.on_cancel(rid)
            self._publish()
        handle._finish(RequestState.CANCELLED, reason=reason, now=now)
        self._finish_trace(handle.trace, "CANCELLED", handle.n_streamed,
                           reason=reason)
        return True

    # ------------------------------------------------------------------
    # scheduler pump
    # ------------------------------------------------------------------
    def step(self) -> bool:
        """One scheduler iteration: expire deadlines, advance the
        engine, publish gauges, stream newly produced tokens.  Returns
        True while live requests remain.  With the tracer on it is one
        ``iteration`` tree on the engine timeline (and on the
        profiler's clock): docs/observability.md has the span table."""
        # the ONE tracer read of an iteration: None when it is off
        tl = TRACER.timeline()
        it = tl and tl.enter("iteration")
        live = queued = 0
        try:
            deliveries: List[_Delivery] = []
            with self._lock:
                now = self._clock()
                if tl:
                    live = len(self._recs)
                    queued = sum(1 for r in self._recs.values()
                                 if not r.req.out)
                sp = tl and tl.enter("expire")
                self._expire(now, deliveries)
                if tl:
                    tl.leave(sp, expired=len(deliveries))
                try:
                    # The scheduler lock IS the engine serialization
                    # point: step() mutates engine batch state, and
                    # every other engine touch (submit's admission,
                    # drain) already goes through _lock.  Callers never
                    # block on _lock for the step duration — they use
                    # the handle condvars.
                    finished = self.engine.step()  # locklint: disable=LK002
                except BaseException as e:
                    self._crash(e)
                    raise
                now = self._clock()
                sp = tl and tl.enter("publish")
                self._publish()
                if tl:
                    tl.leave(sp)
                sp = tl and tl.enter("deliver")
                sc = tl and tl.enter("collect")
                self._collect(finished, now, deliveries)
                pending = bool(self._recs)
                if tl:
                    tl.leave(sc)
            # the lock is released: what follows is the clients' time
            sc = tl and tl.enter("apply")
            self._apply(deliveries)
            if tl:
                tl.leave(sc)
                tl.leave(sp, tokens=sum(len(d.toks) for d in deliveries),
                         finished=len(finished))
            return pending
        finally:
            if tl:
                tl.leave(it, live=live, queued=queued)

    def _collect(self, finished, now: float,
                 deliveries: List[_Delivery]) -> None:
        """Under the scheduler lock: what each live request produced
        this iteration, as deferred deliveries (``_apply`` hands them
        over once the lock is released)."""
        for rid, rec in list(self._recs.items()):
            out = rec.req.out
            n = len(out)
            d = _Delivery(rec, now=now)
            if n > rec.delivered:
                d.toks = list(out[rec.delivered:n])
                if rec.delivered == 0:
                    rec.handle.first_token_t = now
                    self.metrics.on_first_token(
                        rid, now - rec.handle.submit_t)
                    tr = rec.handle.trace
                    if tr is not None:
                        # trace-relative TTFT: the window split
                        # attribution() cuts the timeline at
                        tr.meta["ttft_s"] = tr.now()
                        tr.event("first_token")
                    if len(d.toks) > 1:
                        self.metrics.on_tokens(len(d.toks) - 1, 0.0)
                else:
                    self.metrics.on_tokens(
                        len(d.toks),
                        (now - rec.last_token_t) / len(d.toks))
                rec.last_token_t = now
                rec.delivered = n
            if rid in finished:
                rec.done = True
                del self._recs[rid]
                d.state = RequestState.FINISHED
                d.result = finished[rid]
                self.metrics.on_finish(
                    rid, now - rec.handle.submit_t, n)
                self._finish_trace(rec.handle.trace, "FINISHED", n)
            if d.toks or d.state is not None:
                deliveries.append(d)

    def run_until_drained(self, timeout_s: Optional[float] = None) -> None:
        """Pump (or wait on the driver) until no live requests remain."""
        t0 = time.monotonic()
        while True:
            with self._lock:
                pending = bool(self._recs)
            if not pending:
                return
            if timeout_s is not None \
                    and time.monotonic() - t0 > timeout_s:
                raise TimeoutError(
                    f"requests still live after {timeout_s}s")
            if self._driver_alive():
                time.sleep(0.01)
            else:
                self.step()

    # ------------------------------------------------------------------
    # background driver
    # ------------------------------------------------------------------
    def start(self) -> "ServingFrontend":
        """Run the scheduler on a daemon thread; handles then stream
        with real backpressure.  Idempotent."""
        with self._lock:
            if self._driver is not None and self._driver.is_alive():
                return self
            self._stop.clear()
            self._driver = threading.Thread(
                target=self._drive, name="serving-frontend", daemon=True)
            self._driver.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        t = self._driver
        if t is not None and t is not threading.current_thread():
            t.join(timeout=30.0)
        self._driver = None

    def close(self, cancel_pending: bool = True) -> None:
        """Stop the driver and (by default) abort anything still live,
        so no consumer blocks forever on a dead frontend."""
        self.stop()
        if cancel_pending:
            with self._lock:
                handles = [r.handle for r in self._recs.values()]
            for h in handles:
                self.cancel(h, reason="frontend closed")

    def _driver_alive(self) -> bool:
        t = self._driver
        return (t is not None and t.is_alive()
                and t is not threading.current_thread())

    def _drive(self) -> None:
        try:
            while not self._stop.is_set():
                with self._lock:
                    pending = bool(self._recs)
                if pending:
                    self.step()
                else:
                    self._stop.wait(0.002)
        except BaseException as e:
            # engine failures already ran _crash() inside step(); any
            # other failure (delivery callback, expiry logic) must
            # still abort live streams so consumers don't hang
            if self.error is None:
                self._crash(e)
            return

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------
    def _admission_reason(self, prompt: np.ndarray,
                          max_new_tokens: int) -> Optional[str]:
        adm = self.admission
        if adm.max_queue_len is not None:
            waiting = sum(1 for r in self._recs.values()
                          if len(r.req.out) == 0)
            if waiting >= adm.max_queue_len:
                return (f"queue full: {waiting} waiting >= "
                        f"max_queue_len={adm.max_queue_len}")
        if adm.kv_demand_factor is not None:
            need = self.engine._blocks_needed(
                len(prompt) + max_new_tokens)
            outstanding = sum(r.blocks for r in self._recs.values())
            cap = adm.kv_demand_factor * self.engine.alloc.num_blocks
            if outstanding + need > cap:
                return (f"kv pool saturated: demand {outstanding}+{need} "
                        f"blocks > {adm.kv_demand_factor:g}x pool "
                        f"({self.engine.alloc.num_blocks})")
        return None

    def _expire(self, now: float, deliveries: List[_Delivery]) -> None:
        """Shed queue-time and deadline violators BEFORE the engine
        step, so an expired request never occupies (or takes) a slot
        this iteration — expiry-to-free latency is bounded by one
        scheduler iteration."""
        for rid, rec in list(self._recs.items()):
            phase = None
            if rec.deadline_t is not None and now >= rec.deadline_t:
                phase = "deadline"
            elif (rec.queue_deadline_t is not None
                  and now >= rec.queue_deadline_t
                  and len(rec.req.out) == 0):
                phase = "max_queue_time"
            if phase is None:
                continue
            self.engine.cancel(rid)
            rec.done = True
            del self._recs[rid]
            toks = list(rec.req.out[rec.delivered:])
            rec.delivered = len(rec.req.out)
            deliveries.append(_Delivery(
                rec, toks=toks, state=RequestState.TIMED_OUT,
                reason=phase, now=now))
            self.metrics.on_timeout(rid, phase)
            self._finish_trace(rec.handle.trace, "TIMED_OUT",
                               len(rec.req.out), reason=phase)

    def _apply(self, deliveries: List[_Delivery]) -> None:
        block = threading.current_thread() is self._driver
        for d in deliveries:
            h = d.rec.handle
            if d.toks:
                waited = h._deliver_tokens(d.toks, block=block,
                                           timeout=self._bp_timeout)
                if waited > 0.0:
                    self.metrics.on_backpressure(waited)
            if d.state is not None:
                h._finish(d.state, result=d.result, reason=d.reason,
                          now=d.now)

    def _publish(self) -> None:
        self.metrics.publish_engine(self.engine)

    def _finish_trace(self, tr, state: str, n_tokens: int = 0, *,
                      reason: Optional[str] = None, **meta) -> None:
        """Close a request trace on its terminal state: stamp the token
        count and derived TPOT (decode seconds per post-first token),
        then hand it to the tracer — which emits the span tree as a
        ``trace`` exemplar event (FlightRecorder-visible) when the
        request missed its SLO or ended abnormally."""
        if tr is None:
            return
        ttft = tr.meta.get("ttft_s")
        if ttft is not None and n_tokens > 1:
            meta.setdefault("tpot_s",
                            (tr.now() - ttft) / (n_tokens - 1))
        if reason is not None:
            meta.setdefault("reason", reason)
        meta.setdefault("n_tokens", int(n_tokens))
        TRACER.finish(tr, state, registry=self.metrics.registry, **meta)

    def _crash(self, exc: BaseException) -> None:
        """Engine-step failure: record, dump the serve ring for
        post-mortem, and abort every live stream so consumers get a
        terminal state instead of hanging."""
        with self._lock:       # re-entrant from step(); health_snapshot
            self.error = exc   # reads error from other threads
        self.metrics.event("crash",
                           error=f"{type(exc).__name__}: {exc}")
        with self._lock:
            recs = list(self._recs.values())
            self._recs.clear()
        # close live traces FIRST: their span trees ride the ring as
        # ``trace`` exemplar events, so the dump below is a post-mortem
        # with timelines, not just counters
        for rec in recs:
            self._finish_trace(
                rec.handle.trace, "CANCELLED", len(rec.req.out),
                reason=f"frontend crashed: {type(exc).__name__}: {exc}",
                crash=True)
        try:
            from ..observability.flight_recorder import FlightRecorder
            for sink in self.metrics.registry.sinks:
                if isinstance(sink, FlightRecorder) \
                        and sink.directory is not None:
                    sink.dump(f"serving-frontend crash: "
                              f"{type(exc).__name__}: {exc}")
        except Exception as dump_err:   # the dump must not mask exc
            self.metrics.event("crash_dump_failed", error=str(dump_err))
        now = self._clock()
        for rec in recs:
            rec.done = True
            rec.handle._finish(
                RequestState.CANCELLED,
                reason=f"frontend crashed: {type(exc).__name__}: {exc}",
                now=now)

    # -- introspection --------------------------------------------------
    @property
    def live_requests(self) -> int:
        return len(self._recs)

    def __enter__(self) -> "ServingFrontend":
        return self

    def __exit__(self, *exc_info) -> None:
        self.close()
