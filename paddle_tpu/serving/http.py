"""Network front door (ISSUE 13): a stdlib-only HTTP/SSE serving
endpoint over :class:`~paddle_tpu.serving.frontend.ServingFrontend`.

PRs 11-12 made everything *behind* the front-end fault-tolerant
(supervised recovery, fleet re-placement, graceful drain); this module
puts a robust wire on that resilient core.  The network edge is where
real traffic's failures actually originate — clients disconnect
mid-stream, readers stall, requests are retried after ambiguous
errors, and the process is restarted under load — so every one of
those is a first-class, metered, tested path here, not an accident:

* **Client-disconnect propagation** — a broken/closed socket
  mid-stream cancels the request through the existing
  ``frontend.cancel`` → ``engine.cancel`` path, freeing the decode
  slot and its refcounted KV pages within one scheduler iteration of
  detection (detection itself is bounded by the SSE heartbeat cadence:
  an idle stream still writes ``:`` comment frames, so a dead socket
  surfaces even between tokens).  Disconnect storms drain at zero
  leaked KV blocks — pinned by tests/test_serving_http.py.
* **Slow-client isolation** — each connection carries a write deadline
  (``io_timeout_s`` on the socket); a stalled reader (zero TCP window)
  times the *handler thread's* write out and is cancelled, while the
  frontend's bounded ``stream_capacity`` / ``backpressure_timeout_s``
  machinery keeps the *driver thread* delivering to batchmates — one
  stalled reader never blocks the scheduler or its batch.
* **Idempotent retry** — a client-supplied ``request_id`` enters a
  dedup window: a retry after a timed-out/ambiguous response attaches
  to the live stream, replaying already-streamed tokens from the
  committed prefix (``RequestHandle.stream_from``) instead of
  double-submitting.  A disconnect on an identified request keeps it
  generating for ``retry_grace_s`` so the retry finds a live stream;
  only an un-retried grace expiry cancels.
* **Graceful shutdown** — SIGTERM (or :meth:`HttpServingServer.
  begin_shutdown`) flips ``/readyz`` to 503, answers new work with
  503 + ``Retry-After``, drains in-flight streams, then tears down and
  returns a zero-leak report (``kv_leak_report`` must show zero).
* **Typed status mapping** — every terminal state the resilience
  stack can produce has exactly one wire representation:

  =============================  =====================================
  lattice state                  HTTP
  =============================  =====================================
  REJECTED (queue/KV saturated)  429 + ``Retry-After``
  REJECTED (fleet exhausted /    503 + ``Retry-After``
  no live replica)
  TIMED_OUT, ``deadline``        408
  TIMED_OUT, ``max_queue_time``  503 + ``Retry-After`` (load shedding)
  CANCELLED                      499 (client closed request)
  malformed request              400
  draining (shutdown)            503 + ``Retry-After``
  FINISHED                       200
  =============================  =====================================

  Mid-SSE, terminals arrive as a final ``done`` / ``error`` event
  carrying the same ``code`` — the stream is already 200 by then.

Endpoints (``docs/serving.md`` has the full wire contract):

  ``POST /v1/generate``   SSE token stream (default) or blocking JSON
  ``POST /v1/cancel``     cancel by client ``request_id`` / server id
  ``GET  /healthz``       process liveness (200 while serving)
  ``GET  /readyz``        placement readiness — fleet ``placeable()``
  ``GET  /metrics``       Prometheus text (``write_prometheus`` format)

Everything here is host-side connection plumbing on stdlib
``http.server`` — no new dependencies, nothing traced, and an AOT-warm
engine behind it serves traffic at ZERO backend compiles
(``serve_http_warm`` budget row).

Quickstart::

    python -m paddle_tpu.serving.http --model llama_tiny --port 8821

    curl -N -X POST localhost:8821/v1/generate \\
        -d '{"prompt_ids": [3, 14, 15], "max_new_tokens": 8}'
"""

from __future__ import annotations

import collections
import http.server
import json
import signal
import socket
import socketserver
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

from .fleet import FleetExhaustedError
from .frontend import (RequestAborted, RequestHandle, RequestRejected,
                       RequestState, ServingFrontend)
from .metrics import ServeMetrics

__all__ = ["HttpServingServer", "HttpTransport", "WireHandle",
           "iter_sse", "parse_args", "build_frontend", "main"]


# ---------------------------------------------------------------------
# wire-facing request/status helpers
# ---------------------------------------------------------------------
class _BadRequest(ValueError):
    """Malformed wire request — maps to 400 with a reason body."""


def _parse_generate(body: Dict[str, Any]) -> Dict[str, Any]:
    """Validate a /v1/generate body into frontend.submit kwargs.
    Anything malformed raises :class:`_BadRequest` (→ 400); load
    problems are NOT decided here — admission does that."""
    if not isinstance(body, dict):
        raise _BadRequest("body must be a JSON object")
    ids = body.get("prompt_ids")
    if not isinstance(ids, list) or not ids \
            or not all(isinstance(t, int) and not isinstance(t, bool)
                       for t in ids):
        raise _BadRequest("prompt_ids must be a non-empty list of ints")
    mnt = body.get("max_new_tokens")
    if not isinstance(mnt, int) or isinstance(mnt, bool) or mnt < 1:
        raise _BadRequest("max_new_tokens must be an int >= 1")
    out: Dict[str, Any] = {"prompt_ids": np.asarray(ids, np.int32),
                           "max_new_tokens": mnt}
    for key, typ in (("eos_token_id", int), ("top_k", int), ("seed", int),
                     ("priority", int), ("temperature", (int, float)),
                     ("top_p", (int, float)),
                     ("deadline_s", (int, float)),
                     ("max_queue_time_s", (int, float))):
        v = body.get(key)
        if v is None:
            continue
        if not isinstance(v, typ) or isinstance(v, bool):
            raise _BadRequest(f"{key} must be {typ}")
        out[key] = v
    rid = body.get("request_id")
    if rid is not None and (not isinstance(rid, str) or not rid
                            or len(rid) > 200):
        raise _BadRequest("request_id must be a non-empty string "
                          "(<= 200 chars)")
    stream = body.get("stream", True)
    if not isinstance(stream, bool):
        raise _BadRequest("stream must be a bool")
    return out


def _reject_status(reason: str) -> int:
    """REJECTED reason → status: capacity the caller should back off
    from is 429; a fleet with nowhere to place anything is 503."""
    r = (reason or "").lower()
    if "no live replica" in r or "fleet" in r or "dead" in r:
        return 503
    return 429


def _terminal_code(state: RequestState, reason: Optional[str]) -> int:
    """The one wire code for each abnormal terminal lattice state."""
    if state is RequestState.TIMED_OUT:
        return 408 if reason == "deadline" else 503
    if state is RequestState.CANCELLED:
        return 499
    if state is RequestState.REJECTED:
        return _reject_status(reason or "")
    return 200


@dataclass
class _Tracked:
    """Server bookkeeping for one submitted handle: the dedup/attach
    window entry (keyed by client request_id when given, and always by
    server req_id for /v1/cancel)."""

    handle: RequestHandle
    request_id: Optional[str]
    expires_t: float                 # drop from the window after this
    consumers: int = 0               # connections currently streaming
    grace_t: Optional[float] = None  # disconnected: cancel at this time


class _Server(socketserver.ThreadingMixIn, http.server.HTTPServer):
    daemon_threads = True
    # a FIN mid-accept-queue must not take the listener down
    allow_reuse_address = True
    owner: "HttpServingServer"

    def handle_error(self, request, client_address):
        # stdlib default prints a traceback for every client that goes
        # away mid-handshake; connection aborts are business as usual
        # for a front door — account them instead of spamming stderr.
        # Anything that is NOT a connection fault is a real bug: keep
        # the stdlib traceback so it never disappears silently.
        import sys
        exc = sys.exc_info()[1]
        self.owner._on_handler_error(client_address, exc)
        if not isinstance(exc, (BrokenPipeError, ConnectionError,
                                socket.timeout, TimeoutError)):
            super().handle_error(request, client_address)


class HttpServingServer:
    """HTTP/SSE front door over a :class:`ServingFrontend`.

    Args:
      frontend: the front-end to serve (its engine may be a bare
        ``ContinuousBatchingEngine``, a ``SupervisedEngine``, or an
        ``EngineRouter`` fleet — ``/readyz`` adapts).  The server owns
        driving it: :meth:`start` launches the frontend's background
        driver thread.
      host / port: bind address; ``port=0`` picks an ephemeral port
        (read it back from ``server.port``).
      io_timeout_s: per-connection socket deadline, both directions —
        a stalled reader's SSE write (or a slowloris header read) times
        out and the connection is torn down.
      heartbeat_s: idle SSE streams emit a ``:`` comment frame this
        often; it is also the disconnect-detection cadence while no
        token is flowing.
      heartbeat_pad_bytes: padding appended to heartbeat comments
        (anti-buffering padding for proxies; the stalled-reader chaos
        tests use it to fill kernel socket buffers deterministically).
      event_pad_bytes: padding inside every ``token`` event's JSON
        (same proxy-buster purpose; same chaos use — makes a stalled
        reader's TCP window fill within a bounded token count).
      dedup_window_s: how long a client ``request_id`` stays
        attachable after its stream finishes (idempotent-retry window).
      retry_grace_s: how long an identified request keeps generating
        after its consumer disconnects, waiting for a retry to attach;
        expiry cancels it (an anonymous disconnect cancels at once).
      drain_timeout_s: default graceful-shutdown drain budget.
      retry_after_s: the ``Retry-After`` header value on 429/503.
      sndbuf_bytes: optional SO_SNDBUF override on accepted sockets
        (chaos tests shrink it so a stalled reader back-pressures the
        writer within the test's patience).
      registry: metrics registry (defaults to the process registry via
        :class:`ServeMetrics`).
    """

    def __init__(self, frontend: ServingFrontend, *,
                 host: str = "127.0.0.1", port: int = 0,
                 io_timeout_s: float = 20.0,
                 heartbeat_s: float = 0.5,
                 heartbeat_pad_bytes: int = 0,
                 event_pad_bytes: int = 0,
                 dedup_window_s: float = 30.0,
                 retry_grace_s: float = 2.0,
                 drain_timeout_s: float = 30.0,
                 retry_after_s: float = 1.0,
                 sndbuf_bytes: Optional[int] = None,
                 registry=None):
        self.frontend = frontend
        self.metrics = ServeMetrics(registry) if registry is not None \
            else frontend.metrics
        self.io_timeout_s = float(io_timeout_s)
        self.heartbeat_s = float(heartbeat_s)
        self.heartbeat_pad_bytes = int(heartbeat_pad_bytes)
        self.event_pad_bytes = int(event_pad_bytes)
        self.dedup_window_s = float(dedup_window_s)
        self.retry_grace_s = float(retry_grace_s)
        self.drain_timeout_s = float(drain_timeout_s)
        self.retry_after_s = float(retry_after_s)
        self.sndbuf_bytes = sndbuf_bytes
        self._lock = threading.RLock()
        self._by_request_id: Dict[str, _Tracked] = {}
        self._by_rid: "collections.OrderedDict[int, _Tracked]" = \
            collections.OrderedDict()
        self._active = 0
        self._aborted_conns = 0
        self._draining = False
        self._drain_report: Optional[Dict[str, Any]] = None
        self._drain_done = threading.Event()
        self._stop_housekeeper = threading.Event()
        self._housekeeper: Optional[threading.Thread] = None
        self._serve_thread: Optional[threading.Thread] = None
        self._httpd = _Server((host, port), _RequestHandler)
        self._httpd.owner = self

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    @property
    def host(self) -> str:
        return self._httpd.server_address[0]

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    @property
    def draining(self) -> bool:
        return self._draining

    def start(self) -> "HttpServingServer":
        """Start the frontend driver, the accept loop, and the
        housekeeper.  Idempotent."""
        self.frontend.start()
        if self._serve_thread is None or not self._serve_thread.is_alive():
            self._serve_thread = threading.Thread(
                target=self._httpd.serve_forever,
                kwargs={"poll_interval": 0.05},
                name="serving-http-accept", daemon=True)
            self._serve_thread.start()
        if self._housekeeper is None or not self._housekeeper.is_alive():
            self._stop_housekeeper.clear()
            self._housekeeper = threading.Thread(
                target=self._housekeep, name="serving-http-housekeeper",
                daemon=True)
            self._housekeeper.start()
        return self

    def install_signal_handlers(self) -> None:
        """SIGTERM/SIGINT → graceful shutdown (main thread only — the
        CLI path).  The handler returns immediately; the drain runs on
        a background thread so the signal context stays trivial."""
        def _on_signal(signum, frame):
            # Deliberate fire-and-forget: the signal context must return
            # immediately and nothing can wait on this thread — the
            # drain itself signals completion via _drain_done.
            threading.Thread(  # locklint: disable=LK006
                target=self.begin_shutdown,
                kwargs={"reason": signal.Signals(signum).name},
                name="serving-http-shutdown", daemon=True).start()

        signal.signal(signal.SIGTERM, _on_signal)
        signal.signal(signal.SIGINT, _on_signal)

    def begin_shutdown(self, *, drain_timeout_s: Optional[float] = None,
                       reason: str = "shutdown"
                       ) -> Dict[str, Any]:
        """Graceful shutdown: stop taking new work (503 + Retry-After,
        ``/readyz`` 503), drain in-flight streams through the frontend,
        cancel whatever outlives the drain budget, tear down, and
        return the zero-leak report.  Idempotent — concurrent callers
        all get the same report."""
        with self._lock:
            already = self._draining
            self._draining = True
        if already:
            self._drain_done.wait()
            return dict(self._drain_report or {})
        budget = self.drain_timeout_s if drain_timeout_s is None \
            else float(drain_timeout_s)
        t0 = time.monotonic()
        self.metrics.event("http_shutdown_begin", reason=reason)
        drained_clean = True
        while self.frontend.live_requests > 0:
            if time.monotonic() - t0 > budget:
                drained_clean = False
                break
            time.sleep(0.01)
        cancelled = 0
        if not drained_clean:
            with self._lock:
                stragglers = [t.handle for t in self._by_rid.values()
                              if not t.handle.state.terminal]
            for h in stragglers:
                if self.frontend.cancel(
                        h, reason="shutdown drain deadline"):
                    cancelled += 1
        # give connection threads a moment to flush terminal events
        conn_t0 = time.monotonic()
        while self._active > 0 and time.monotonic() - conn_t0 < 5.0:
            time.sleep(0.01)
        self._stop_housekeeper.set()
        self._httpd.shutdown()
        # shutdown() returns once serve_forever exits; join the worker
        # threads so close() never returns with live threads behind it
        # (current-thread guard: begin_shutdown may run ON them)
        if self._serve_thread is not None \
                and self._serve_thread is not threading.current_thread():
            self._serve_thread.join(timeout=5.0)
        if self._housekeeper is not None \
                and self._housekeeper is not threading.current_thread():
            self._housekeeper.join(timeout=5.0)
        self.frontend.close(cancel_pending=True)
        leak = self.frontend.engine.kv_leak_report()
        drain_secs = time.monotonic() - t0
        with self._lock:
            drained = len([t for t in self._by_rid.values()
                           if t.handle.state is RequestState.FINISHED])
        report = {
            "reason": reason,
            "drain_secs": round(drain_secs, 4),
            "drained_within_budget": drained_clean,
            "finished_total": drained,
            "cancelled_at_deadline": cancelled,
            "kv_leak_report": leak,
            "kv_leaked_blocks": leak["leaked"] + leak["unaccounted"],
        }
        self.metrics.on_shutdown_drain(drain_secs, drained, cancelled)
        with self._lock:   # concurrent callers read it after the event
            self._drain_report = report
        self._drain_done.set()
        return dict(report)

    def close(self) -> Dict[str, Any]:
        """Graceful shutdown + full teardown (the context-manager
        exit); returns the drain report."""
        report = self.begin_shutdown(reason="close")
        self._httpd.server_close()
        return report

    def __enter__(self) -> "HttpServingServer":
        return self.start()

    def __exit__(self, *exc_info) -> None:
        self.close()

    def chaos(self, fn):
        """Run ``fn(frontend.engine)`` under the frontend's scheduler
        lock — the ops/chaos entry point for fleet surgery
        (``kill_replica``, ``drain``) while the driver thread is
        pumping.  Returns ``fn``'s result."""
        with self.frontend._lock:
            return fn(self.frontend.engine)

    # ------------------------------------------------------------------
    # ready / health
    # ------------------------------------------------------------------
    def ready(self) -> Dict[str, Any]:
        """The /readyz payload: ready iff not draining, the frontend is
        alive, and (for a fleet) at least one replica is placeable."""
        from .resilience import ResilienceError
        engine_reason = None
        try:
            placeable = getattr(self.frontend.engine, "placeable", None)
            census = getattr(self.frontend.engine, "health_census", None)
            ok_place = placeable() if callable(placeable) else True
            census_val = census() if callable(census) else None
        except ResilienceError as e:
            # a dead supervisor / exhausted fleet answers every engine-
            # surface access with its typed error — that IS not-ready
            ok_place, census_val = False, None
            engine_reason = f"{type(e).__name__}: {e}"
        ok = (not self._draining and self.frontend.error is None
              and ok_place)
        out: Dict[str, Any] = {"ready": bool(ok)}
        if self._draining:
            out["reason"] = "draining"
        elif self.frontend.error is not None:
            out["reason"] = ("frontend crashed: "
                             f"{type(self.frontend.error).__name__}")
        elif not ok:
            out["reason"] = engine_reason or "no placeable replica"
        if census_val is not None:
            out["health_census"] = census_val
        return out

    # ------------------------------------------------------------------
    # submit / attach / cancel (handler-thread entry points)
    # ------------------------------------------------------------------
    def submit_or_attach(self, kwargs: Dict[str, Any],
                         request_id: Optional[str]):
        """Submit a new request, or attach to the live/terminal stream
        a previous submit with the same ``request_id`` created.
        Returns ``(tracked, dedup_hit)``."""
        with self._lock:
            if request_id is not None:
                t = self._by_request_id.get(request_id)
                if t is not None:
                    t.grace_t = None          # a consumer is (re)attached
                    t.consumers += 1
                    t.expires_t = time.monotonic() + self.dedup_window_s
                    self.metrics.on_dedup_hit(
                        request_id, live=not t.handle.state.terminal)
                    return t, True
            handle = self.frontend.submit(**kwargs)
            if handle.trace is not None and request_id is not None:
                # index the trace under the CLIENT id too, so
                # GET /v1/trace/<request_id> resolves either id space
                handle.trace.request_id = request_id
            t = _Tracked(handle=handle, request_id=request_id,
                         expires_t=time.monotonic() + self.dedup_window_s,
                         consumers=1)
            # a REJECTED submit never enters the window: a retry after
            # 429/503 + Retry-After SHOULD be a fresh admission attempt,
            # not a replay of the rejection
            if handle.state is not RequestState.REJECTED:
                if request_id is not None:
                    self._by_request_id[request_id] = t
                if handle.req_id is not None:
                    self._by_rid[handle.req_id] = t
            return t, False

    def release(self, t: _Tracked, *, disconnected: bool) -> None:
        """A consumer detached from ``t``'s stream.  A clean detach on
        a terminal handle just drops the refcount; a disconnect on a
        live identified request arms the retry grace timer, and on an
        anonymous request cancels immediately (slot + KV pages free
        within one scheduler iteration)."""
        cancel = False
        with self._lock:
            t.consumers = max(t.consumers - 1, 0)
            if disconnected and not t.handle.state.terminal \
                    and t.consumers == 0:
                if t.request_id is not None and self.retry_grace_s > 0:
                    t.grace_t = time.monotonic() + self.retry_grace_s
                else:
                    cancel = True
        if cancel:
            n = t.handle.n_streamed
            if self.frontend.cancel(t.handle,
                                    reason="client disconnected"):
                self.metrics.on_disconnect_cancel(t.handle.req_id, n)

    def cancel_request(self, *, request_id: Optional[str] = None,
                       req_id: Optional[int] = None) -> Dict[str, Any]:
        """/v1/cancel body → result.  Looks up by client request_id
        first, then by server req_id."""
        with self._lock:
            t = None
            if request_id is not None:
                t = self._by_request_id.get(request_id)
            if t is None and req_id is not None:
                t = self._by_rid.get(req_id)
        if t is None:
            return {"cancelled": False, "found": False}
        ok = self.frontend.cancel(t.handle, reason="cancelled by client")
        return {"cancelled": bool(ok), "found": True,
                "state": t.handle.state.value,
                "req_id": t.handle.req_id}

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------
    def _conn_opened(self) -> None:
        with self._lock:
            self._active += 1
            n = self._active
        self.metrics.on_connection(n, opened=True)

    def _conn_closed(self) -> None:
        with self._lock:
            self._active = max(self._active - 1, 0)
            n = self._active
        self.metrics.on_connection(n, opened=False)

    def _on_handler_error(self, client_address,
                          exc: Optional[BaseException] = None) -> None:
        with self._lock:
            self._aborted_conns += 1
        self.metrics.event("http_connection_aborted",
                           peer=str(client_address),
                           error=(f"{type(exc).__name__}: {exc}"[:200]
                                  if exc is not None else "unknown"))

    def _housekeep(self) -> None:
        """Expire retry-grace timers (cancel abandoned disconnected
        requests) and prune the dedup window."""
        while not self._stop_housekeeper.wait(0.02):
            now = time.monotonic()
            to_cancel: List[_Tracked] = []
            with self._lock:
                for t in list(self._by_request_id.values()):
                    if t.grace_t is not None and now >= t.grace_t \
                            and not t.handle.state.terminal:
                        t.grace_t = None
                        to_cancel.append(t)
                for key, t in list(self._by_request_id.items()):
                    if now >= t.expires_t and t.consumers == 0 \
                            and t.handle.state.terminal:
                        del self._by_request_id[key]
                for rid, t in list(self._by_rid.items()):
                    if now >= t.expires_t and t.consumers == 0 \
                            and t.handle.state.terminal:
                        del self._by_rid[rid]
            for t in to_cancel:
                if self.frontend.cancel(
                        t.handle,
                        reason="client disconnected (retry grace "
                               "expired)"):
                    self.metrics.on_abandoned(t.request_id or "")
                    self.metrics.on_disconnect_cancel(
                        t.handle.req_id, t.handle.n_streamed)


# ---------------------------------------------------------------------
# the request handler
# ---------------------------------------------------------------------
class _RequestHandler(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    server_version = "paddle-tpu-serve/1.0"

    @property
    def srv(self) -> HttpServingServer:
        return self.server.owner

    # quiet the default per-request stderr logging; the metric/event
    # stream is the log
    def log_message(self, fmt, *args):
        pass

    def setup(self):
        owner = self.server.owner
        self.timeout = owner.io_timeout_s
        super().setup()
        if owner.sndbuf_bytes is not None:
            self.connection.setsockopt(socket.SOL_SOCKET,
                                       socket.SO_SNDBUF,
                                       int(owner.sndbuf_bytes))
        owner._conn_opened()

    def finish(self):
        try:
            super().finish()
        finally:
            self.server.owner._conn_closed()

    # -- plumbing -------------------------------------------------------
    def _send_json(self, code: int, payload: Dict[str, Any],
                   headers: Optional[Dict[str, str]] = None) -> None:
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)
        self.wfile.flush()

    def _retry_after(self) -> Dict[str, str]:
        return {"Retry-After": f"{self.srv.retry_after_s:g}"}

    def _read_body(self) -> Dict[str, Any]:
        n = int(self.headers.get("Content-Length") or 0)
        if n <= 0:
            raise _BadRequest("missing request body")
        if n > 10 * 1024 * 1024:
            raise _BadRequest("request body too large")
        raw = self.rfile.read(n)
        try:
            return json.loads(raw)
        except (ValueError, UnicodeDecodeError) as e:
            raise _BadRequest(f"invalid JSON body: {e}") from e

    # -- GET ------------------------------------------------------------
    def do_GET(self):
        if self.path == "/healthz":
            self._send_json(200, {
                "status": "ok",
                "live_requests": self.srv.frontend.live_requests,
                "draining": self.srv.draining})
        elif self.path == "/readyz":
            payload = self.srv.ready()
            self._send_json(200 if payload["ready"] else 503, payload,
                            None if payload["ready"]
                            else self._retry_after())
        elif self.path == "/metrics":
            # publish-on-scrape: the engine gauges (kv_utilization,
            # queue_depth, fleet census) are otherwise only fresh when
            # a frontend step happens to run _publish — an idle server
            # would serve Prometheus stale zeros forever
            from .resilience import ResilienceError
            fe = self.srv.frontend
            try:
                with fe._lock:
                    fe._publish()
            except ResilienceError:
                # dead engine surface: scrape whatever gauges exist —
                # the crash counters are the signal Prometheus needs
                pass
            text = self.srv.metrics.registry.prometheus_text().encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(text)))
            self.end_headers()
            self.wfile.write(text)
            self.wfile.flush()
        elif self.path.startswith("/v1/trace/"):
            self._trace_debug(self.path[len("/v1/trace/"):])
        else:
            self._send_json(404, {"error": f"unknown path {self.path}"})

    def _trace_debug(self, key: str) -> None:
        """``GET /v1/trace/<key>``: one request's span tree (live or
        from the finished ring) as JSON — ``key`` is the server req_id,
        the client request_id, or the trace_id (tried in that order);
        ``/v1/trace/engine`` is the scheduler's own timeline, unless
        a request carries that id."""
        from ..observability.tracing import TRACER
        if not TRACER.enabled:
            self._send_json(404, {
                "error": "tracing is disabled (enable "
                         "paddle_tpu.observability.TRACER)"})
            return
        tr = None
        if key.isdigit():
            tr = TRACER.lookup(rid=int(key))
        if tr is None:
            tr = TRACER.lookup(request_id=key)
        if tr is None:
            tr = TRACER.lookup(trace_id=key)
        if tr is None and key == "engine":
            # no request is called that: the engine timeline's newest
            # iterations (ISSUE 27)
            self._send_json(200, TRACER.timeline().to_dict(last=256))
            return
        if tr is None:
            self._send_json(404, {"error": f"no trace for {key!r}"})
            return
        self._send_json(200, tr.to_dict())

    # -- POST -----------------------------------------------------------
    def do_POST(self):
        try:
            if self.path == "/v1/generate":
                self._generate()
            elif self.path == "/v1/cancel":
                body = self._read_body()
                rid = body.get("request_id")
                num = body.get("req_id")
                if rid is None and num is None:
                    raise _BadRequest(
                        "cancel needs request_id or req_id")
                self._send_json(200, self.srv.cancel_request(
                    request_id=rid, req_id=num))
            else:
                self._send_json(404,
                                {"error": f"unknown path {self.path}"})
        except _BadRequest as e:
            self._send_json(400, {"error": str(e)})
        except FleetExhaustedError as e:
            self._send_json(503, {"error": str(e)},
                            self._retry_after())
        except (BrokenPipeError, ConnectionResetError, socket.timeout):
            # response path died with the client; the generate handler
            # already routed the request through release(disconnected)
            self.close_connection = True

    def _generate(self) -> None:
        srv = self.srv
        body = self._read_body()
        kwargs = _parse_generate(body)
        request_id = body.get("request_id")
        stream = body.get("stream", True)
        srv.metrics.on_http_request()
        if srv.draining:
            self._send_json(
                503, {"error": "server is draining (shutdown in "
                               "progress)", "state": "DRAINING"},
                self._retry_after())
            return
        try:
            tracked, dedup = srv.submit_or_attach(kwargs, request_id)
        except ValueError as e:
            # the frontend raises ValueError only for malformed
            # requests (load problems come back as REJECTED handles)
            self._send_json(400, {"error": str(e)})
            return
        handle = tracked.handle
        if handle.state is RequestState.REJECTED:
            code = _reject_status(handle.reason or "")
            self._send_json(code, {"state": "REJECTED",
                                   "error": handle.reason},
                            self._retry_after())
            srv.release(tracked, disconnected=False)
            return
        if stream:
            self._stream_sse(tracked, dedup)
        else:
            self._blocking_json(tracked)

    @staticmethod
    def _with_trace(handle: RequestHandle,
                    payload: Dict[str, Any]) -> Dict[str, Any]:
        tr = getattr(handle, "trace", None)
        if tr is not None:
            payload["trace_id"] = tr.trace_id
        return payload

    # -- blocking JSON mode ---------------------------------------------
    def _blocking_json(self, tracked: _Tracked) -> None:
        srv = self.srv
        handle = tracked.handle
        try:
            try:
                result = handle.result()
                payload = self._with_trace(handle, {
                    "state": "FINISHED",
                    "req_id": handle.req_id,
                    "tokens": handle.tokens(),
                    "ids": np.asarray(result).tolist()})
                self._send_json(200, payload)
            except RequestRejected:
                self._send_json(_reject_status(handle.reason or ""),
                                self._with_trace(handle, {
                                    "state": "REJECTED",
                                    "error": handle.reason}),
                                self._retry_after())
            except RequestAborted as e:
                code = _terminal_code(e.state, handle.reason)
                hdrs = self._retry_after() if code == 503 else None
                self._send_json(code,
                                self._with_trace(handle, {
                                    "state": e.state.value,
                                    "req_id": handle.req_id,
                                    "reason": handle.reason,
                                    "tokens": handle.tokens()}),
                                hdrs)
        except (BrokenPipeError, ConnectionResetError,
                socket.timeout, OSError):
            srv.release(tracked, disconnected=True)
            self.close_connection = True
            return
        srv.release(tracked, disconnected=False)

    # -- SSE streaming mode ----------------------------------------------
    def _sse_headers(self, handle: RequestHandle, replayed: bool) -> None:
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        self.send_header("Connection", "close")
        self.send_header("X-Request-Id", str(handle.req_id))
        tr = getattr(handle, "trace", None)
        if tr is not None:
            self.send_header("X-Trace-Id", tr.trace_id)
        if replayed:
            self.send_header("X-Replayed", "true")
        self.end_headers()
        self.close_connection = True

    def _sse_event(self, event: str, payload: Dict[str, Any]) -> None:
        self.wfile.write(
            f"event: {event}\ndata: {json.dumps(payload)}\n\n".encode())
        self.wfile.flush()

    def _heartbeat(self) -> None:
        pad = "x" * self.srv.heartbeat_pad_bytes
        self.wfile.write(f": hb {pad}\n\n".encode())
        self.wfile.flush()

    def _stream_sse(self, tracked: _Tracked, dedup: bool) -> None:
        """The streaming path: replay the committed prefix (a dedup
        attach starts at index 0 — idempotent retry), then follow the
        live stream, heartbeating while idle.  Any socket failure
        routes through ``release(disconnected=True)``: anonymous
        requests cancel within one scheduler iteration, identified
        ones arm the retry grace timer."""
        srv = self.srv
        handle = tracked.handle
        last_write = [time.monotonic()]

        def heartbeat():
            if time.monotonic() - last_write[0] >= srv.heartbeat_s:
                self._heartbeat()
                last_write[0] = time.monotonic()

        try:
            self._sse_headers(handle, replayed=dedup)
            try:
                for i, tok in handle.stream_from(
                        0, poll_s=min(srv.heartbeat_s, 0.05),
                        idle_cb=heartbeat):
                    ev = {"i": i, "t": int(tok)}
                    if srv.event_pad_bytes:
                        ev["pad"] = "x" * srv.event_pad_bytes
                    self._sse_event("token", ev)
                    last_write[0] = time.monotonic()
                result = handle.result(timeout=30.0)
                self._sse_event("done", self._with_trace(handle, {
                    "state": "FINISHED", "req_id": handle.req_id,
                    "n": handle.n_streamed,
                    "tokens": handle.tokens(),
                    "ids": np.asarray(result).tolist()}))
            except RequestRejected:
                self._sse_event("error", self._with_trace(handle, {
                    "state": "REJECTED",
                    "code": _reject_status(handle.reason or ""),
                    "reason": handle.reason}))
            except RequestAborted as e:
                self._sse_event("error", self._with_trace(handle, {
                    "state": e.state.value,
                    "code": _terminal_code(e.state, handle.reason),
                    "req_id": handle.req_id,
                    "reason": handle.reason,
                    "n": handle.n_streamed}))
        except socket.timeout:
            srv.metrics.on_write_stall(handle.req_id, srv.io_timeout_s)
            srv.release(tracked, disconnected=True)
            self.close_connection = True
            return
        except (BrokenPipeError, ConnectionResetError, OSError):
            srv.release(tracked, disconnected=True)
            self.close_connection = True
            return
        srv.release(tracked, disconnected=False)


# ---------------------------------------------------------------------
# wire client: the loadgen transport (and the test suite's SSE client)
# ---------------------------------------------------------------------
def iter_sse(resp):
    """Parse an SSE byte stream into ``(event, payload_dict)`` pairs;
    comment/heartbeat frames are skipped.  ``resp`` is anything with
    ``readline()`` (an ``http.client.HTTPResponse``)."""
    event: Optional[str] = None
    data: List[str] = []
    while True:
        line = resp.readline()
        if not line:
            return
        line = line.rstrip(b"\r\n")
        if not line:
            if event is not None:
                yield event, json.loads("\n".join(data)) if data else {}
            event, data = None, []
            continue
        if line.startswith(b":"):
            continue                              # heartbeat / comment
        if line.startswith(b"event:"):
            event = line[len(b"event:"):].strip().decode()
        elif line.startswith(b"data:"):
            data.append(line[len(b"data:"):].strip().decode())


class WireHandle:
    """Client-side mirror of a :class:`RequestHandle` for one request
    streamed over HTTP/SSE — the surface the load generator reads
    (state / n_streamed / ttft / cancel), fed by a reader thread."""

    def __init__(self, transport: "HttpTransport", request_id: str,
                 payload: Dict[str, Any]):
        self._tp = transport
        self.request_id = request_id
        self.payload = payload
        self.req_id: Optional[int] = None
        self.submit_t = time.monotonic()
        self.first_token_t: Optional[float] = None
        self.finish_t: Optional[float] = None
        self.reason: Optional[str] = None
        self.wire_error: Optional[str] = None
        self.status: Optional[int] = None         # HTTP status
        self.code: Optional[int] = None           # terminal lattice code
        self._lock = threading.Lock()
        self._tokens: Dict[int, int] = {}
        self._state = RequestState.QUEUED
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=f"wire-{request_id}")
        self._thread.start()

    # -- RequestHandle-compatible surface -------------------------------
    @property
    def state(self) -> RequestState:
        return self._state

    @property
    def n_streamed(self) -> int:
        with self._lock:
            return len(self._tokens)

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_t is None:
            return None
        return self.first_token_t - self.submit_t

    def tokens(self) -> List[int]:
        with self._lock:
            return [self._tokens[i] for i in sorted(self._tokens)]

    def cancel(self) -> bool:
        if self._state.terminal:
            return False
        return self._tp._cancel(self.request_id)

    def join(self, timeout: Optional[float] = None) -> None:
        self._thread.join(timeout)

    # -- reader thread ---------------------------------------------------
    def _run(self) -> None:
        import http.client as hc
        conn = hc.HTTPConnection(self._tp.host, self._tp.port,
                                 timeout=self._tp.timeout_s)
        try:
            conn.request("POST", "/v1/generate",
                         json.dumps(self.payload),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            self.status = resp.status
            if resp.status != 200:
                body = resp.read().decode(errors="replace")
                self._finish_from_status(resp.status, body)
                return
            rid = resp.getheader("X-Request-Id")
            if rid is not None and rid != "None":
                self.req_id = int(rid)
            for event, payload in iter_sse(resp):
                now = time.monotonic()
                if event == "token":
                    with self._lock:
                        first = not self._tokens
                        self._tokens[int(payload["i"])] = \
                            int(payload["t"])
                    if first and self.first_token_t is None:
                        self.first_token_t = now
                    if self._state is RequestState.QUEUED:
                        self._state = RequestState.RUNNING
                elif event == "done":
                    self.finish_t = now
                    self._state = RequestState.FINISHED
                    return
                elif event == "error":
                    self.finish_t = now
                    self.reason = payload.get("reason")
                    self.code = payload.get("code")
                    self._state = RequestState(
                        payload.get("state", "CANCELLED"))
                    return
            # EOF without a terminal event: ambiguous wire death
            self.wire_error = "stream ended without terminal event"
            self._state = RequestState.CANCELLED
        except (OSError, ValueError) as e:
            self.wire_error = f"{type(e).__name__}: {e}"
            if not self._state.terminal:
                self._state = RequestState.CANCELLED
        finally:
            conn.close()

    def _finish_from_status(self, status: int, body: str) -> None:
        self.finish_t = time.monotonic()
        try:
            payload = json.loads(body)
        except ValueError:
            payload = {}
        self.reason = payload.get("error") or body[:200]
        self.code = status
        if status in (429, 503):
            self._state = RequestState.REJECTED
        elif status == 408:
            self._state = RequestState.TIMED_OUT
        else:
            self._state = RequestState.CANCELLED

    def __repr__(self) -> str:
        return (f"WireHandle({self.request_id}, "
                f"state={self._state.value}, "
                f"streamed={self.n_streamed})")


class HttpTransport:
    """Load-generator transport that submits over the HTTP/SSE wire
    instead of calling ``frontend.submit`` in-process.

    Same seed, same engine vocab → the SAME request sequence as the
    in-process transport (pinned by tests): the loadgen's plan is a
    pure function of its seed, and both transports consume the plan
    through one kwargs builder, so wire chaos results are directly
    comparable to the in-process fleet-chaos baselines (PR 12).

    ``server=`` (optional) points at a co-located
    :class:`HttpServingServer` for end-of-run introspection
    (``kv_leak_report``) — over a real network the leak check runs
    server-side instead."""

    def __init__(self, host: str, port: int, *,
                 server: Optional[HttpServingServer] = None,
                 vocab_size: Optional[int] = None,
                 timeout_s: float = 60.0, tag: str = "lg"):
        self.host = host
        self.port = port
        self.server = server
        self.timeout_s = float(timeout_s)
        self.tag = tag
        self._n = 0
        self.submitted: List[Dict[str, Any]] = []
        self.handles: List[WireHandle] = []
        if vocab_size is None:
            if server is None:
                raise ValueError("HttpTransport needs vocab_size= (or a "
                                 "co-located server= to read it from)")
            vocab_size = int(server.frontend.engine.cfg.vocab_size)
        self.vocab_size = int(vocab_size)

    def submit(self, **kwargs) -> WireHandle:
        """Submit one request (frontend.submit kwargs) over the wire."""
        payload: Dict[str, Any] = {
            "prompt_ids": np.asarray(kwargs.pop("prompt_ids"),
                                     np.int32).tolist(),
            "max_new_tokens": int(kwargs.pop("max_new_tokens")),
            "stream": True,
        }
        for k, v in kwargs.items():
            if v is not None:
                payload[k] = v
        request_id = f"{self.tag}-{self._n}"
        self._n += 1
        payload["request_id"] = request_id
        self.submitted.append(dict(payload))
        h = WireHandle(self, request_id, payload)
        self.handles.append(h)
        return h

    def _cancel(self, request_id: str) -> bool:
        import http.client as hc
        conn = hc.HTTPConnection(self.host, self.port,
                                 timeout=self.timeout_s)
        try:
            conn.request("POST", "/v1/cancel",
                         json.dumps({"request_id": request_id}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            ok = resp.status == 200 and \
                json.loads(resp.read()).get("cancelled", False)
            return bool(ok)
        except (OSError, ValueError):
            return False
        finally:
            conn.close()

    def pump(self, sleep) -> None:
        """The loadgen's between-arrivals tick: the server drives its
        own scheduler, so the wire client only yields."""
        sleep(0.002)

    def drain(self, timeout_s: float = 120.0) -> None:
        """Wait for every reader thread to reach a terminal event."""
        deadline = time.monotonic() + timeout_s
        for h in self.handles:
            h.join(max(deadline - time.monotonic(), 0.0))

    def kv_leak_report(self) -> Dict[str, int]:
        if self.server is not None:
            return self.server.frontend.engine.kv_leak_report()
        # remote server: the leak invariant is checked server-side
        return {"free_blocks": -1, "index_blocks": -1, "slot_blocks": -1,
                "leaked": 0, "unaccounted": 0}

    def prefix_stats(self) -> Optional[Dict[str, Any]]:
        """Co-located server's prefix-cache counters (the loadgen's
        ``LoadReport.prefix`` section), or None over a real network —
        hit-rate is then read server-side from ``serve.prefix.*``."""
        if self.server is None:
            return None
        fn = getattr(self.server.frontend.engine, "prefix_stats", None)
        return fn() if callable(fn) else None


# ---------------------------------------------------------------------
# CLI: python -m paddle_tpu.serving.http --model llama_tiny --port 8821
# ---------------------------------------------------------------------
def build_frontend(args) -> ServingFrontend:
    """The CLI's engine + frontend from :func:`parse_args` output
    (``chip_smoke.py`` builds its server through the same two calls).
    Weights are seeded params only — no train state is ever built."""
    import jax

    from .. import parallel as dist
    from ..inference.serving import ContinuousBatchingEngine
    from ..models import glm_moe_lite as latent_zoo
    from ..models import granite_hybrid as hybrid_zoo
    from ..models import ling_linear as linear_zoo
    from ..models import llama as llama_zoo
    from ..parallel.topology import HybridTopology, set_topology
    from .frontend import AdmissionConfig

    # the zoo: the Llama family's presets, the hybrid family's
    # (state-space + attention layers), the latent-attention family's
    # and the linear-attention family's (a state beside a latent
    # cache); the engine reads the kind of model from the
    # configuration object
    families = (hybrid_zoo, latent_zoo, linear_zoo)
    zoo = next((z for z in families if args.model in z.PRESETS),
               llama_zoo)
    cfg_fn = getattr(zoo, args.model, None)
    if cfg_fn is None:
        raise SystemExit(f"unknown model {args.model!r} (the zoo has "
                         "llama_tiny / llama_7b / ... and "
                         + " / ".join(p for z in families
                                      for p in z.PRESETS) + ")")
    cfg_kw = {k: v for k, v in (("dtype", args.dtype),) if v is not None}
    if args.num_layers is not None:
        if zoo is hybrid_zoo:
            # the depth of a hybrid is its pattern's: its first layers
            cfg_kw["layer_types"] = \
                cfg_fn().layer_types[:args.num_layers]
        elif zoo in (latent_zoo, linear_zoo):
            cfg_kw["num_hidden_layers"] = args.num_layers
        else:
            cfg_kw["num_layers"] = args.num_layers
    cfg = cfg_fn(**cfg_kw)
    if zoo in families:
        params = zoo.init_params(cfg, args.seed)
    else:
        topo = dist.init_topology(devices=jax.devices()[:1])
        params = llama_zoo.init_llama_params(cfg, topo, args.seed)
    set_topology(HybridTopology())
    eng_kw: Dict[str, Any] = dict(
        max_batch=args.max_batch, block_size=args.block_size,
        num_blocks=args.num_blocks,
        prefill_buckets=tuple(args.prefill_buckets),
        aot_dir=args.aot_dir)
    if args.replicas > 1:
        from ..aot.serve import warm_engine_factory
        from .fleet import EngineRouter
        if args.aot_dir is None:
            raise SystemExit("--replicas > 1 needs --aot-dir (replicas "
                             "share one AOT artifact generation)")
        factory = warm_engine_factory(cfg, params, aot_dir=args.aot_dir,
                                      **{k: v for k, v in eng_kw.items()
                                         if k != "aot_dir"})
        engine: Any = EngineRouter([factory] * args.replicas)
    else:
        engine = ContinuousBatchingEngine(cfg, params, **eng_kw)
    return ServingFrontend(
        engine,
        admission=AdmissionConfig(max_queue_len=args.max_queue_len),
        stream_capacity=args.stream_capacity)


def parse_args(argv: Optional[List[str]] = None):
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.serving.http",
        description="HTTP/SSE serving endpoint over the "
                    "continuous-batching engine")
    ap.add_argument("--model", default="llama_tiny",
                    help="model-zoo config name (default: llama_tiny)")
    ap.add_argument("--dtype", default=None,
                    help="override the config's dtype (e.g. bfloat16)")
    ap.add_argument("--num-layers", type=int, default=None,
                    help="override the config's depth")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8821)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=256)
    ap.add_argument("--prefill-buckets", type=int, nargs="+",
                    default=[16])
    ap.add_argument("--aot-dir", default=None,
                    help="AOT artifact dir for a zero-compile warm "
                         "start (docs/aot.md)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="EngineRouter fleet size (needs --aot-dir)")
    ap.add_argument("--max-queue-len", type=int, default=256)
    ap.add_argument("--stream-capacity", type=int, default=512)
    ap.add_argument("--drain-timeout-s", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def main(argv: Optional[List[str]] = None) -> int:
    args = parse_args(argv)

    from ..core.device import enable_compile_cache
    from ..observability import REGISTRY
    from ..observability.tracing import TRACER
    enable_compile_cache()
    REGISTRY.enable()
    TRACER.enable()
    fe = build_frontend(args)
    server = HttpServingServer(fe, host=args.host, port=args.port,
                               drain_timeout_s=args.drain_timeout_s)
    server.install_signal_handlers()
    server.start()
    print(json.dumps({"serving": f"http://{server.host}:{server.port}",
                      "model": args.model,
                      "replicas": args.replicas}))
    server._drain_done.wait()           # until SIGTERM/SIGINT drains
    report = dict(server._drain_report or {})
    print(json.dumps({"shutdown": report}))
    return 0 if report.get("kv_leaked_blocks", 1) == 0 else 1


if __name__ == "__main__":
    import sys
    sys.exit(main(sys.argv[1:]))
