"""HTTP/SSE network front door (ISSUE 13): wire bit-identity, typed
status mapping, disconnect-safe streaming, slow-client isolation,
idempotent retry, graceful shutdown, and wire-level chaos.

Load-bearing contracts:

* token streams fetched over HTTP/SSE are BIT-IDENTICAL to the
  in-process ``ServingFrontend`` streams for the same seeds — greedy,
  sampled, and across a mid-stream replica kill observed through the
  socket (the PR 12 re-placement machinery, now proven at the wire);
* a broken/closed client socket cancels its request and frees the
  decode slot + refcounted KV pages (disconnect storms drain at
  ``kv_leaked_blocks == 0``);
* one stalled reader is isolated by the per-connection write deadline
  and never blocks the driver thread or its batchmates;
* a retry with the same ``request_id`` attaches to the live stream and
  replays the committed prefix instead of double-submitting;
* graceful shutdown under load drains in-flight streams, 503s new
  work with ``Retry-After``, and exits with a zero-leak report;
* the loadgen's wire transport offers the IDENTICAL seeded request
  sequence as its in-process mode, so wire chaos results are
  comparable to the fleet-chaos baselines.
"""

import http.client
import json
import signal
import tempfile
import threading
import time

import jax
import numpy as np
import pytest

from paddle_tpu import parallel as dist
from paddle_tpu.aot.serve import export_engine, warm_engine_factory
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models.llama import build_llama_train_step, llama_tiny
from paddle_tpu.observability import REGISTRY
from paddle_tpu.parallel.topology import HybridTopology, set_topology
from paddle_tpu.serving import (AdmissionConfig, EngineRouter,
                                HttpServingServer, LoadGenConfig,
                                PoissonLoadGenerator, RetryPolicy,
                                ServingFrontend)
from paddle_tpu.serving.http import HttpTransport, iter_sse

import faults

rng = np.random.default_rng(0)

# one geometry for the whole module so the AOT artifacts (exported
# once) warm-start every engine — tests pay deserialization, not
# tracing
GEOM = dict(max_batch=2, block_size=8, num_blocks=64,
            prefill_buckets=(8,))


@pytest.fixture(scope="module")
def model():
    cfg = llama_tiny()
    topo = dist.init_topology(devices=jax.devices()[:1])
    _, init_fn = build_llama_train_step(cfg, topo, num_microbatches=1)
    params = init_fn(0)["params"]
    set_topology(HybridTopology())
    return cfg, params


@pytest.fixture(scope="module")
def aot_dir(model):
    cfg, params = model
    d = tempfile.mkdtemp(prefix="http_aot_")
    export_engine(ContinuousBatchingEngine(cfg, params, **GEOM), d)
    return d


def _engine(model, aot_dir=None, **kw):
    cfg, params = model
    geom = dict(GEOM)
    geom.update(kw)
    return ContinuousBatchingEngine(cfg, params, aot_dir=aot_dir, **geom)


def _prompt(model, n):
    return rng.integers(0, model[0].vocab_size, (n,)).astype(np.int32)


def _assert_no_leaks(eng):
    rep = eng.kv_leak_report()
    assert rep["leaked"] == 0 and rep["unaccounted"] == 0, rep


def _post(port, path, payload, timeout=60.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", path, json.dumps(payload),
                 {"Content-Type": "application/json"})
    return conn, conn.getresponse()


def _get_json(port, path, timeout=10.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"{}"), \
            dict(resp.getheaders())
    finally:
        conn.close()


def _sse_collect(port, payload, timeout=120.0):
    """POST a streaming generate and collect ``(tokens_in_order,
    terminal_event, terminal_payload)`` from the SSE stream."""
    conn, resp = _post(port, "/v1/generate", payload, timeout)
    try:
        assert resp.status == 200, resp.read()
        toks = {}
        for event, data in iter_sse(resp):
            if event == "token":
                toks[data["i"]] = data["t"]
            else:
                return ([toks[i] for i in sorted(toks)], event, data)
        return ([toks[i] for i in sorted(toks)], "eof", {})
    finally:
        conn.close()


def _wait(pred, timeout_s=10.0, msg="condition"):
    t0 = time.monotonic()
    while not pred():
        if time.monotonic() - t0 > timeout_s:
            raise AssertionError(f"timed out waiting for {msg}")
        time.sleep(0.01)


def _counter(name):
    m = REGISTRY.get(name)
    return 0 if m is None else (m.value or 0)


# ---------------------------------------------------------------------
# wire bit-identity
# ---------------------------------------------------------------------
def test_wire_stream_bit_identical_to_inprocess(model, aot_dir):
    """Greedy AND sampled token streams over HTTP/SSE == the in-process
    frontend streams (== the batch engine results) for the same
    seeds."""
    prompts = [_prompt(model, n) for n in (5, 9)]
    kwargs = [dict(), dict(temperature=0.8, top_k=20, seed=7)]

    ref_eng = _engine(model, aot_dir)
    rids = [ref_eng.add_request(p, 6, **kw)
            for p, kw in zip(prompts, kwargs)]
    ref = ref_eng.run_to_completion()

    fe = ServingFrontend(_engine(model, aot_dir))
    srv = HttpServingServer(fe, heartbeat_s=0.1)
    with srv:
        results = []
        for p, kw in zip(prompts, kwargs):
            payload = {"prompt_ids": p.tolist(), "max_new_tokens": 6}
            payload.update(kw)
            results.append(_sse_collect(srv.port, payload))
        for (toks, event, data), rid, p in zip(results, rids, prompts):
            assert event == "done" and data["state"] == "FINISHED"
            full = np.concatenate([p, np.asarray(toks, np.int32)])
            np.testing.assert_array_equal(full, ref[rid])
            # the terminal event carries the same full ids
            np.testing.assert_array_equal(np.asarray(data["ids"]),
                                          ref[rid])
        _assert_no_leaks(fe.engine)


def test_wire_nonstream_json_mode(model, aot_dir):
    p = _prompt(model, 7)
    ref_eng = _engine(model, aot_dir)
    rid = ref_eng.add_request(p, 5)
    ref = ref_eng.run_to_completion()[rid]

    fe = ServingFrontend(_engine(model, aot_dir))
    with HttpServingServer(fe) as srv:
        conn, resp = _post(srv.port, "/v1/generate",
                           {"prompt_ids": p.tolist(),
                            "max_new_tokens": 5, "stream": False})
        try:
            assert resp.status == 200
            body = json.loads(resp.read())
        finally:
            conn.close()
        assert body["state"] == "FINISHED"
        np.testing.assert_array_equal(np.asarray(body["ids"]), ref)


def test_wire_bit_identity_across_replica_kill(model, aot_dir):
    """The PR 12 invariant observed through a socket: a replica dies
    mid-stream, the router re-places and replays from the committed
    prefix, and the SSE client sees ONE gap-free stream whose tokens
    are bit-identical to an unkilled run — greedy and sampled."""
    prompts = [_prompt(model, n) for n in (5, 8)]
    kwargs = [dict(), dict(temperature=0.8, top_k=20, seed=11)]

    ref_eng = _engine(model, aot_dir)
    rids = [ref_eng.add_request(p, 8, **kw)
            for p, kw in zip(prompts, kwargs)]
    ref = ref_eng.run_to_completion()

    factory = warm_engine_factory(model[0], model[1], aot_dir=aot_dir,
                                  **GEOM)
    router = EngineRouter([factory, factory],
                          policy=RetryPolicy(backoff_base_s=0.0),
                          sleep=lambda s: None)
    fe = ServingFrontend(router)
    srv = HttpServingServer(fe, heartbeat_s=0.05)
    with srv:
        streams = [{} for _ in prompts]
        done = [None, None]

        def consume(idx, payload):
            conn, resp = _post(srv.port, "/v1/generate", payload, 120.0)
            try:
                assert resp.status == 200
                for event, data in iter_sse(resp):
                    if event == "token":
                        assert data["i"] not in streams[idx], \
                            "duplicated token index on the wire"
                        streams[idx][data["i"]] = data["t"]
                    else:
                        done[idx] = (event, data)
                        return
            finally:
                conn.close()

        threads = []
        for i, (p, kw) in enumerate(zip(prompts, kwargs)):
            payload = {"prompt_ids": p.tolist(), "max_new_tokens": 8}
            payload.update(kw)
            t = threading.Thread(target=consume, args=(i, payload),
                                 daemon=True)
            t.start()
            threads.append(t)

        # wait until both streams have committed tokens, then kill the
        # replica actually running request 0 — mid-stream, through the
        # server's locked chaos hook
        _wait(lambda: all(len(s) >= 2 for s in streams), 60.0,
              "2 tokens on both wire streams")

        def kill(engine):
            victim = next(pl.replica
                          for pl in engine._placements.values())
            engine.kill_replica(victim, "wire chaos kill")
            return victim

        victim = srv.chaos(kill)
        for t in threads:
            t.join(timeout=120.0)
            assert not t.is_alive()
        assert router.stats["deaths"] == 1 and victim in (0, 1)
        for i, (p, rid) in enumerate(zip(prompts, rids)):
            event, data = done[i]
            assert event == "done", done[i]
            toks = [streams[i][j] for j in sorted(streams[i])]
            assert sorted(streams[i]) == list(range(len(toks))), \
                "token indices must be gap-free"
            np.testing.assert_array_equal(
                np.concatenate([p, np.asarray(toks, np.int32)]),
                ref[rid])
        _assert_no_leaks(router)


# ---------------------------------------------------------------------
# typed status mapping
# ---------------------------------------------------------------------
def test_malformed_requests_are_400(model, aot_dir):
    fe = ServingFrontend(_engine(model, aot_dir))
    with HttpServingServer(fe) as srv:
        cases = [
            b"{not json",
            json.dumps({"max_new_tokens": 4}).encode(),
            json.dumps({"prompt_ids": [], "max_new_tokens": 4}).encode(),
            json.dumps({"prompt_ids": [1, "a"],
                        "max_new_tokens": 4}).encode(),
            json.dumps({"prompt_ids": [1, 2],
                        "max_new_tokens": 0}).encode(),
            json.dumps({"prompt_ids": [1, 2], "max_new_tokens": 4,
                        "temperature": "hot"}).encode(),
        ]
        for raw in cases:
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=10)
            try:
                conn.request("POST", "/v1/generate", raw,
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                assert resp.status == 400, (raw, resp.status)
                assert "error" in json.loads(resp.read())
            finally:
                conn.close()
        # unknown path
        conn, resp = _post(srv.port, "/v1/nope", {})
        assert resp.status == 404
        resp.read()
        conn.close()


def test_overload_maps_to_429_with_retry_after(model, aot_dir):
    fe = ServingFrontend(
        _engine(model, aot_dir, max_batch=1),
        admission=AdmissionConfig(max_queue_len=1))
    with HttpServingServer(fe) as srv:
        # occupy the slot + the queue
        c1, r1 = _post(srv.port, "/v1/generate",
                       {"prompt_ids": _prompt(model, 5).tolist(),
                        "max_new_tokens": 40})
        assert r1.status == 200
        _wait(lambda: fe.engine.active_requests == 1, 30.0,
              "first request scheduled")
        c2, r2 = _post(srv.port, "/v1/generate",
                       {"prompt_ids": _prompt(model, 5).tolist(),
                        "max_new_tokens": 4})
        assert r2.status == 200
        conn, resp = _post(srv.port, "/v1/generate",
                           {"prompt_ids": _prompt(model, 5).tolist(),
                            "max_new_tokens": 4, "stream": False})
        try:
            assert resp.status == 429
            assert resp.getheader("Retry-After") is not None
            body = json.loads(resp.read())
            assert body["state"] == "REJECTED"
            assert "queue full" in body["error"]
        finally:
            conn.close()
        for c in (c1, c2):
            c.close()
        _assert_no_leaks(fe.engine)


def test_deadline_maps_to_408_and_queue_shed_to_503(model, aot_dir):
    eng = _engine(model, aot_dir, max_batch=1)
    # slow the decode so the deadline deterministically expires
    # mid-stream rather than racing a fast drain
    slow = faults.slow_steps(eng, 0.01, n=10 ** 6)
    slow.__enter__()
    fe = ServingFrontend(eng)
    with HttpServingServer(fe) as srv:
        # a request whose deadline expires mid-decode → 408 (JSON mode)
        conn, resp = _post(srv.port, "/v1/generate",
                           {"prompt_ids": _prompt(model, 5).tolist(),
                            "max_new_tokens": 100,
                            "deadline_s": 0.15, "stream": False})
        try:
            assert resp.status == 408
            body = json.loads(resp.read())
            assert body["state"] == "TIMED_OUT"
            assert body["reason"] == "deadline"
        finally:
            conn.close()
        # a request that cannot be seated inside its queue budget is
        # shed — load shedding is 503 + Retry-After.  Stealing the
        # whole KV pool (under the scheduler lock) makes "cannot seat"
        # deterministic
        stolen = srv.chaos(
            lambda eng: eng.alloc.acquire(eng.alloc.free_blocks))
        try:
            conn, resp = _post(srv.port, "/v1/generate",
                               {"prompt_ids": _prompt(model, 5).tolist(),
                                "max_new_tokens": 4, "stream": False,
                                "max_queue_time_s": 0.1})
            try:
                assert resp.status == 503
                assert resp.getheader("Retry-After") is not None
                assert json.loads(resp.read())["state"] == "TIMED_OUT"
            finally:
                conn.close()
        finally:
            srv.chaos(lambda eng: eng.alloc.release(stolen))
    slow.__exit__(None, None, None)


def test_cancel_endpoint_maps_to_499(model, aot_dir):
    fe = ServingFrontend(_engine(model, aot_dir))
    with HttpServingServer(fe) as srv:
        got = {}

        def blocking():
            conn, resp = _post(srv.port, "/v1/generate",
                               {"prompt_ids": _prompt(model, 5).tolist(),
                                "max_new_tokens": 100,
                                "request_id": "cancel-me",
                                "stream": False}, timeout=120.0)
            try:
                got["status"] = resp.status
                got["body"] = json.loads(resp.read())
            finally:
                conn.close()

        t = threading.Thread(target=blocking, daemon=True)
        t.start()
        _wait(lambda: fe.live_requests == 1, 30.0, "request live")
        conn, resp = _post(srv.port, "/v1/cancel",
                           {"request_id": "cancel-me"})
        assert resp.status == 200
        assert json.loads(resp.read())["cancelled"] is True
        conn.close()
        t.join(timeout=30.0)
        assert got["status"] == 499
        assert got["body"]["state"] == "CANCELLED"
        # unknown id is found=False, not an error
        conn, resp = _post(srv.port, "/v1/cancel",
                           {"request_id": "never-existed"})
        assert json.loads(resp.read()) == {"cancelled": False,
                                           "found": False}
        conn.close()
        _assert_no_leaks(fe.engine)


def test_fleet_exhausted_maps_to_503(model, aot_dir):
    factory = warm_engine_factory(model[0], model[1], aot_dir=aot_dir,
                                  **GEOM)
    router = EngineRouter([factory],
                          policy=RetryPolicy(backoff_base_s=0.0),
                          sleep=lambda s: None)
    fe = ServingFrontend(router)
    with HttpServingServer(fe) as srv:
        status, body, _ = _get_json(srv.port, "/readyz")
        assert status == 200 and body["ready"] is True
        assert body["health_census"]["HEALTHY"] == 1
        srv.chaos(lambda r: r.kill_replica(0, "chaos"))
        conn, resp = _post(srv.port, "/v1/generate",
                           {"prompt_ids": _prompt(model, 5).tolist(),
                            "max_new_tokens": 4, "stream": False})
        try:
            assert resp.status == 503
            assert resp.getheader("Retry-After") is not None
        finally:
            conn.close()
        status, body, headers = _get_json(srv.port, "/readyz")
        assert status == 503 and body["ready"] is False
        assert body["health_census"]["DEAD"] == 1


# ---------------------------------------------------------------------
# health / ready / metrics endpoints
# ---------------------------------------------------------------------
def test_health_ready_metrics_endpoints(model, aot_dir):
    REGISTRY.reset()
    REGISTRY.enable()
    try:
        fe = ServingFrontend(_engine(model, aot_dir))
        with HttpServingServer(fe, heartbeat_s=0.1) as srv:
            status, body, _ = _get_json(srv.port, "/healthz")
            assert status == 200 and body["status"] == "ok"
            status, body, _ = _get_json(srv.port, "/readyz")
            assert status == 200 and body["ready"] is True
            toks, event, _ = _sse_collect(
                srv.port, {"prompt_ids": _prompt(model, 5).tolist(),
                           "max_new_tokens": 4})
            assert event == "done" and len(toks) == 4
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=10)
            try:
                conn.request("GET", "/metrics")
                resp = conn.getresponse()
                assert resp.status == 200
                text = resp.read().decode()
            finally:
                conn.close()
            # the Prometheus dump carries the serve.http.* family
            assert "serve_http_connections_total" in text
            assert "serve_submitted_total" in text
    finally:
        REGISTRY.disable()
        REGISTRY.reset()


# ---------------------------------------------------------------------
# disconnect propagation + storms
# ---------------------------------------------------------------------
def test_disconnect_mid_stream_cancels_and_frees(model, aot_dir):
    """A client that vanishes mid-stream cancels its request — slot and
    refcounted KV pages free — while the batchmate's stream stays
    bit-identical to its solo run."""
    REGISTRY.reset()
    REGISTRY.enable()
    try:
        pb = _prompt(model, 9)
        solo = _engine(model, aot_dir, max_batch=1)
        rid = solo.add_request(pb, 6)
        want = solo.run_to_completion()[rid]

        fe = ServingFrontend(_engine(model, aot_dir))
        with HttpServingServer(fe, heartbeat_s=0.02,
                               retry_grace_s=0.0) as srv:
            mate = {}

            def consume_mate():
                mate["r"] = _sse_collect(
                    srv.port, {"prompt_ids": pb.tolist(),
                               "max_new_tokens": 6})

            t = threading.Thread(target=consume_mate, daemon=True)
            t.start()
            toks = faults.http_disconnect_mid_stream(
                "127.0.0.1", srv.port,
                {"prompt_ids": _prompt(model, 5).tolist(),
                 "max_new_tokens": 100},
                after_tokens=2, rst=True)
            assert len(toks) == 2
            # the abandoned request must cancel and free its slot
            _wait(lambda: fe.live_requests <= 1, 15.0,
                  "disconnected request cancelled")
            t.join(timeout=60.0)
            mate_toks, event, _ = mate["r"]
            assert event == "done"
            np.testing.assert_array_equal(
                np.concatenate([pb, np.asarray(mate_toks, np.int32)]),
                want)
            _wait(lambda: fe.live_requests == 0, 15.0, "drained")
            assert fe.engine.active_requests == 0
            _assert_no_leaks(fe.engine)
            assert _counter(
                "serve.http.disconnect_cancels_total") >= 1
    finally:
        REGISTRY.disable()
        REGISTRY.reset()


def test_disconnect_storm_drains_with_zero_leaks(model, aot_dir):
    """A storm of connect-stream-vanish clients (FIN and RST mixed)
    plus surviving requests: every abandoned request cancels, the
    survivors' streams stay correct, and the pool drains to zero leaked
    blocks."""
    fe = ServingFrontend(_engine(model, aot_dir),
                         admission=AdmissionConfig(max_queue_len=64))
    with HttpServingServer(fe, heartbeat_s=0.02,
                           retry_grace_s=0.0) as srv:
        p = _prompt(model, 6)
        ref_eng = _engine(model, aot_dir, max_batch=1)
        rid = ref_eng.add_request(p, 6)
        want = ref_eng.run_to_completion()[rid]

        survivors = []
        surv_lock = threading.Lock()

        def survivor():
            r = _sse_collect(srv.port, {"prompt_ids": p.tolist(),
                                        "max_new_tokens": 6})
            with surv_lock:
                survivors.append(r)

        threads = [threading.Thread(target=survivor, daemon=True)
                   for _ in range(3)]
        for t in threads:
            t.start()
        for i in range(10):
            faults.http_disconnect_mid_stream(
                "127.0.0.1", srv.port,
                {"prompt_ids": _prompt(model, 4).tolist(),
                 "max_new_tokens": 100},
                after_tokens=1, rst=bool(i % 2))
        for t in threads:
            t.join(timeout=120.0)
            assert not t.is_alive()
        _wait(lambda: fe.live_requests == 0, 30.0,
              "storm requests all cancelled")
        assert fe.engine.active_requests == 0
        assert fe.engine.queue_depth == 0
        _assert_no_leaks(fe.engine)
        for toks, event, _ in survivors:
            assert event == "done"
            np.testing.assert_array_equal(
                np.concatenate([p, np.asarray(toks, np.int32)]), want)


def test_connect_then_abandon_flood_is_harmless(model, aot_dir):
    """Connections that send nothing (or a partial request line) and
    vanish must not submit anything, wedge handler threads, or take
    the listener down."""
    REGISTRY.reset()
    REGISTRY.enable()
    try:
        fe = ServingFrontend(_engine(model, aot_dir))
        with HttpServingServer(fe, io_timeout_s=0.5) as srv:
            opened = faults.connect_then_abandon_flood(
                "127.0.0.1", srv.port, n=20)
            assert opened == 20
            # the server still answers, nothing was ever submitted
            status, body, _ = _get_json(srv.port, "/healthz")
            assert status == 200
            toks, event, _ = _sse_collect(
                srv.port, {"prompt_ids": _prompt(model, 5).tolist(),
                           "max_new_tokens": 4})
            assert event == "done" and len(toks) == 4
            assert REGISTRY.get("serve.submitted_total").value == 1
            _wait(lambda: (_counter(
                "serve.http.active_connections")) <= 1,
                15.0, "flood connections shed")
            _assert_no_leaks(fe.engine)
    finally:
        REGISTRY.disable()
        REGISTRY.reset()


def test_partial_line_writes_parse_fine(model, aot_dir):
    """A client that dribbles the request bytes mid-line is just a slow
    client: the request parses and streams normally."""
    p = _prompt(model, 5)
    ref_eng = _engine(model, aot_dir, max_batch=1)
    rid = ref_eng.add_request(p, 4)
    want = ref_eng.run_to_completion()[rid]
    fe = ServingFrontend(_engine(model, aot_dir))
    with HttpServingServer(fe) as srv:
        status, raw = faults.http_partial_line_writes(
            "127.0.0.1", srv.port,
            {"prompt_ids": p.tolist(), "max_new_tokens": 4})
        assert status == 200
        toks = [json.loads(line.split(b":", 1)[1])["t"]
                for line in raw.split(b"\n")
                if line.startswith(b"data:") and b'"t"' in line]
        np.testing.assert_array_equal(
            np.concatenate([p, np.asarray(toks, np.int32)]), want)
        _assert_no_leaks(fe.engine)


# ---------------------------------------------------------------------
# slow-client isolation
# ---------------------------------------------------------------------
def test_stalled_reader_isolated_from_batchmates(model, aot_dir):
    """A reader that stops draining its socket (closed TCP window)
    times out on the per-connection write deadline and is cancelled;
    the driver thread and the batchmate never notice."""
    REGISTRY.reset()
    REGISTRY.enable()
    try:
        pb = _prompt(model, 9)
        solo = _engine(model, aot_dir, max_batch=1)
        rid = solo.add_request(pb, 6)
        want = solo.run_to_completion()[rid]

        fe = ServingFrontend(_engine(model, aot_dir),
                             stream_capacity=4,
                             backpressure_timeout_s=0.2)
        with HttpServingServer(fe, heartbeat_s=0.02,
                               heartbeat_pad_bytes=4096,
                               event_pad_bytes=4096,
                               io_timeout_s=0.5,
                               retry_grace_s=0.0,
                               sndbuf_bytes=4096) as srv:
            stalled = faults.http_stalled_reader(
                "127.0.0.1", srv.port,
                {"prompt_ids": _prompt(model, 5).tolist(),
                 "max_new_tokens": 100}, rcvbuf=1024)
            try:
                # batchmate streams to completion while the stall is live
                toks, event, _ = _sse_collect(
                    srv.port, {"prompt_ids": pb.tolist(),
                               "max_new_tokens": 6})
                assert event == "done"
                np.testing.assert_array_equal(
                    np.concatenate([pb, np.asarray(toks, np.int32)]),
                    want)
                # the stalled stream hits the write deadline → cancelled
                _wait(lambda: fe.live_requests == 0, 30.0,
                      "stalled request isolated")
                assert _counter(
                    "serve.http.write_stall_timeouts_total") >= 1
            finally:
                stalled.close()
            _assert_no_leaks(fe.engine)
    finally:
        REGISTRY.disable()
        REGISTRY.reset()


# ---------------------------------------------------------------------
# idempotent retry / dedup window
# ---------------------------------------------------------------------
def test_retry_attaches_and_replays_committed_prefix(model, aot_dir):
    """A retry with the same request_id after a mid-stream disconnect
    attaches to the LIVE stream: the committed prefix replays from
    index 0 and the stream continues — one engine submission total,
    bit-identical to the uninterrupted run."""
    REGISTRY.reset()
    REGISTRY.enable()
    try:
        p = _prompt(model, 6)
        ref_eng = _engine(model, aot_dir, max_batch=1)
        rid = ref_eng.add_request(p, 10)
        want = ref_eng.run_to_completion()[rid]

        fe = ServingFrontend(_engine(model, aot_dir))
        with HttpServingServer(fe, heartbeat_s=0.02,
                               retry_grace_s=10.0) as srv:
            payload = {"prompt_ids": p.tolist(), "max_new_tokens": 10,
                       "request_id": "retry-1"}
            first = faults.http_disconnect_mid_stream(
                "127.0.0.1", srv.port, payload, after_tokens=2)
            assert len(first) == 2
            # retry: replays tokens 0..n then continues to done
            toks, event, data = _sse_collect(srv.port, payload)
            assert event == "done"
            np.testing.assert_array_equal(
                np.concatenate([p, np.asarray(toks, np.int32)]), want)
            assert toks[:2] == first          # committed prefix replayed
            assert REGISTRY.get("serve.submitted_total").value == 1
            assert _counter("serve.http.dedup_hits_total") == 1
            _assert_no_leaks(fe.engine)
    finally:
        REGISTRY.disable()
        REGISTRY.reset()


def test_retry_after_finish_replays_terminal_result(model, aot_dir):
    """A duplicate of an already-FINISHED identified request inside the
    dedup window replays the whole stream + terminal result without
    resubmitting."""
    REGISTRY.reset()
    REGISTRY.enable()
    try:
        p = _prompt(model, 5)
        fe = ServingFrontend(_engine(model, aot_dir))
        with HttpServingServer(fe, dedup_window_s=30.0) as srv:
            payload = {"prompt_ids": p.tolist(), "max_new_tokens": 6,
                       "request_id": "dup-1"}
            toks1, ev1, data1 = _sse_collect(srv.port, payload)
            toks2, ev2, data2 = _sse_collect(srv.port, payload)
            assert ev1 == ev2 == "done"
            assert toks1 == toks2
            assert data1["ids"] == data2["ids"]
            assert REGISTRY.get("serve.submitted_total").value == 1
            assert _counter("serve.http.dedup_hits_total") == 1
    finally:
        REGISTRY.disable()
        REGISTRY.reset()


def test_abandoned_identified_request_cancels_after_grace(model,
                                                          aot_dir):
    """Identified disconnects get a retry grace window; when nothing
    re-attaches, the request cancels (freeing its slot + pages) and is
    counted abandoned."""
    REGISTRY.reset()
    REGISTRY.enable()
    try:
        eng = _engine(model, aot_dir)
        # slow every decode step so the request is deterministically
        # still running when the grace timer fires
        slow = faults.slow_steps(eng, 0.02, n=10 ** 6)
        slow.__enter__()
        try:
            fe = ServingFrontend(eng)
            with HttpServingServer(fe, heartbeat_s=0.02,
                                   retry_grace_s=0.3) as srv:
                faults.http_disconnect_mid_stream(
                    "127.0.0.1", srv.port,
                    {"prompt_ids": _prompt(model, 5).tolist(),
                     "max_new_tokens": 100, "request_id": "ghost-1"},
                    after_tokens=1)
                # still generating inside the grace window
                time.sleep(0.05)
                assert fe.live_requests == 1
                _wait(lambda: fe.live_requests == 0, 30.0,
                      "grace expiry cancelled the request")
                assert _counter("serve.http.abandoned_total") == 1
                assert _counter("serve.finished_total") == 0
                _assert_no_leaks(fe.engine)
        finally:
            slow.__exit__(None, None, None)
    finally:
        REGISTRY.disable()
        REGISTRY.reset()


def test_retry_flood_single_submission(model, aot_dir):
    """Many concurrent retries of one request_id: exactly one engine
    submission, every reader gets the same bit-identical stream."""
    REGISTRY.reset()
    REGISTRY.enable()
    try:
        p = _prompt(model, 6)
        fe = ServingFrontend(_engine(model, aot_dir))
        with HttpServingServer(fe, heartbeat_s=0.02) as srv:
            payload = {"prompt_ids": p.tolist(), "max_new_tokens": 8,
                       "request_id": "flood-1"}
            results = []
            lock = threading.Lock()

            def reader():
                r = _sse_collect(srv.port, payload)
                with lock:
                    results.append(r)

            threads = [threading.Thread(target=reader, daemon=True)
                       for _ in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120.0)
                assert not t.is_alive()
            assert REGISTRY.get("serve.submitted_total").value == 1
            first = results[0]
            for toks, event, data in results:
                assert event == "done"
                assert toks == first[0]
                assert data["ids"] == first[2]["ids"]
            _assert_no_leaks(fe.engine)
    finally:
        REGISTRY.disable()
        REGISTRY.reset()


# ---------------------------------------------------------------------
# graceful shutdown
# ---------------------------------------------------------------------
def test_graceful_shutdown_drains_under_load(model, aot_dir):
    """SIGTERM semantics: new work gets 503 + Retry-After, /readyz goes
    503, in-flight streams run to completion, and the report is
    zero-leak."""
    REGISTRY.reset()
    REGISTRY.enable()
    try:
        p = _prompt(model, 6)
        ref_eng = _engine(model, aot_dir, max_batch=1)
        rid = ref_eng.add_request(p, 12)
        want = ref_eng.run_to_completion()[rid]

        fe = ServingFrontend(_engine(model, aot_dir))
        srv = HttpServingServer(fe, heartbeat_s=0.02,
                                drain_timeout_s=60.0).start()
        inflight = {}

        def consume():
            inflight["r"] = _sse_collect(
                srv.port, {"prompt_ids": p.tolist(),
                           "max_new_tokens": 12})

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        _wait(lambda: fe.live_requests == 1, 30.0, "stream live")
        report_box = {}

        def shutdown():
            report_box["r"] = srv.begin_shutdown(reason="test-sigterm")

        st = threading.Thread(target=shutdown, daemon=True)
        st.start()
        _wait(lambda: srv.draining, 10.0, "draining flag")
        # new work during the drain: 503 + Retry-After
        conn, resp = _post(srv.port, "/v1/generate",
                           {"prompt_ids": p.tolist(),
                            "max_new_tokens": 4, "stream": False})
        assert resp.status == 503
        assert resp.getheader("Retry-After") is not None
        resp.read()
        conn.close()
        status, body, _ = _get_json(srv.port, "/readyz")
        assert status == 503 and body["reason"] == "draining"
        st.join(timeout=120.0)
        t.join(timeout=120.0)
        assert not st.is_alive() and not t.is_alive()
        report = report_box["r"]
        # the in-flight stream completed through the drain, bit-identical
        toks, event, _ = inflight["r"]
        assert event == "done"
        np.testing.assert_array_equal(
            np.concatenate([p, np.asarray(toks, np.int32)]), want)
        assert report["drained_within_budget"] is True
        assert report["cancelled_at_deadline"] == 0
        assert report["kv_leaked_blocks"] == 0
        hist = REGISTRY.get("serve.http.shutdown_drain_secs")
        assert hist is not None and hist.count == 1
        srv._httpd.server_close()
    finally:
        REGISTRY.disable()
        REGISTRY.reset()


def test_sigterm_triggers_graceful_shutdown(model, aot_dir):
    """The installed SIGTERM handler runs the same drain path (the CLI
    contract: `python -m paddle_tpu.serving.http` exits clean on
    SIGTERM with a zero-leak report)."""
    old_term = signal.getsignal(signal.SIGTERM)
    old_int = signal.getsignal(signal.SIGINT)
    fe = ServingFrontend(_engine(model, aot_dir))
    srv = HttpServingServer(fe, drain_timeout_s=30.0).start()
    try:
        srv.install_signal_handlers()
        toks, event, _ = _sse_collect(
            srv.port, {"prompt_ids": _prompt(model, 5).tolist(),
                       "max_new_tokens": 4})
        assert event == "done"
        signal.raise_signal(signal.SIGTERM)
        assert srv._drain_done.wait(timeout=60.0)
        report = srv._drain_report
        assert report["reason"] == "SIGTERM"
        assert report["kv_leaked_blocks"] == 0
        srv._httpd.server_close()
    finally:
        signal.signal(signal.SIGTERM, old_term)
        signal.signal(signal.SIGINT, old_int)


def test_cli_builds_the_model_its_flags_name():
    """`--dtype` / `--num-layers` reach the zoo config, and the weights
    are the seeded params a train state starts from — built WITHOUT the
    train state (two fp32 Adam moment trees are 4x the bf16 weights:
    27 GB at llama_7b width and 16 layers, on a 16 GB chip)."""
    from paddle_tpu.serving.http import build_frontend, parse_args
    fe = build_frontend(parse_args(
        ["--model", "llama_tiny", "--dtype", "bfloat16", "--num-layers",
         "1", "--num-blocks", "16", "--prefill-buckets", "8", "32",
         "--seed", "3"]))
    try:
        eng = fe.engine
        assert (eng.cfg.dtype, eng.cfg.num_layers) == ("bfloat16", 1)
        assert set(eng.params) == {"wte", "head", "lnf_w", "blocks"}
        topo = dist.init_topology(devices=jax.devices()[:1])
        _, init_fn = build_llama_train_step(eng.cfg, topo,
                                            num_microbatches=1)
        ref = init_fn(3)["params"]
        set_topology(HybridTopology())
        # the engine holds q/k/v as its programs read them
        from paddle_tpu.ops.decode_block import serving_layout
        ref = dict(ref, blocks=serving_layout(ref["blocks"]))
        assert "q_wt" in eng.params["blocks"]
        for got, want in zip(jax.tree.leaves(eng.params),
                             jax.tree.leaves(ref)):
            assert got.dtype == want.dtype == np.dtype("bfloat16")
            np.testing.assert_array_equal(
                np.asarray(got.astype("float32")),
                np.asarray(want.astype("float32")))
        # a Llama-family engine has no kernel choice left to name
        assert eng.kernel_tiers() == {}
    finally:
        fe.close()


# ---------------------------------------------------------------------
# loadgen over the wire
# ---------------------------------------------------------------------
def test_loadgen_wire_transport_matches_inprocess_sequence(model,
                                                           aot_dir):
    """ISSUE 13 satellite: the same seed produces the IDENTICAL
    submitted request sequence — content, budgets, sampling, cancel
    plan — over the wire as in-process, so wire chaos numbers are
    comparable to the PR 12 fleet-chaos baselines."""
    lg = LoadGenConfig(
        n_requests=12, rate_rps=200.0, seed=17, prompt_len=(3, 8),
        max_new_tokens=(3, 6), sampled_fraction=0.3,
        cancel_fraction=0.25, cancel_after_tokens=1,
        slo_ttft_s=60.0, slo_tpot_s=30.0)

    fe1 = ServingFrontend(_engine(model, aot_dir))
    gen1 = PoissonLoadGenerator(fe1, lg)
    rep1 = gen1.run()
    plan1 = gen1.plan()
    inproc_kwargs = [gen1.request_kwargs(pp) for pp in plan1]

    fe2 = ServingFrontend(_engine(model, aot_dir))
    with HttpServingServer(fe2, heartbeat_s=0.05,
                           retry_grace_s=0.0) as srv:
        tp = HttpTransport("127.0.0.1", srv.port, server=srv)
        gen2 = PoissonLoadGenerator(None, lg, transport=tp)
        rep2 = gen2.run()
        _wait(lambda: fe2.live_requests == 0, 30.0, "wire drained")

        assert len(tp.submitted) == len(inproc_kwargs) == lg.n_requests
        for sub, kw, pp in zip(tp.submitted, inproc_kwargs, plan1):
            assert sub["prompt_ids"] == \
                np.asarray(kw["prompt_ids"]).tolist()
            assert sub["max_new_tokens"] == kw["max_new_tokens"]
            assert sub.get("temperature", 0.0) == kw["temperature"]
            assert sub.get("top_k") == kw["top_k"]
            assert sub.get("seed", 0) == kw["seed"]
        # the cancel plan is part of the sequence contract
        assert [pp.cancel for pp in plan1] == \
            [pp.cancel for pp in gen2.plan()]
        # both runs drain with zero leaks and full terminal accounting
        for rep in (rep1, rep2):
            d = rep.to_dict()
            assert d["kv_leaked_blocks"] == 0
            assert (rep.finished + rep.rejected + rep.cancelled
                    + rep.timed_out) == lg.n_requests
        # every request that FINISHED on both transports emitted the
        # same number of tokens (the engine's per-request determinism
        # observed through the wire)
        for r1, r2 in zip(rep1.per_request, rep2.per_request):
            if r1["state"] == "FINISHED" and r2["state"] == "FINISHED":
                assert r1["n_tokens"] == r2["n_tokens"]
        _assert_no_leaks(fe2.engine)


def test_loadgen_wire_chaos_smoke(model, aot_dir):
    """Seeded wire traffic with mid-stream cancels + a disconnect storm
    riding the same server drains clean — the wire analogue of the
    fleet chaos smoke."""
    fe = ServingFrontend(_engine(model, aot_dir),
                         admission=AdmissionConfig(max_queue_len=64))
    with HttpServingServer(fe, heartbeat_s=0.02,
                           retry_grace_s=0.0) as srv:
        tp = HttpTransport("127.0.0.1", srv.port, server=srv)
        gen = PoissonLoadGenerator(None, LoadGenConfig(
            n_requests=10, rate_rps=300.0, seed=23, prompt_len=(3, 8),
            max_new_tokens=(3, 6), sampled_fraction=0.25,
            cancel_fraction=0.2, cancel_after_tokens=1,
            slo_ttft_s=60.0, slo_tpot_s=30.0), transport=tp)
        storm = threading.Thread(
            target=lambda: [faults.http_disconnect_mid_stream(
                "127.0.0.1", srv.port,
                {"prompt_ids": _prompt(model, 4).tolist(),
                 "max_new_tokens": 80}, after_tokens=1,
                rst=bool(i % 2)) for i in range(4)],
            daemon=True)
        storm.start()
        rep = gen.run()
        storm.join(timeout=60.0)
        _wait(lambda: fe.live_requests == 0, 30.0, "all drained")
        d = rep.to_dict()
        assert d["kv_leaked_blocks"] == 0
        assert rep.finished > 0
        assert (rep.finished + rep.rejected + rep.cancelled
                + rep.timed_out) == 10
        assert fe.engine.active_requests == 0
        assert fe.engine.queue_depth == 0
        _assert_no_leaks(fe.engine)
