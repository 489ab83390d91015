"""End-to-end request tracing (ISSUE 20): span timelines across
wire → router → engine with p99 latency-budget attribution.

Load-bearing contracts:

* every terminal request state (FINISHED / REJECTED / CANCELLED /
  TIMED_OUT) yields a rooted span tree — unique monotonically-ordered
  span ids, valid parents, every span inside ``[0, duration]``;
* a request that survives a mid-stream replica kill keeps ONE
  trace_id: the ``re_place`` span and the post-replay engine spans
  land on the original trace, and the finished trace is exemplar-
  captured as ``replayed``;
* the ISSUE 20 acceptance scenario — an SLO-violating request under
  injected chaos (KV-pool exhaustion + a replica kill from
  tests/faults.py) — produces a flight dump whose span tree attributes
  the TTFT overrun to the queueing/replay phases, not to compute;
* disabled-mode tracing allocates nothing on the hot path (the
  MetricsRegistry bar from test_observability.py);
* the tracing module and every instrumented serve file carry ZERO
  tracelint/locklint findings, and both ledgers stay EMPTY;
* the engine timeline (ISSUE 27): every ``frontend.step()`` is one
  ``iteration`` span tree with the documented names and parents, every
  span is also one ``pt:`` profiler annotation over the same interval,
  the boundary counters count to the unit with the tracer on or off,
  and the per-request ``decode_step`` / ``ttft_s`` records the
  benchmark harness reads are untouched.
"""

import gc
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

from paddle_tpu import parallel as dist
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models.llama import build_llama_train_step, llama_tiny
from paddle_tpu.observability import (FlightRecorder, MemorySink,
                                      MetricsRegistry, REGISTRY)
from paddle_tpu.observability import tracing
from paddle_tpu.observability.tracing import (PROFILER_PREFIX, TRACER,
                                              SpanTracer, Timeline, Trace,
                                              attribution,
                                              write_spans_jsonl)
from paddle_tpu.parallel.topology import HybridTopology, set_topology
from paddle_tpu.serving import (AdmissionConfig, EngineRouter,
                                HttpServingServer, LoadGenConfig,
                                PoissonLoadGenerator, RequestState,
                                RetryPolicy, ServingFrontend)

import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import trace_report  # noqa: E402

rng = np.random.default_rng(20)

TERMINAL = {"FINISHED", "REJECTED", "CANCELLED", "TIMED_OUT"}


@pytest.fixture(scope="module")
def model():
    cfg = llama_tiny()
    topo = dist.init_topology(devices=jax.devices()[:1])
    _, init_fn = build_llama_train_step(cfg, topo, num_microbatches=1)
    params = init_fn(0)["params"]
    set_topology(HybridTopology())
    return cfg, params


@pytest.fixture(autouse=True)
def _tracer_isolation():
    """The process-wide TRACER must come out of every test the way it
    went in: disabled, empty, default SLOs (mirrors the REGISTRY
    isolation in test_observability.py)."""
    yield
    TRACER.disable()
    TRACER.reset()
    TRACER.configure(slo_ttft_s=None, slo_tpot_s=None)
    REGISTRY.disable()
    for s in REGISTRY.sinks:
        REGISTRY.remove_sink(s)


def _engine(model, **kw):
    cfg, params = model
    kw.setdefault("max_batch", 2)
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("prefill_buckets", (8,))
    return ContinuousBatchingEngine(cfg, params, **kw)


def _router(model, n=2, **kw):
    cfg, params = model
    geom = dict(max_batch=2, block_size=8, num_blocks=64,
                prefill_buckets=(8,))
    geom.update(kw)

    def factory():
        return ContinuousBatchingEngine(cfg, params, **geom)

    return EngineRouter([factory] * n,
                        policy=RetryPolicy(backoff_base_s=0.0),
                        sleep=lambda s: None)


def _prompt(model, n):
    return rng.integers(0, model[0].vocab_size, (n,)).astype(np.int32)


def _drain(fe, timeout_s=120.0):
    t0 = time.monotonic()
    while fe.step():
        if time.monotonic() - t0 > timeout_s:
            raise AssertionError("frontend never drained")


def _assert_well_formed(tr: Trace):
    """The structural pin: rooted, id-monotonic, time-bounded tree."""
    assert tr.finished, tr.trace_id
    assert tr.state in TERMINAL, tr.state
    dur = tr.duration_s
    assert dur is not None and dur >= 0.0
    spans = tr.snapshot()
    ids = [s.span_id for s in spans]
    assert ids == sorted(ids) and len(ids) == len(set(ids)), ids
    known = {0} | set(ids)
    eps = 1e-6
    for s in spans:
        assert s.parent in known and s.parent < s.span_id, \
            (tr.trace_id, s.name, s.parent, s.span_id)
        assert -eps <= s.t0 <= s.t1 <= dur + eps, \
            (tr.trace_id, s.name, s.t0, s.t1, dur)
    assert tr.dropped == 0
    d = tr.to_dict()
    assert d["trace_id"] == tr.trace_id and len(d["spans"]) == len(spans)


# ---------------------------------------------------------------------
# span-tree structure across every terminal state
# ---------------------------------------------------------------------
def test_every_terminal_state_yields_wellformed_tree(model):
    """Two overload runs — one behind a tiny admission cap (sheds via
    REJECTED), one behind a queue-time budget (sheds via TIMED_OUT),
    both with mid-stream cancels — leave one well-formed span tree per
    request across ALL FOUR terminal states, each reachable through
    the finished ring."""
    TRACER.enable()
    TRACER.reset()
    fe = ServingFrontend(_engine(model, num_blocks=48),
                         admission=AdmissionConfig(max_queue_len=4))
    rep = PoissonLoadGenerator(fe, LoadGenConfig(
        n_requests=24, rate_rps=500.0, seed=7, prompt_len=(3, 8),
        max_new_tokens=(4, 10), sampled_fraction=0.25,
        cancel_fraction=0.2, cancel_after_tokens=2,
        slo_ttft_s=60.0, slo_tpot_s=30.0)).run()
    assert rep.rejected > 0 and rep.finished > 0 and rep.cancelled > 0
    fe2 = ServingFrontend(_engine(model, num_blocks=48))
    rep2 = PoissonLoadGenerator(fe2, LoadGenConfig(
        n_requests=30, rate_rps=500.0, seed=11, prompt_len=(4, 10),
        max_new_tokens=(8, 16), cancel_fraction=0.1,
        max_queue_time_s=0.1, slo_ttft_s=60.0, slo_tpot_s=30.0)).run()
    assert rep2.timed_out >= 1 and rep2.finished >= 1
    done = TRACER.done_traces()
    assert len(done) == rep.n_requests + rep2.n_requests
    states = set()
    for tr in done:
        _assert_well_formed(tr)
        states.add(tr.state)
        names = [s.name for s in tr.snapshot()]
        meta = tr.meta
        assert meta["prompt_tokens"] >= 1
        if tr.state == "REJECTED":
            assert "reason" in meta and "prefill" not in names
        if tr.state == "FINISHED":
            assert meta["ttft_s"] > 0.0
            assert "queue_wait" in names and "prefill" in names
            assert "first_token" in names
        if tr.state == "TIMED_OUT":
            assert "reason" in meta
    assert states == TERMINAL, states
    assert len({tr.trace_id for tr in done}) == len(done)
    # the finished ring resolves every trace after the fact — by
    # trace_id always; by rid too, though the two frontends both
    # number from rid 0, so rid lookup resolves SOME trace with that
    # rid (newest wins, per the lookup contract).  Only REJECTED
    # requests never reached an engine and so carry no rid.
    for tr in done:
        assert TRACER.lookup(trace_id=tr.trace_id) is tr
        if tr.rid is not None:
            assert TRACER.lookup(rid=tr.rid).rid == tr.rid
        else:
            assert tr.state == "REJECTED"
    # attribution rides the report when the tracer is on; it covers
    # the requests that produced a first token (TTFT exists)
    for r in (rep, rep2):
        assert r.attribution is not None
        assert r.attribution["n_traced"] >= r.finished >= 1
        assert "queue_wait" in r.attribution["ttft"]


def test_preempt_restore_spans_on_one_trace(model):
    """An explicit preempt/restore cycle leaves spill + queue_wait +
    restore spans (in that order) on the preempted request's trace."""
    TRACER.enable()
    TRACER.reset()
    eng = _engine(model, max_batch=1)
    fe = ServingFrontend(eng)
    h = fe.submit(_prompt(model, 8), 8)
    fe.step()
    assert eng.active_requests == 1
    eng.preempt(next(s for s in range(eng.B)
                     if eng.slots[s] is not None))
    _drain(fe)
    assert h.state is RequestState.FINISHED
    tr = h.trace
    _assert_well_formed(tr)
    names = [s.name for s in tr.snapshot()]
    i_spill = names.index("preempt_spill")
    i_rest = names.index("preempt_restore")
    assert i_spill < i_rest
    assert "queue_wait" in names[i_spill:i_rest]
    spill = tr.snapshot()[i_spill]
    assert spill.attrs["committed"] >= 1


def test_sampled_and_spec_requests_trace_too(model):
    """Sampled decode traces like greedy; a speculating engine emits
    spec_decode_step spans with committed-token counts."""
    from paddle_tpu.spec_decode import SpecDecodeConfig
    cfg, params = model
    TRACER.enable()
    TRACER.reset()
    eng = ContinuousBatchingEngine(
        cfg, params, max_batch=2, block_size=8, num_blocks=64,
        prefill_buckets=(8,),
        spec_config=SpecDecodeConfig(draft_cfg=cfg, draft_params=params,
                                     k=3, window=12))
    fe = ServingFrontend(eng)
    h1 = fe.submit(_prompt(model, 8), 6)
    h2 = fe.submit(_prompt(model, 6), 6, temperature=0.8, top_k=8,
                   seed=5)
    _drain(fe)
    assert h1.state is RequestState.FINISHED
    assert h2.state is RequestState.FINISHED
    for h in (h1, h2):
        _assert_well_formed(h.trace)
        names = [s.name for s in h.trace.snapshot()]
        assert "spec_decode_step" in names, names
    committed = sum(s.attrs["committed"]
                    for s in h1.trace.snapshot()
                    if s.name == "spec_decode_step")
    # prefill itself emits the first token; spec steps commit the rest
    assert committed == h1.n_streamed - 1


# ---------------------------------------------------------------------
# replay links: one trace_id across replica death
# ---------------------------------------------------------------------
def test_replica_kill_keeps_one_trace_with_replay_spans(model):
    """The ISSUE 20 replay-link pin: a request whose replica dies
    mid-stream keeps its original trace_id; the re-placement and the
    post-replay engine spans land on the SAME tree, the finished trace
    is marked replayed, and the exemplar capture fires."""
    TRACER.enable()
    TRACER.reset()
    reg = MetricsRegistry(enabled=True)
    sink = MemorySink()
    reg.add_sink(sink)
    router = _router(model, n=2)
    fe = ServingFrontend(router, registry=reg)
    h = fe.submit(_prompt(model, 9), 10)
    tid0 = h.trace.trace_id
    it = iter(h)
    got = [next(it), next(it)]
    router.kill_replica(router._placements[h.req_id].replica, "chaos")
    got.extend(it)
    assert h.state is RequestState.FINISHED
    assert len(got) == 10
    tr = h.trace
    assert tr.trace_id == tid0
    _assert_well_formed(tr)
    assert tr.meta["replayed"] is True
    assert tr.meta["exemplar"] == "replayed"
    names = [s.name for s in tr.snapshot()]
    i_move = names.index("re_place")
    mv = tr.snapshot()[i_move]
    assert mv.attrs["from_replica"] != mv.attrs["to_replica"]
    assert mv.attrs["committed"] >= 2
    # engine spans continue on the same tree after the move
    assert "decode_step" in names[i_move:], names
    # both placements' decisions are on the tree
    assert names.count("placement") >= 2
    # exemplar capture: the full span tree rode the registry event
    ex = [r for r in sink.records
          if r.get("kind") == "trace"
          and r.get("action") == "slo_exemplar"]
    assert any(r["trace"]["trace_id"] == tid0
               and r["reason"] == "replayed" for r in ex), ex


def test_crash_replay_links_supervised_engine(model):
    """Single-replica analogue: a supervised engine crash mid-stream
    replays onto a rebuilt engine; the crash_replay span lands on the
    original trace."""
    from paddle_tpu.serving.resilience import (RetryPolicy as RP,
                                               SupervisedEngine)
    TRACER.enable()
    TRACER.reset()
    sup = SupervisedEngine(lambda: _engine(model),
                           policy=RP(backoff_base_s=0.0),
                           sleep=lambda s: None)
    fe = ServingFrontend(sup)
    h = fe.submit(_prompt(model, 8), 8)
    it = iter(h)
    got = [next(it), next(it)]
    with faults.fail_step_n(sup.engine, n=1):
        got.extend(it)
    assert h.state is RequestState.FINISHED
    tr = h.trace
    _assert_well_formed(tr)
    assert tr.meta["replayed"] is True
    names = [s.name for s in tr.snapshot()]
    i_rp = names.index("crash_replay")
    assert tr.snapshot()[i_rp].attrs["committed"] >= 2
    assert "decode_step" in names[i_rp:], names


# ---------------------------------------------------------------------
# the ISSUE 20 acceptance scenario
# ---------------------------------------------------------------------
def test_chaos_slo_miss_flight_dump_attributes_ttft(model, tmp_path):
    """An SLO-violating request under injected chaos — KV-pool
    exhaustion stalling admission plus a replica kill mid-run
    (tests/faults.py) — is exemplar-captured into the FlightRecorder
    ring, and the dumped span tree attributes the TTFT overrun to the
    queueing/replay phases (queue_wait dominates; compute does not)."""
    reg = MetricsRegistry(enabled=True)
    fr = FlightRecorder(capacity=512)
    reg.add_sink(fr)
    router = _router(model, n=2, max_batch=1)
    fe = ServingFrontend(router, registry=reg)
    # compile-warm both replicas so XLA compile time cannot pollute
    # the attribution below
    warm = [fe.submit(_prompt(model, 8), 2) for _ in range(4)]
    _drain(fe)
    assert all(w.state is RequestState.FINISHED for w in warm)

    TRACER.enable()
    TRACER.reset()
    TRACER.configure(slo_ttft_s=1e-4, slo_tpot_s=30.0)
    busy = [fe.submit(_prompt(model, 8), 16) for _ in range(2)]
    for _ in range(2):
        fe.step()
    # chaos 1: exhaust one replica's KV pool so admission stalls and
    # head-of-line requests queue
    victim = router._placements[busy[0].req_id].replica
    eng = router._replicas[victim].sup.engine
    with faults.exhaust_kv_pool(eng, leave=1):
        h = fe.submit(_prompt(model, 8), 4)
        for _ in range(3):
            fe.step()
        # chaos 2: kill the starved replica mid-run — its live request
        # re-places and replays on the survivor
        router.kill_replica(victim, "chaos")
        _drain(fe)
    assert h.state is RequestState.FINISHED
    tr = h.trace
    _assert_well_formed(tr)
    assert tr.meta["ttft_s"] > 1e-4          # the SLO was violated
    assert tr.meta["exemplar"] in ("slo_ttft", "replayed")

    # the flight dump carries the full span tree
    path = fr.dump("slo miss under chaos",
                   str(tmp_path / "flight.json"))
    dump = json.load(open(path))
    exemplars = [r for r in dump["records"]
                 if r.get("kind") == "trace"
                 and r.get("action") == "slo_exemplar"]
    mine = [r for r in exemplars
            if r["trace"]["trace_id"] == tr.trace_id]
    assert mine, [r["trace"]["trace_id"] for r in exemplars]
    td = mine[0]["trace"]

    # attribution from the DUMP (the offline tool's view): the TTFT
    # overrun belongs to queueing/replay, not prefill/decode compute
    att = trace_report.attribution([td])
    ttft = att["ttft"]
    assert "queue_wait" in ttft, ttft
    chaos_s = sum(d["sum"] for k, d in ttft.items()
                  if k in ("queue_wait", "re_place", "prefix_replay",
                           "crash_replay", "preempt_restore"))
    compute_s = sum(d["sum"] for k, d in ttft.items()
                    if k in ("prefill", "decode_step",
                             "spec_decode_step"))
    assert chaos_s > compute_s, att
    assert chaos_s > 0.5 * td["meta"]["ttft_s"], att
    # the killed replica's request was exemplar-captured as replayed
    # with the re_place span on ITS original trace
    replayed = [r for r in exemplars if r["reason"] == "replayed"]
    assert any("re_place" in [s["name"] for s in r["trace"]["spans"]]
               for r in replayed), replayed
    _drain(fe)


# ---------------------------------------------------------------------
# wire layer: /v1/trace, headers, /metrics freshness
# ---------------------------------------------------------------------
def _get(port, path):
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30.0)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read(), dict(resp.getheaders())
    finally:
        conn.close()


def test_http_trace_endpoint_and_headers(model):
    """GET /v1/trace/<key> resolves the server rid, the client
    request_id, AND the trace_id; the SSE response carries X-Trace-Id
    and the done event carries trace_id."""
    import http.client
    from paddle_tpu.serving.http import iter_sse
    TRACER.enable()
    TRACER.reset()
    fe = ServingFrontend(_engine(model))
    srv = HttpServingServer(fe, heartbeat_s=0.1)
    with srv:
        payload = {"prompt_ids": _prompt(model, 6).tolist(),
                   "max_new_tokens": 4, "request_id": "client-abc"}
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=60.0)
        conn.request("POST", "/v1/generate", json.dumps(payload),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        tid = resp.getheader("X-Trace-Id")
        rid = resp.getheader("X-Request-Id")
        assert tid
        done = None
        for event, data in iter_sse(resp):
            if event != "token":
                done = (event, data)
                break
        conn.close()
        assert done is not None and done[0] == "done"
        assert done[1]["trace_id"] == tid
        # all three key spaces resolve to the same trace
        for key in (rid, "client-abc", tid):
            status, body, _ = _get(srv.port, f"/v1/trace/{key}")
            assert status == 200, (key, body)
            d = json.loads(body)
            assert d["trace_id"] == tid
            assert d["state"] == "FINISHED"
            assert any(s["name"] == "prefill" for s in d["spans"])
        status, body, _ = _get(srv.port, "/v1/trace/nope")
        assert status == 404
        # the scheduler's own timeline under the same endpoint
        status, body, _ = _get(srv.port, "/v1/trace/engine")
        assert status == 200
        its = json.loads(body)["iterations"]
        assert its and its[0]["spans"][0]["name"] == "iteration"


def test_http_trace_endpoint_404_when_disabled(model):
    fe = ServingFrontend(_engine(model))
    srv = HttpServingServer(fe)
    with srv:
        status, body, _ = _get(srv.port, "/v1/trace/0")
        assert status == 404
        assert b"disabled" in body


def test_metrics_scrape_publishes_fresh_gauges(model):
    """The /metrics staleness fix: an idle server (driver parked, zero
    scheduler iterations) still serves CURRENT engine gauges because
    the handler publishes on scrape."""
    reg = MetricsRegistry(enabled=True)
    fe = ServingFrontend(_engine(model, num_blocks=64), registry=reg)
    srv = HttpServingServer(fe)
    with srv:
        status, body, _ = _get(srv.port, "/metrics")
        assert status == 200
        text = body.decode()
        # these gauges are ONLY set by _publish(); with no traffic the
        # driver never steps, so their presence proves the scrape path
        assert "paddle_tpu_serve_kv_free_blocks 64" in text, text[:800]
        assert "paddle_tpu_serve_queue_depth 0" in text


# ---------------------------------------------------------------------
# export + offline report
# ---------------------------------------------------------------------
def _traced_run(model, n=6):
    TRACER.enable()
    TRACER.reset()
    fe = ServingFrontend(_engine(model, num_blocks=48))
    PoissonLoadGenerator(fe, LoadGenConfig(
        n_requests=n, rate_rps=200.0, seed=3, prompt_len=(3, 8),
        max_new_tokens=(3, 6), sampled_fraction=0.25,
        slo_ttft_s=60.0, slo_tpot_s=30.0)).run()
    return TRACER.done_traces()


def test_jsonl_roundtrip(model, tmp_path):
    done = _traced_run(model)
    jp = str(tmp_path / "traces.jsonl")
    write_spans_jsonl(done, jp)
    lines = [json.loads(ln) for ln in open(jp)]
    assert len(lines) == len(done)
    assert all("spans" in d and "trace_id" in d for d in lines)
    names = {s["name"] for d in lines for s in d["spans"]}
    assert "prefill" in names and "queue_wait" in names


def test_trace_report_tool(model, tmp_path, capsys):
    """tools/trace_report.py renders the attribution table and a
    per-trace waterfall from the JSONL dump (the tier-1 smoke)."""
    done = _traced_run(model)
    jp = str(tmp_path / "traces.jsonl")
    write_spans_jsonl(done, jp)
    assert trace_report.main([jp]) == 0
    out = capsys.readouterr().out
    assert "TTFT attribution" in out
    assert "queue_wait" in out and "prefill" in out
    assert trace_report.main([jp, "--trace", done[0].trace_id]) == 0
    out = capsys.readouterr().out
    assert done[0].trace_id in out
    assert "prefill" in out
    # offline attribution agrees with the live one on phase totals
    live = attribution(done)
    offline = trace_report.attribution([t.to_dict() for t in done])
    assert set(offline["ttft"]) == set(live["ttft"])
    for k in live["ttft"]:
        assert offline["ttft"][k]["sum"] == pytest.approx(
            live["ttft"][k]["sum"], abs=2e-4)
    # empty / unknown inputs fail loudly, not silently
    empty = str(tmp_path / "empty.jsonl")
    open(empty, "w").close()
    assert trace_report.main([empty]) == 1
    assert trace_report.main([jp, "--trace", "no-such-trace"]) == 1


def test_training_twin_records_steps(tmp_path):
    """Model.fit's telemetry hook lands train_step spans on the
    process-wide training trace (the serve-path trace's training
    twin); ElasticTrainer reshape lands a reshape span (exercised by
    the chaos runs in test_parallel_elastic)."""
    import paddle_tpu as pt
    from paddle_tpu import nn
    from paddle_tpu.io.dataset import TensorDataset
    TRACER.enable()
    TRACER.reset()
    pt.seed(0)
    net = nn.Sequential(nn.Flatten(), nn.Linear(16, 8), nn.ReLU(),
                        nn.Linear(8, 4))
    m = pt.Model(net)
    m.prepare(
        optimizer=pt.optimizer.Adam(1e-2, parameters=net.parameters()),
        loss=nn.CrossEntropyLoss())
    data = np.random.default_rng(0)
    x = data.normal(size=(32, 16)).astype(np.float32)
    y = data.integers(0, 4, size=(32,)).astype(np.int64)
    m.fit(TensorDataset([x, y]), batch_size=16, epochs=2, verbose=0,
          shuffle=False, observe=str(tmp_path / "tele"))
    tt = TRACER.train_trace()
    steps = [s for s in tt.snapshot() if s.name == "train_step"]
    assert len(steps) == 4                    # 2 epochs x 2 batches
    for s in steps:
        assert s.t1 >= s.t0 >= 0.0
        assert "loss" in s.attrs and s.attrs["skipped"] is False
        assert s.attrs["step"] >= 1


# ---------------------------------------------------------------------
# overhead: disabled mode is free
# ---------------------------------------------------------------------
class TestDisabledOverhead:
    def test_disabled_begin_is_none_and_records_nothing(self):
        t = SpanTracer(enabled=False)
        assert t.begin(rid=1) is None
        assert t.current() is None
        with t.activating(None):
            assert t.current() is None
        t.finish(None, "FINISHED")
        assert t.done_traces() == []
        assert t.lookup(rid=1) is None

    def test_disabled_serve_path_allocates_nothing(self):
        """The ISSUE 20 bar, mirroring the MetricsRegistry test: with
        tracing off, the per-request begin/activate/finish path and the
        per-step current() probe allocate nothing."""
        t = SpanTracer(enabled=False)

        def one_request():
            tr = t.begin(rid=1)
            with t.activating(tr):
                t.current()
                t.current()
            t.finish(tr, "FINISHED")

        for _ in range(2000):                 # warm freelists/caches
            one_request()
        gc.collect()
        before = sys.getallocatedblocks()
        for _ in range(2000):
            one_request()
        gc.collect()
        delta = sys.getallocatedblocks() - before
        assert delta <= 8, f"disabled tracing leaked {delta} blocks"

    def test_disabled_fleet_serve_runs_without_traces(self, model):
        assert not TRACER.enabled
        fe = ServingFrontend(_engine(model))
        h = fe.submit(_prompt(model, 6), 3)
        _drain(fe)
        assert h.state is RequestState.FINISHED
        assert h.trace is None
        assert TRACER.done_traces() == []


# ---------------------------------------------------------------------
# span cap + thread safety of the Trace itself
# ---------------------------------------------------------------------
def test_span_ring_bounded_and_drop_counted():
    tr = Trace("t-1", max_spans=8)
    for i in range(20):
        tr.add("s", 0.0, 1.0)
    assert len(tr.snapshot()) == 8
    assert tr.dropped == 12
    assert tr.to_dict()["dropped_spans"] == 12


def test_trace_thread_safety():
    import threading
    tr = Trace("t-2", max_spans=100_000)
    n_threads, per_thread = 8, 2000

    def work():
        for _ in range(per_thread):
            tr.add("s", 0.0, 1.0)

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    spans = tr.snapshot()
    assert len(spans) == n_threads * per_thread
    ids = [s.span_id for s in spans]
    assert len(set(ids)) == len(ids)          # no duplicate ids


# ---------------------------------------------------------------------
# the engine timeline (ISSUE 27)
# ---------------------------------------------------------------------
#: span -> parent, as docs/observability.md tables them
TIMELINE_PARENT = {
    "iteration": None, "expire": "iteration", "engine_step": "iteration",
    "retire": "engine_step", "admit": "engine_step", "prefill": "admit",
    "prefill_chunk": "prefill", "first_token_fetch": "prefill",
    "decode_dispatch": "engine_step", "logits_fetch": "engine_step",
    "pick": "engine_step", "spec_decode": "engine_step",
    "deliver": "iteration", "publish": "iteration",
    # the seam between two decode steps, phase by phase (ISSUE 38)
    "plan": "engine_step", "upload": "decode_dispatch",
    "launch": "decode_dispatch", "device_wait": "logits_fetch",
    "copy": "logits_fetch", "stamp": "engine_step",
    "collect": "deliver", "apply": "deliver"}


def _assert_tree(it, root="iteration"):
    """One iteration: the documented names and parents, children inside
    their parent, siblings in order and not overlapping."""
    by_id = {s.span_id: s for s in it.spans}
    assert [s.span_id for s in it.spans] == \
        list(range(1, len(it.spans) + 1))
    roots = [s for s in it.spans if s.parent == 0]
    assert [s.name for s in roots] == [root], it.to_dict()
    assert roots[0].attrs["n"] == it.n
    last_end = {}
    for s in it.spans:
        assert s.t0 <= s.t1
        if s.parent == 0:
            continue
        up = by_id[s.parent]
        assert up.name == TIMELINE_PARENT[s.name], (s.name, up.name)
        assert up.t0 <= s.t0 and s.t1 <= up.t1, (s.name, up.name)
        assert s.t0 >= last_end.get(s.parent, 0.0), s.name
        last_end[s.parent] = s.t1


def test_timeline_one_tree_per_frontend_step(model):
    TRACER.enable()
    fe = ServingFrontend(_engine(model))
    hs = [fe.submit(_prompt(model, 20), 4)]
    steps = 1
    fe.step()
    hs.append(fe.submit(_prompt(model, 11), 3, temperature=0.8, seed=5))
    while fe.step():
        steps += 1
    steps += 1
    its = TRACER.timeline().iterations()
    assert len(its) == steps
    assert [it.n for it in its] == list(range(1, steps + 1))
    for it in its:
        _assert_tree(it)
    names = {s.name for it in its for s in it.spans}
    assert names == set(TIMELINE_PARENT) - {"spec_decode"}
    assert its[0].spans[0].attrs["live"] == 1
    assert its[0].spans[0].attrs["queued"] == 1
    # a prefill joins its request trace: same rid, same interval
    prefills = [s for it in its for s in it.spans if s.name == "prefill"]
    assert sorted(s.attrs["rid"] for s in prefills) == \
        sorted(h.trace.rid for h in hs)
    for h in hs:
        tr = h.trace
        mine = next(s for s in tr.snapshot() if s.name == "prefill")
        on_tl = next(s for s in prefills if s.attrs["rid"] == tr.rid)
        assert on_tl.attrs["tokens"] == mine.attrs["tokens"]
        assert on_tl.attrs["chunks"] == -(-mine.attrs["tokens"] // 8)
        assert tr.mono_t0 + mine.t0 <= on_tl.t0 <= on_tl.t1 \
            <= tr.mono_t0 + mine.t1 + 1e-6
        assert (mine.t1 - mine.t0) - (on_tl.t1 - on_tl.t0) < 0.05
    # the sampled request went through the sampler inside `pick`
    assert any(s.name == "pick" and s.attrs["sampled"] == 1
               for it in its for s in it.spans)


def test_timeline_engine_root_spec_and_fleet(model):
    """An engine driven without a frontend records ``engine_step`` as
    the root; the spec-decode branch records ``spec_decode`` in place
    of dispatch/fetch/pick; a fleet's replicas share the one timeline
    and differ by ``replica``."""
    from paddle_tpu.spec_decode import SpecDecodeConfig
    TRACER.enable()
    eng = _engine(model, spec_config=SpecDecodeConfig(
        draft_cfg=model[0], draft_params=model[1], k=3, window=12))
    eng.add_request(_prompt(model, 6), 5)
    eng.run_to_completion()
    its = TRACER.timeline().iterations()
    assert its
    for it in its:
        _assert_tree(it, root="engine_step")
    spec = [s for it in its for s in it.spans if s.name == "spec_decode"]
    assert spec and sum(s.attrs["committed"] for s in spec) == 4
    assert not any(s.name in ("decode_dispatch", "logits_fetch", "pick")
                   for it in its for s in it.spans)
    TRACER.reset()
    fe = ServingFrontend(_router(model))
    for n in (5, 7):
        fe.submit(_prompt(model, n), 3)
    _drain(fe)
    its = TRACER.timeline().iterations()
    for it in its:
        _assert_tree(it)
    replicas = {s.attrs["replica"] for it in its for s in it.spans
                if s.name == "engine_step"}
    assert replicas == {0, 1}
    # the router sums its replicas' scheduler counters
    st = fe.engine.scheduler_stats()
    assert st["admissions"] == 2 and st["prefill_chunks"] == 2
    assert 0 < st["bucket_fill"] <= 1 and st["stalled_share"] == 0


@pytest.mark.parametrize("tracer_on", [False, True])
def test_timeline_counters_scripted_admission(model, tracer_on):
    """A 700-token prompt admitted through buckets [128, 512] while one
    stream is running: the chunk plan on the timeline, the boundary
    counters to the unit — the counters with the tracer off as well."""
    from paddle_tpu.models.llama import llama_tiny
    if tracer_on:
        TRACER.enable()
    cfg = llama_tiny(max_position_embeddings=1024)
    eng = ContinuousBatchingEngine(
        cfg, model[1], max_batch=2, block_size=8, num_blocks=128,
        prefill_buckets=(128, 512), enable_prefix_caching=False)
    reg = MetricsRegistry(enabled=True)
    fe = ServingFrontend(eng, registry=reg)
    fe.submit(_prompt(model, 20), 12)
    fe.step()                                   # one stream is running
    assert (eng.stalled_slot_iterations, eng.prefill_chunks,
            eng.prefill_tokens_dispatched) == (0, 1, 128)
    fe.submit(_prompt(model, 700), 2)
    fe.step()
    plan = eng._buckets.plan_chunks(700)
    assert plan == [(512, 512), (128, 128), (128, 60)]
    steps = 2
    while fe.step():
        steps += 1
    assert eng.admissions == 2
    assert eng.prefill_chunks == 1 + len(plan)
    assert eng.prefill_tokens_dispatched == 128 + sum(c for c, _ in plan)
    assert eng.stats["prefill_tokens_computed"] == 720
    assert eng.stalled_slot_iterations == 1
    # the operator's surface: the serve.sched.* gauges read these
    assert {k: reg.gauge(f"serve.sched.{k}").value for k in (
        "admissions", "prefill_chunks", "bucket_fill", "stalled_share")} \
        == {"admissions": 2, "prefill_chunks": 4,
            "bucket_fill": 720 / 896,
            "stalled_share": 1 / eng.decode_slot_steps}
    if not tracer_on:
        assert TRACER.timeline() is None
        return
    its = TRACER.timeline().iterations()
    assert len(its) == steps + 1
    # what the counter sums is on the timeline too: the one admit that
    # ran chunks while a stream was live
    assert [(it.n, s.attrs["running"]) for it in its for s in it.spans
            if s.name == "admit" and s.attrs["running"]
            and any(c.name == "prefill_chunk" for c in it.spans)] \
        == [(2, 1)]
    second = its[1].spans
    admit = next(s for s in second if s.name == "admit")
    assert admit.attrs == {"running": 1, "admitted": 1}
    assert [(s.attrs["size"], s.attrs["valid"]) for s in second
            if s.name == "prefill_chunk"] == plan
    pf = next(s for s in second if s.name == "prefill")
    assert pf.attrs["tokens"] == 700 and pf.attrs["chunks"] == 3 \
        and pf.attrs["cached_tokens"] == 0


def test_decode_walk_counters_scripted(model):
    """Two requests of known lengths through a table four chunks wide
    (128 pages of 8 tokens, 32 pages a trip): the decode program's page
    walk counted to the unit, tracer off, by the arithmetic the program
    itself uses — and the operator's gauges and the router's rollup."""
    from paddle_tpu.models.llama import llama_tiny
    from paddle_tpu.ops.paged_kv import decode_walk
    cfg = llama_tiny(max_position_embeddings=1024)
    geom = dict(max_batch=2, block_size=8, num_blocks=128,
                prefill_buckets=(128, 512), enable_prefix_caching=False)
    eng = ContinuousBatchingEngine(cfg, model[1], **geom)
    assert (eng.MB, decode_walk(eng.lengths + 1, eng.MB, eng.BS)) \
        == (128, (1, 32))
    assert eng.scheduler_stats()["kv_walk_share"] is None
    reg = MetricsRegistry(enabled=True)
    fe = ServingFrontend(eng, registry=reg)
    a = fe.submit(_prompt(model, 20), 6)
    fe.step()           # the attention sees 21 tokens of A: one trip,
    # still on the device (a step counts when its tokens are taken)
    assert (eng.decode_pages_walked, eng.decode_pages_live) == (0, 0)
    b = fe.submit(_prompt(model, 300), 3)
    fe.step()           # A's first step taken; A 22, B 301 launched
    assert (eng.decode_pages_walked, eng.decode_pages_live) == (64, 3)
    fe.step()           # A 22, B 301 taken: two trips over both rows
    assert (eng.decode_pages_walked, eng.decode_pages_live) \
        == (64 + 128, 3 + 3 + 38)
    _drain(fe)          # A 23 + B 302, then A alone at 24 and 25
    assert (len(a.tokens()), len(b.tokens())) == (6, 3)
    assert eng.decode_steps == 5
    assert (eng.decode_pages_walked, eng.decode_pages_live) \
        == (64 + 128 + 128 + 64 + 64, 3 + 41 + 41 + 3 + 4)
    st = eng.scheduler_stats()
    assert st["decode_pages_table"] == 5 * 2 * 128
    assert st["kv_walk_share"] == 448 / 1280 < 1
    assert st["kv_walk_fill"] == 92 / 448
    assert reg.gauge("serve.sched.kv_walk_share").value == 448 / 1280
    assert reg.gauge("serve.sched.kv_walk_fill").value == 92 / 448
    text = reg.prometheus_text()
    assert "serve_sched_kv_walk_share" in text \
        and "serve_sched_kv_walk_fill" in text
    # the router sums the integers and takes the ratios over the sums
    cfg_params = (cfg, model[1])
    fe = ServingFrontend(_router(cfg_params, **geom))
    fe.submit(_prompt(model, 20), 3)
    fe.submit(_prompt(model, 300), 3)
    _drain(fe)
    per = [r.sup.scheduler_stats() for r in fe.engine._live()]
    assert all(p["decode_pages_walked"] for p in per)
    tot = fe.engine.scheduler_stats()
    for k in ("decode_pages_walked", "decode_pages_live",
              "decode_pages_table"):
        assert tot[k] == sum(p[k] for p in per)
    # one replica walked one trip a step, the other two: 2 steps each
    assert (tot["decode_pages_walked"], tot["decode_pages_table"]) \
        == (2 * 64 + 2 * 128, 4 * 2 * 128)
    assert tot["kv_walk_share"] == 384 / 1024
    assert tot["kv_walk_fill"] == \
        tot["decode_pages_live"] / tot["decode_pages_walked"]


def test_timeline_ring_keeps_newest_and_counts_dropped(monkeypatch):
    monkeypatch.setattr(tracing, "TIMELINE_CAPACITY", 4)
    tl = Timeline()
    for i in range(10):
        root = tl.enter("engine_step")
        sp = tl.enter("admit", running=i)
        tl.leave(sp, admitted=0)
        tl.leave(root)
    its = tl.iterations()
    assert [it.n for it in its] == [7, 8, 9, 10]
    assert tl.dropped == 6
    assert its[-1].spans[1].attrs == {"running": 9, "admitted": 0}
    assert [it.n for it in tl.iterations(last=2)] == [9, 10]
    d = tl.to_dict(last=1)
    assert d["dropped"] == 6 and d["iterations"][0]["n"] == 10
    # a raise between open and close: the root's close takes the
    # abandoned children with it, and the next tree starts clean
    root = tl.enter("engine_step")
    tl.enter("admit")
    lost = tl.enter("prefill")
    tl.leave(root)
    tl.leave(lost)                               # late close: a no-op
    it = tl.iterations()[-1]
    assert [s.name for s in it.spans] == ["engine_step", "admit",
                                          "prefill"]
    assert all(s.t1 == it.spans[0].t1 for s in it.spans)
    assert tl.enter("engine_step").parent == 0
    # the tracer owns ONE timeline, lazily, and reset() drops it
    t = SpanTracer(enabled=True)
    assert t.timeline() is t.timeline()
    first = t.timeline()
    t.reset()
    assert t.timeline() is not first
    t.disable()
    assert t.timeline() is None


class _AnnotationRecorder:
    """Stands in for jax.profiler's annotation classes: no profiler
    session in tier-1, so record what WOULD have been opened."""

    def __init__(self):
        self.log = []              # ("enter"|"exit", kind, name, kwargs)

    def patch(self, monkeypatch):
        rec = self

        def make(kind):
            class Ann:
                def __init__(self, name, **kw):
                    self.name, self.kw = name, kw

                def __enter__(self):
                    rec.log.append(("enter", kind, self.name, self.kw))
                    return self

                def __exit__(self, *exc):
                    rec.log.append(("exit", kind, self.name, self.kw))
            return Ann

        monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                            make("trace"))
        monkeypatch.setattr(jax.profiler, "StepTraceAnnotation",
                            make("step"))
        return self


def test_timeline_spans_are_profiler_annotations(model, monkeypatch):
    rec = _AnnotationRecorder().patch(monkeypatch)
    TRACER.enable()
    fe = ServingFrontend(_engine(model))
    fe.submit(_prompt(model, 20), 3)
    fe.submit(_prompt(model, 5), 2)
    _drain(fe)
    its = TRACER.timeline().iterations()
    # one annotation a span, same name with the one prefix, same order
    entered = [e for e in rec.log if e[0] == "enter"]
    spans = [s for it in its for s in it.spans]
    assert [e[2] for e in entered] == \
        [PROFILER_PREFIX + s.name for s in spans]
    assert PROFILER_PREFIX == "pt:"
    # the iteration is a StepTraceAnnotation numbered like the tree
    steps = [e for e in entered if e[1] == "step"]
    assert [e[2] for e in steps] == ["pt:iteration"] * len(its)
    assert [e[3]["step_num"] for e in steps] == [it.n for it in its]
    assert all(e[1] == "trace" for e in entered
               if e[2] != "pt:iteration")
    # properly nested: every exit closes the innermost open annotation
    stack = []
    for what, _, name, _ in rec.log:
        if what == "enter":
            stack.append(name)
        else:
            assert stack.pop() == name
    assert stack == []


def test_timeline_disabled_costs_nothing(model, monkeypatch):
    """Tracer off: no annotation is opened, and 250 engine steps that
    admit, decode and retire keep no record — under half a block a
    step stays allocated (what JAX's own dispatch leaves behind),
    against dozens a step once the tracer is on."""
    rec = _AnnotationRecorder().patch(monkeypatch)
    eng = _engine(model, enable_prefix_caching=False)
    prompt = _prompt(model, 6)

    def fifty_steps():
        # per request: the admitting step (its first decode step goes
        # to the device), two more launched as the last is taken, the
        # drain of the last, the retire
        for _ in range(10):
            eng.add_request(prompt, 4)
            for _ in range(5):
                eng.step()
            assert not eng.queue and eng.active_requests == 0

    def net_blocks():
        gc.collect()
        before = sys.getallocatedblocks()
        for _ in range(5):
            fifty_steps()
        gc.collect()
        return sys.getallocatedblocks() - before

    for _ in range(4):                          # compile, fill caches
        fifty_steps()
    off = min(net_blocks() for _ in range(3))
    assert off <= 100, f"disabled timeline kept {off} blocks"
    assert rec.log == [] and TRACER.timeline() is None
    TRACER.enable()
    assert net_blocks() > 2000 and len(rec.log) > 2000


def test_timeline_leaves_harness_readings_alone(model):
    """What benchmark/lib/serve.py:_readings extracts: the per-request
    ``decode_step`` spans (one engine-level fact copied per slot) name
    exactly ``decode_steps`` distinct steps, each from inside the
    ``engine_step`` that launched it to inside the one that took its
    tokens, the next (the step runs ahead) or the same (a drain);
    ``ttft_s`` is the ``first_token`` instant."""
    TRACER.enable()
    eng = _engine(model)
    fe = ServingFrontend(eng)
    hs = [fe.submit(_prompt(model, n), 5) for n in (6, 9, 4)]
    _drain(fe)
    steps = set()
    for h in hs:
        tr = h.trace
        spans = tr.snapshot()
        for s in spans:
            if s.name == "decode_step":
                steps.add((round(tr.mono_t0 + s.t0, 6),
                           round(s.t1 - s.t0, 9)))
                assert s.attrs["batch"] in (1, 2)
        first = next(s for s in spans if s.name == "first_token")
        assert tr.meta["ttft_s"] == pytest.approx(first.t0, abs=1e-3)
    assert len(steps) == eng.decode_steps > 0
    tl_steps = [s for it in TRACER.timeline().iterations()
                for s in it.spans if s.name == "engine_step"]
    def within(t):
        return [i for i, s in enumerate(tl_steps)
                if s.t0 <= t + 1e-6 and t <= s.t1 + 1e-6]
    for t0, dur in steps:
        i, j = within(t0), within(t0 + dur)
        assert i and j and j[-1] - i[0] in (0, 1)


def test_trace_report_xplane_mode(capsys):
    """``tools/trace_report.py --xplane`` on the benchmark's recorded
    trace (no ``pt:`` spans there: it says so and still gives the
    device's busy and idle time), and its gap arithmetic on spans it
    would find in a traced serve run."""
    from benchmark.lib import xplane as xp
    small = os.path.join(REPO, "benchmark", "tests", "data",
                         "small.xplane.pb")
    assert trace_report.main(["--xplane", small]) == 0
    out = capsys.readouterr().out
    assert "no pt: spans" in out
    assert "busy 0.000366 s" in out and "idle 0.034417 s" in out
    host = [("bench:engine_step", 0.0, 10.0), ("pt:iteration", 0.5, 9.5),
            ("pt:engine_step", 1.0, 8.0), ("pt:logits_fetch", 2.0, 5.0),
            ("pt:pick", 5.0, 6.0), ("pt:deliver", 8.5, 9.0)]
    named = xp.idle_by_span([(4.5, 8.75), (20.0, 21.0)], host)
    assert named == pytest.approx({
        "pt:logits_fetch": 0.5, "pt:pick": 1.0, "pt:engine_step": 2.0,
        "pt:iteration": 0.5, "pt:deliver": 0.25, "unannotated": 1.0})
    # the decode step's two handshakes bound the clocks' offset: the
    # program started 0.2 after its dispatch began (the device is at
    # most 0.2 ahead) and ended 0.499 before the fetch returned (at
    # least -0.499 ahead); another program's events do not count
    host.append(("pt:decode_dispatch", 1.3, 1.9))
    mods = [("jit_step(123)", 1.5, 4.501), ("jit_fill(9)", 4.6, 4.7)]
    upper, lower = trace_report.clock_check(mods, host)
    assert upper == pytest.approx([0.2])
    assert lower == pytest.approx([-0.499])
    # a fetch that "returned" 1 ms before its program ended: the
    # device's clock is at least that far ahead
    _, lower = trace_report.clock_check([("jit_step(1)", 1.5, 5.001)],
                                        host)
    assert lower == pytest.approx([0.001])
    # the gaps named again with the device moved along that interval:
    # two operations leave the device idle from 4.5 to 9
    ops = [(0.0, 4.5), (9.0, 10.0)]
    window_s, idle_s, n_gaps, named = trace_report.idle_by_span(
        ops, host, 0.0, xp)
    assert (window_s, idle_s, n_gaps) == (10.0, 4.5, 1)
    assert trace_report.leaf_share(named) == pytest.approx(100 * 2 / 4.5)
    named = trace_report.idle_by_span(ops, host, 0.499, xp)[3]
    assert named["pt:logits_fetch"] == pytest.approx(0.001)
    assert named["pt:deliver"] == pytest.approx(0.5)


def test_trace_report_narrow_handshakes(capsys):
    """A trace that holds ``pt:launch`` and ``pt:device_wait`` bounds
    the clocks' offset by them: the uploads before the launch and the
    copy after the wait leave the interval; one that holds neither
    keeps the old pair.  The spans that got children name no idle time
    as leaves any more."""
    host = [("pt:iteration", 0.5, 9.5), ("pt:engine_step", 1.0, 8.0),
            ("pt:decode_dispatch", 1.3, 1.9), ("pt:logits_fetch", 2.0, 5.0),
            ("pt:pick", 5.0, 6.0), ("pt:deliver", 8.5, 9.0)]
    mods = [("jit_step(123)", 1.7, 4.2), ("jit_fill(9)", 4.6, 4.7)]
    assert trace_report.handshakes(host) == trace_report.SYNC_WIDE
    assert trace_report.inner_nodes(host) == trace_report.INNER_NODES
    upper, lower = trace_report.clock_check(mods, host)
    assert (upper, lower) == (pytest.approx([0.4]), pytest.approx([-0.8]))
    host += [("pt:upload", 1.3, 1.55), ("pt:launch", 1.6, 1.9),
             ("pt:device_wait", 2.0, 4.3), ("pt:copy", 4.3, 5.0),
             ("pt:collect", 8.5, 8.7), ("pt:apply", 8.7, 9.0)]
    assert trace_report.handshakes(host) == trace_report.SYNC_NARROW
    upper, lower = trace_report.clock_check(mods, host)
    assert (upper, lower) == (pytest.approx([0.1]), pytest.approx([-0.1]))
    # the wide pair is still there to compare with
    upper, lower = trace_report.clock_check(mods, host,
                                            trace_report.SYNC_WIDE)
    assert (upper, lower) == (pytest.approx([0.4]), pytest.approx([-0.8]))
    # the next step launched while this one runs is not its dispatch
    upper, _ = trace_report.clock_check(mods, host + [("pt:launch", 2.5,
                                                       2.8)])
    assert upper == pytest.approx([0.1])
    inner = trace_report.inner_nodes(host)
    assert set(inner) == {"pt:iteration", "pt:engine_step",
                          "pt:decode_dispatch", "pt:logits_fetch",
                          "pt:deliver"}
    # idle from 4.2 (the program ended) to 9: device_wait .1, copy .7,
    # pick 1, engine_step 2, iteration .5, collect .2, apply .3
    from benchmark.lib import xplane as xp
    named = trace_report.idle_by_span([(0.0, 4.2), (9.0, 10.0)],
                                      host, 0.0, xp)[3]
    assert named == pytest.approx({
        "pt:device_wait": 0.1, "pt:copy": 0.7, "pt:pick": 1.0,
        "pt:engine_step": 2.0, "pt:iteration": 0.5, "pt:collect": 0.2,
        "pt:apply": 0.3})
    assert trace_report.leaf_share(named, inner) == \
        pytest.approx(100 * 2.3 / 4.8)


# ---------------------------------------------------------------------
# the seam between two decode steps, phase by phase (ISSUE 38)
# ---------------------------------------------------------------------
def _kids(it, name):
    """The children of the iteration's one span ``name``, in order."""
    up = [s for s in it.spans if s.name == name]
    assert len(up) == 1, (name, [s.name for s in it.spans])
    return up[0], [s for s in it.spans if s.parent == up[0].span_id]


def _sent(eng):
    return eng.block_table.nbytes + eng.lengths.nbytes + eng.tokens.nbytes


def test_seam_spans_solo_engine(model):
    """Every host phase between "the device is done" and "the next step
    is handed over" is a leaf under the span that held it; the parents
    keep their names and their attrs.  The step runs ahead: an
    iteration hands step n+1 over, then takes step n's greedy row."""
    TRACER.enable()
    eng = _engine(model)
    eng.add_request(_prompt(model, 6), 4)
    eng.run_to_completion()
    its = TRACER.timeline().iterations()
    for it in its:
        _assert_tree(it, root="engine_step")
    head = ["retire", "admit", "retire", "plan"]
    launch, take = ["decode_dispatch"], ["logits_fetch", "pick", "stamp"]
    # the first step goes to the device; two more are launched as the
    # one before is taken; the last is drained; the last iteration
    # retires and decodes nothing
    assert [[s.name for s in _kids(it, "engine_step")[1]] for it in its] \
        == [head + launch, head + launch + take, head + launch + take,
            head + take, head]
    assert eng.decode_steps == 3
    greedy_bytes = eng.B * 4
    for it in its:
        root, kids = _kids(it, "engine_step")
        assert root.attrs == {"n": it.n}
        by = {s.name: s for s in kids}
        launched = "decode_dispatch" in by
        # the plan says what the step it hands over runs
        assert by["plan"].attrs == {"batch": int(launched)}
        if launched:
            assert by["decode_dispatch"].attrs == {"batch": 1}
            _, sub = _kids(it, "decode_dispatch")
            assert [(s.name, s.attrs) for s in sub] == [
                ("upload", {"bytes": _sent(eng)}), ("launch", None)]
        if "logits_fetch" in by:
            assert by["logits_fetch"].attrs == {"bytes": greedy_bytes}
            assert by["pick"].attrs == {"sampled": 0}
            # no request trace was active at add_request: nothing to
            # stamp
            assert by["stamp"].attrs == {"traces": 0}
            _, sub = _kids(it, "logits_fetch")
            assert [(s.name, s.attrs) for s in sub] == [
                ("device_wait", None), ("copy", {"bytes": greedy_bytes})]


def test_seam_spans_frontend_and_request_traces(model):
    """Under a frontend ``deliver`` splits into the bookkeeping under
    the lock and the hand-over to the clients; ``stamp`` holds the
    per-request ``decode_step`` records, which still run from before
    the dispatch that launched the step to after the pick that took
    it (an iteration later: the step runs ahead)."""
    TRACER.enable()
    eng = _engine(model)
    fe = ServingFrontend(eng)
    hs = [fe.submit(_prompt(model, n), 4) for n in (6, 9)]
    _drain(fe)
    its = TRACER.timeline().iterations()
    stamped = 0
    launched = []           # steps on the device: (plan batch, dispatch)
    for it in its:
        _assert_tree(it)
        root, kids = _kids(it, "iteration")
        assert set(root.attrs) == {"n", "live", "queued"}
        assert [s.name for s in kids] == ["expire", "engine_step",
                                          "publish", "deliver"]
        deliver, kids = _kids(it, "deliver")
        assert set(deliver.attrs) == {"tokens", "finished"}
        assert [(s.name, s.attrs) for s in kids] == [("collect", None),
                                                     ("apply", None)]
        by = {s.name: s for s in it.spans}
        for sp in sorted((by[k] for k in ("decode_dispatch", "stamp")
                          if k in by), key=lambda s: s.t0):
            if sp.name == "decode_dispatch":
                launched.append((by["plan"].attrs["batch"], sp))
                continue
            batch, dispatch = launched.pop(0)
            assert sp.attrs == {"traces": batch}
            stamped += batch
            # what decode_step_ms reads (dispatch start to pick end)
            # lies inside the step as the requests' traces record it
            for h in hs:
                tr = h.trace
                for s in tr.snapshot():
                    if s.name == "decode_step" \
                            and sp.t0 <= tr.mono_t0 + s.t1 + 1e-9 \
                            and tr.mono_t0 + s.t1 <= sp.t1:
                        assert tr.mono_t0 + s.t0 <= dispatch.t0 + 1e-6
                        assert by["pick"].t1 <= tr.mono_t0 + s.t1 + 1e-6
    assert not launched
    assert stamped == eng.decode_slot_steps == sum(
        sum(s.name == "decode_step" for s in h.trace.snapshot())
        for h in hs)


def test_seam_spans_spec_decode_stamp(model):
    """The speculative branch has its own ``plan`` and ``stamp`` around
    ``spec_decode``; the requests' ``spec_decode_step`` records are what
    they were."""
    from paddle_tpu.spec_decode import SpecDecodeConfig
    TRACER.enable()
    eng = _engine(model, spec_config=SpecDecodeConfig(
        draft_cfg=model[0], draft_params=model[1], k=3, window=12))
    fe = ServingFrontend(eng)
    h = fe.submit(_prompt(model, 6), 5)
    _drain(fe)
    its = TRACER.timeline().iterations()
    spec = [it for it in its
            if any(s.name == "spec_decode" for s in it.spans)]
    assert len(spec) == eng.decode_steps > 0
    for it in spec:
        _assert_tree(it)
        _, kids = _kids(it, "engine_step")
        assert [s.name for s in kids] == ["retire", "admit", "retire",
                                          "plan", "spec_decode", "stamp"]
        assert kids[-3].attrs == {"batch": 1}
        assert kids[-1].attrs == {"traces": 1}
    mine = [s for s in h.trace.snapshot() if s.name == "spec_decode_step"]
    assert len(mine) == len(spec)
    assert [set(s.attrs) for s in mine] == [{"batch", "committed"}] * \
        len(mine)
    assert sum(s.attrs["committed"] for s in mine) == 4


@pytest.mark.parametrize("tracer_on", [False, True])
def test_seam_counters_and_the_untraced_path(model, monkeypatch, tracer_on):
    """``decode_fetch_bytes`` counts N steps x the greedy row's size
    (``B`` int32: the logits stay on the device) with the tracer on or
    off (what a step sends up is the ``upload``
    span's ``bytes``, timeline on only); off, the step path waits for
    the device nowhere but in the copy it always made (no
    ``block_until_ready``), and there is no timeline."""
    calls = []
    real = jax.block_until_ready

    def watched(x):
        calls.append(1)
        if not tracer_on:
            raise AssertionError("block_until_ready on the untraced path")
        return real(x)

    if tracer_on:
        TRACER.enable()
    eng = _engine(model, enable_prefix_caching=False)
    monkeypatch.setattr(jax, "block_until_ready", watched)
    for n in (6, 11):
        eng.add_request(_prompt(model, n), 5)
    eng.run_to_completion()
    n = eng.decode_steps
    assert n == 4
    assert eng.stats["decode_fetch_bytes"] == n * eng.B * 4
    assert len(calls) == (n if tracer_on else 0)
    if not tracer_on:
        assert TRACER.timeline() is None
        return
    fetched = [s.attrs["bytes"] for it in TRACER.timeline().iterations()
               for s in it.spans if s.name == "logits_fetch"]
    assert sum(fetched) == eng.stats["decode_fetch_bytes"]
    sent = [s.attrs["bytes"] for it in TRACER.timeline().iterations()
            for s in it.spans if s.name == "upload"]
    assert sent == [_sent(eng)] * n


def test_last_logits_fetched_on_demand(model, monkeypatch):
    """The Llama step leaves its logits on the device: read through
    ``last_logits`` after a step, they are the logits of the step the
    host took last, the same that the synchronous order fetches after
    that step, and the greedy row the rows were fed is their argmax."""
    ahead = _engine(model, enable_prefix_caching=False)
    sync = _engine(model, enable_prefix_caching=False)
    monkeypatch.setattr(sync, "_runs_ahead", lambda: False)
    for n in (6, 11):
        p = _prompt(model, n)
        ahead.add_request(p, 6)
        sync.add_request(p, 6)
    for _ in range(3):
        sync.step()
    for _ in range(4):                  # the fourth takes the third step
        ahead.step()
    assert ahead._inflight is not None
    assert not isinstance(ahead._last_logits, np.ndarray)
    got = ahead.last_logits
    assert isinstance(got, np.ndarray)
    assert got.shape == (ahead.B, model[0].vocab_size)
    np.testing.assert_array_equal(got, sync.last_logits)
    np.testing.assert_array_equal(got.argmax(-1), ahead.tokens)
    assert ahead.decode_steps == sync.decode_steps == 3


def test_trace_report_engine_mode(model, tmp_path, capsys):
    """``tools/trace_report.py --engine`` on the ``/v1/trace/engine``
    payload of a scripted run: the iteration's budget by phase, the one
    stalled iteration, the bucket fill and the prefill's chunk plan."""
    from paddle_tpu.models.llama import llama_tiny
    TRACER.enable()
    eng = ContinuousBatchingEngine(
        llama_tiny(max_position_embeddings=1024), model[1], max_batch=2,
        block_size=8, num_blocks=128, prefill_buckets=(128, 512),
        enable_prefix_caching=False)
    fe = ServingFrontend(eng)
    fe.submit(_prompt(model, 20), 12)
    fe.step()
    fe.submit(_prompt(model, 700), 2)
    _drain(fe)
    path = tmp_path / "engine.json"
    path.write_text(json.dumps(TRACER.timeline().to_dict()))
    assert trace_report.main(["--engine", str(path)]) == 0
    out = capsys.readouterr().out
    n = len(TRACER.timeline().iterations())
    assert out.startswith(f"{n} iterations (numbers 1-{n}, root iteration")
    assert f"were live: 1 of {n} =" in out
    assert f"decode steps 1 of {eng.decode_slot_steps} =" in out
    assert "useful / dispatched: 720 / 896 = 80.36%" in out
    assert "dispatching a chunk of 128: 3 times" in out
    rows = {ln.split()[0]: ln.split() for ln in out.splitlines()
            if len(ln.split()) == 7 and ln.split()[1].isdigit()}
    assert rows["prefill_chunk"][1:3] == ["4", "2"]      # spans, in_iters
    assert rows["iteration"][1:3] == [str(n), str(n)]
    assert any(ln.startswith("  512 + 128 + 128") and ln.split()[-3] == "1"
               for ln in out.splitlines())
    path.write_text(json.dumps({"dropped": 0, "iterations": []}))
    assert trace_report.main(["--engine", str(path)]) == 0
    assert "no iterations" in capsys.readouterr().out


# ---------------------------------------------------------------------
# static analysis: the tracing surface carries zero findings
# ---------------------------------------------------------------------
INSTRUMENTED = (
    "paddle_tpu/observability/tracing.py",
    "paddle_tpu/inference/serving.py",
    "paddle_tpu/serving/frontend.py",
    "paddle_tpu/serving/resilience.py",
    "paddle_tpu/serving/fleet.py",
    "paddle_tpu/serving/http.py",
    "paddle_tpu/serving/loadgen.py",
)


def test_tracing_has_zero_findings():
    """The ISSUE 20 lint pin: the tracing module and every instrumented
    serve file carry ZERO tracelint (TL) and locklint (LK) findings,
    and both committed ledgers stay EMPTY — tracing never added a
    silent broad except, a host-sync in traced code, or
    blocking-under-lock."""
    from paddle_tpu.analysis import baseline as baseline_mod
    from paddle_tpu.analysis import core
    from paddle_tpu.analysis.cli import default_paths
    select = {r.id for r in core.all_rules()
              if r.id.startswith(("TL", "LK"))}
    live = [f for f in core.run(default_paths(), select=select)
            if f.path in INSTRUMENTED]
    assert live == [], [f.format() for f in live]
    assert baseline_mod.load() == {}                       # tracelint
    assert baseline_mod.load(baseline_mod.locklint_path()) == {}
