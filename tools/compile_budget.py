"""COMPILE_BUDGET.md generator / recompile-budget ratchet (ISSUE 6).

* ``python tools/compile_budget.py``          — regenerate the ledger
  from the current per-scenario backend-compile counts (regenerating to
  ratchet DOWN is routine; growing a budget requires explanation in
  review).
* ``python tools/compile_budget.py --check``  — exit non-zero if any
  scenario compiles MORE than its committed budget; the pre-commit-style
  one-liner for the ratchet tests/test_compile_budget.py runs under
  pytest.
* ``--scenarios a,b`` restricts either mode; ``--inject N`` adds N
  synthetic compiles to every measured count (proves the ratchet trips —
  used by the tier-1 test and for CI smoke).

Each scenario runs one serving or training flow at CPU liveness shapes
and counts ``backend_compile`` events (observability.CompileMonitor) over
its WORKLOAD phase only — setup (weight init, AOT export) is excluded.
``serve_aot_warm`` is the acceptance scenario: an engine warm-started
from an AOT artifact directory must record ZERO backend compiles.

Counts are upper bounds: in-process runs (pytest) may measure fewer
compiles than the committed budget because earlier tests already
populated jax's op-by-op executable cache — the ratchet only fails on
MORE.
"""

from __future__ import annotations

import json
import os
import re
import sys
from typing import Callable, Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# standalone runs need the tier-1 virtual 8-device mesh (conftest.py sets
# the same flags for pytest) — `train_elastic_warm` reshapes a dp2 mesh.
# Must happen before the first jax import, i.e. before any scenario setup.
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
        " --xla_cpu_enable_concurrency_optimized_scheduler=false").strip()

LEDGER = os.path.join(REPO, "COMPILE_BUDGET.md")
MAGIC = "compile-budget v1"


# ---------------------------------------------------------------------
# scenarios: setup returns the workload callable; only the workload is
# measured
# ---------------------------------------------------------------------
def _tiny_llama():
    import jax
    import numpy as np
    from paddle_tpu import parallel as dist
    from paddle_tpu.models.llama import init_llama_params, llama_tiny
    from paddle_tpu.parallel.topology import HybridTopology, set_topology

    cfg = llama_tiny()
    topo = dist.init_topology(devices=jax.devices()[:1])
    params = init_llama_params(cfg, topo, 0)
    set_topology(HybridTopology())
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 9, 17)]
    return cfg, params, prompts


def _engine(cfg, params, aot_dir=None, spec=False):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    spec_config = None
    if spec:
        from paddle_tpu.spec_decode import SpecDecodeConfig
        spec_config = SpecDecodeConfig(draft_cfg=cfg, draft_params=params,
                                       k=3, window=12)
    return ContinuousBatchingEngine(
        cfg, params, max_batch=2, block_size=8, num_blocks=64,
        prefill_buckets=(8,), aot_dir=aot_dir, spec_config=spec_config)


def gpt_train() -> Callable[[], None]:
    """GPT train step, steady loop."""
    import jax
    import numpy as np
    from paddle_tpu import parallel as dist
    from paddle_tpu.models.gpt import GPTConfig, build_gpt_train_step

    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_position_embeddings=64)
    topo = dist.init_topology(devices=jax.devices()[:1])
    step_fn, init_fn = build_gpt_train_step(cfg, topo, num_microbatches=1)
    state = init_fn(0)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (2, 32)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)

    def workload():
        s, loss = state, None
        for _ in range(3):
            s, loss = step_fn(s, ids, labels)
        jax.device_get(loss)

    return workload


def serve_fresh() -> Callable[[], None]:
    """Serving at liveness shapes: cold engine start
    (the one-time q/k/v relayout, 13 -> 14 with ISSUE 31; decode step +
    one declared-bucket fill compile) + full drain.  14 -> 15 once the
    Llama family's steps launch ahead of their fetch: the merge of the
    host's tokens with the greedy row in flight.  The step still
    compiles once: these weights are committed to the train step's
    mesh, and the host's rows are uploaded with the placement the
    step's greedy row has."""
    cfg, params, prompts = _tiny_llama()

    def workload():
        eng = _engine(cfg, params)
        for p in prompts:
            eng.add_request(p, 4)
        eng.run_to_completion()

    return workload


def serve_aot_warm() -> Callable[[], None]:
    """The fleet-restart path: engine warm-started from an AOT artifact
    directory.  Budget is ZERO backend compiles — any compile here means
    warm start silently fell back to tracing."""
    import tempfile
    from paddle_tpu.aot.serve import export_engine

    cfg, params, prompts = _tiny_llama()
    aot_dir = tempfile.mkdtemp(prefix="aot_budget_")
    export_engine(_engine(cfg, params), aot_dir)

    def workload():
        eng = _engine(cfg, params, aot_dir=aot_dir)
        for p in prompts:
            eng.add_request(p, 4)
        eng.run_to_completion()
        if not eng.aot_loaded:
            raise RuntimeError(f"warm start fell back: {eng.aot_error}")

    return workload


def serve_aot_warm_sampled() -> Callable[[], None]:
    """Warm start + per-request sampling (ISSUE 7): the engine samples
    at the fixed decode width, so the single exported sampler program
    covers every sampled sub-batch — budget is ZERO like greedy."""
    import tempfile
    from paddle_tpu.aot.serve import export_engine

    cfg, params, prompts = _tiny_llama()
    aot_dir = tempfile.mkdtemp(prefix="aot_budget_sampled_")
    export_engine(_engine(cfg, params), aot_dir)

    def workload():
        eng = _engine(cfg, params, aot_dir=aot_dir)
        for i, p in enumerate(prompts):
            eng.add_request(p, 4, temperature=0.7, top_k=8, seed=i + 1)
        eng.run_to_completion()
        if not eng.aot_loaded:
            raise RuntimeError(f"warm start fell back: {eng.aot_error}")

    return workload


def serve_spec_warm() -> Callable[[], None]:
    """Speculative decode warm start (ISSUE 8): the draft and the
    fixed-width K+1 verify are exported next to the decode step, and
    the runner keeps every per-proposal op (argmax included) inside
    those programs — budget is ZERO backend compiles, like the other
    warm rows."""
    import tempfile
    from paddle_tpu.aot.serve import export_engine

    cfg, params, prompts = _tiny_llama()
    aot_dir = tempfile.mkdtemp(prefix="aot_budget_spec_")
    export_engine(_engine(cfg, params, spec=True), aot_dir)

    def workload():
        eng = _engine(cfg, params, aot_dir=aot_dir, spec=True)
        for i, p in enumerate(prompts):
            # one sampled request: spec rejection sampling must not
            # compile anything either
            eng.add_request(p, 4, temperature=0.7 if i == 0 else 0.0,
                            top_k=8 if i == 0 else None, seed=i + 1)
        eng.run_to_completion()
        if not eng.aot_loaded:
            raise RuntimeError(f"warm start fell back: {eng.aot_error}")
        if eng.spec_stats()["spec_steps"] < 1:
            raise RuntimeError("spec decode never ran — the scenario "
                               "is not measuring the speculative path")

    return workload


def serve_recovery_warm() -> Callable[[], None]:
    """Crash recovery on a warm fleet (ISSUE 11): a supervised engine
    built from an AOT-warm factory crashes mid-traffic, rebuilds, and
    replays every live request from its committed prefix.  Budget is
    ZERO backend compiles — the whole point of AOT-warm recovery is
    that a restart never pays tracing under traffic (replay prefills
    run on the deserialized bucketed fills, any prefix length)."""
    import tempfile
    from paddle_tpu.aot.serve import export_engine, warm_engine_factory
    from paddle_tpu.serving import RetryPolicy, SupervisedEngine

    cfg, params, prompts = _tiny_llama()
    aot_dir = tempfile.mkdtemp(prefix="aot_budget_recovery_")
    export_engine(_engine(cfg, params), aot_dir)
    factory = warm_engine_factory(cfg, params, aot_dir=aot_dir,
                                  max_batch=2, block_size=8,
                                  num_blocks=64)

    def workload():
        sup = SupervisedEngine(factory, policy=RetryPolicy(
            backoff_base_s=0.0), sleep=lambda s: None)
        for i, p in enumerate(prompts):
            # one sampled request: replay through the warm sampler too
            sup.add_request(p, 6, temperature=0.7 if i == 0 else 0.0,
                            top_k=8 if i == 0 else None, seed=i + 1)
        sup.step()
        sup.step()
        inner = sup.engine
        real = inner.step

        def crash_once():
            inner.step = real
            raise RuntimeError("injected crash (budget scenario)")

        inner.step = crash_once
        sup.run_to_completion()
        if sup.stats["recoveries"] != 1:
            raise RuntimeError("the scenario never exercised recovery")
        if not sup.engine.aot_loaded:
            raise RuntimeError(
                f"recovery rebuild fell back: {sup.engine.aot_error}")

    return workload


def fleet_warm() -> Callable[[], None]:
    """Fleet cold-start + chaos on warm replicas (ISSUE 12): an
    EngineRouter builds every replica from the same AOT artifact
    generation, serves greedy AND sampled traffic, loses a replica
    mid-stream (cross-replica re-placement replays on the survivor's
    deserialized programs), and gracefully drains another after a
    replacement joins.  Budget is ZERO backend compiles — fleet
    cold-start, death re-placement, and drain transplant must never
    trace under traffic."""
    import tempfile
    from paddle_tpu.aot.serve import export_engine, warm_engine_factory
    from paddle_tpu.serving import EngineRouter, RetryPolicy

    cfg, params, prompts = _tiny_llama()
    aot_dir = tempfile.mkdtemp(prefix="aot_budget_fleet_")
    export_engine(_engine(cfg, params), aot_dir)
    factory = warm_engine_factory(cfg, params, aot_dir=aot_dir,
                                  max_batch=2, block_size=8,
                                  num_blocks=64, prefill_buckets=(8,))

    def workload():
        router = EngineRouter(
            [factory, factory],
            policy=RetryPolicy(backoff_base_s=0.0),
            sleep=lambda s: None)
        rids = [router.add_request(
            p, 6, temperature=0.7 if i == 0 else 0.0,
            top_k=8 if i == 0 else None, seed=i + 1)
            for i, p in enumerate(prompts)]
        router.step()
        router.step()
        victim = next(r.replica for r in router._placements.values())
        router.kill_replica(victim, "budget scenario kill")
        router.step()
        survivor = next(r.idx for r in router.replicas if r.live)
        router.add_replica(factory)
        router.drain(survivor)
        res = router.run_to_completion()
        if set(res) != set(rids):
            raise RuntimeError("fleet scenario lost requests")
        if router.stats["deaths"] != 1 or router.stats["drains"] != 1:
            raise RuntimeError("fleet scenario never exercised "
                               "death + drain")
        for rep in router.replicas:
            if rep.live and not rep.sup.aot_loaded:
                raise RuntimeError("a fleet replica fell back to fresh "
                                   f"compiles: {rep.sup.aot_error}")

    return workload


def serve_http_warm() -> Callable[[], None]:
    """HTTP front door on a warm engine (ISSUE 13): server cold-start
    from AOT artifacts, greedy AND sampled traffic over real localhost
    sockets, one mid-stream client disconnect, and a graceful shutdown
    with a zero-leak report — ZERO backend compiles; the wire layer is
    host-side plumbing and must never trace."""
    import tempfile
    from paddle_tpu.aot.serve import export_engine

    cfg, params, prompts = _tiny_llama()
    aot_dir = tempfile.mkdtemp(prefix="aot_budget_http_")
    export_engine(_engine(cfg, params), aot_dir)

    def workload():
        import http.client
        import socket

        from paddle_tpu.serving import HttpServingServer, ServingFrontend
        from paddle_tpu.serving.http import iter_sse

        eng = _engine(cfg, params, aot_dir=aot_dir)
        fe = ServingFrontend(eng)
        srv = HttpServingServer(fe, heartbeat_s=0.02,
                                retry_grace_s=0.0).start()
        try:
            for i, p in enumerate(prompts[:2]):
                payload = {"prompt_ids": p.tolist(),
                           "max_new_tokens": 4}
                if i == 0:       # one sampled request through the
                    payload.update(temperature=0.7, top_k=8,
                                   seed=i + 1)  # warm sampler program
                conn = http.client.HTTPConnection(
                    srv.host, srv.port, timeout=120)
                conn.request("POST", "/v1/generate",
                             json.dumps(payload),
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                if resp.status != 200:
                    raise RuntimeError(f"generate failed: "
                                       f"{resp.status} {resp.read()}")
                events = [e for e, _ in iter_sse(resp)]
                conn.close()
                if "done" not in events:
                    raise RuntimeError(f"no terminal event: {events}")
            # one mid-stream client disconnect: read a few bytes of the
            # stream, vanish — the server must cancel and free
            body = json.dumps({"prompt_ids": prompts[2].tolist(),
                               "max_new_tokens": 16}).encode()
            s = socket.create_connection((srv.host, srv.port),
                                         timeout=30)
            s.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: b\r\n"
                      b"Content-Type: application/json\r\n"
                      b"Content-Length: " + str(len(body)).encode()
                      + b"\r\nConnection: close\r\n\r\n" + body)
            s.recv(256)
            s.close()
            report = srv.begin_shutdown(reason="budget scenario")
            if report["kv_leaked_blocks"] != 0:
                raise RuntimeError(f"leaked: {report}")
            if not eng.aot_loaded:
                raise RuntimeError(
                    f"warm start fell back: {eng.aot_error}")
        finally:
            srv._httpd.server_close()

    return workload


def serve_prefix_warm() -> Callable[[], None]:
    """Cross-request prefix cache on a warm engine (ISSUE 14):
    shared-prefix hits (suffix-only prefill through the declared
    buckets, greedy AND sampled), eviction under pool pressure into
    the host-RAM offload tier, and an offload restore by exact-byte
    scatter — ZERO backend compiles; every cache operation is
    host-side bookkeeping plus the pre-warmed pool-shaped copy op."""
    import tempfile

    import numpy as np

    from paddle_tpu.aot.serve import export_engine

    cfg, params, _prompts = _tiny_llama()
    aot_dir = tempfile.mkdtemp(prefix="aot_budget_prefix_")
    export_engine(_engine(cfg, params), aot_dir)

    def workload():
        from paddle_tpu.inference.serving import ContinuousBatchingEngine
        from paddle_tpu.serving.prefix_cache import PrefixCacheConfig

        eng = ContinuousBatchingEngine(
            cfg, params, max_batch=2, block_size=8, num_blocks=64,
            prefill_buckets=(8,), aot_dir=aot_dir,
            prefix_cache_config=PrefixCacheConfig(
                offload_capacity_bytes=1 << 24))
        rng = np.random.default_rng(5)
        shared = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
        tail = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
        eng.add_request(np.concatenate([shared, tail]), 4)
        eng.run_to_completion()              # registers the 2 blocks
        # shared-prefix hit, sampled: the warm sampler serves hits too
        eng.add_request(np.concatenate([shared, tail[:2]]), 4,
                        temperature=0.7, top_k=8, seed=3)
        eng.run_to_completion()
        if eng.prefix_stats()["hits"] < 1:
            raise RuntimeError("scenario never hit the prefix cache")
        # pool pressure: eviction must offload the cached prefix
        stolen = eng.alloc.acquire(eng.alloc.free_blocks)
        try:
            eng.add_request(
                rng.integers(0, cfg.vocab_size, (9,)).astype(np.int32),
                4)
            eng.run_to_completion()
        finally:
            eng.alloc.release(stolen)
        # offload restore: exact bytes scatter back, no recompute
        eng.add_request(np.concatenate([shared, tail]), 4)
        eng.run_to_completion()
        ps = eng.prefix_stats()
        if ps["offloads"] < 1 or ps["restores"] < 1:
            raise RuntimeError(
                f"scenario never offloaded/restored: {ps}")
        rep = eng.kv_leak_report()
        if rep["leaked"] or rep["unaccounted"]:
            raise RuntimeError(f"scenario leaked KV blocks: {rep}")
        if not eng.aot_loaded:
            raise RuntimeError(f"warm start fell back: {eng.aot_error}")

    return workload


def serve_prefill_warm() -> Callable[[], None]:
    """Chunked prefill on a warm engine (ISSUE 18): an export
    warm-starts an engine that serves bucketed fills at several prompt
    lengths (greedy AND sampled), a prefix-cache hit running ONLY the
    suffix through the chunk fill, and one explicit preempt/restore —
    ZERO backend compiles."""
    import tempfile

    import numpy as np

    from paddle_tpu.aot.serve import export_engine
    from paddle_tpu.inference.serving import ContinuousBatchingEngine

    cfg, params, prompts = _tiny_llama()
    aot_dir = tempfile.mkdtemp(prefix="aot_budget_prefill_")
    export_engine(_engine(cfg, params), aot_dir)

    def workload():
        from paddle_tpu.serving.prefix_cache import PrefixCacheConfig

        eng = ContinuousBatchingEngine(
            cfg, params, max_batch=2, block_size=8, num_blocks=64,
            prefill_buckets=(8,), aot_dir=aot_dir,
            prefix_cache_config=PrefixCacheConfig(
                offload_capacity_bytes=1 << 24))
        rng = np.random.default_rng(18)
        # bucketed fills: single-chunk and multi-chunk prompt lengths
        for i, p in enumerate(prompts):
            eng.add_request(p, 4, temperature=0.7 if i == 1 else 0.0,
                            top_k=8 if i == 1 else None, seed=i)
        eng.run_to_completion()
        # prefix-cache hit: ONLY the suffix runs through the chunk fill
        shared = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
        tail = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
        eng.add_request(np.concatenate([shared, tail]), 4)
        eng.run_to_completion()
        eng.add_request(np.concatenate([shared, tail[:3]]), 4)
        eng.run_to_completion()
        if eng.prefix_stats()["hits"] < 1:
            raise RuntimeError("scenario never hit the prefix cache")
        # one preempt/restore: the replay prefill re-runs the committed
        # prefix through the same warm bucketed fills
        eng.add_request(prompts[2], 6)
        eng.step()
        eng.preempt(0)
        eng.run_to_completion()
        rs = eng.resilience_stats()
        if rs["preemptions"] < 1 or rs["restores"] < 1:
            raise RuntimeError(
                f"scenario never preempted/restored: {rs}")
        rep = eng.kv_leak_report()
        if rep["leaked"] or rep["unaccounted"]:
            raise RuntimeError(f"scenario leaked KV blocks: {rep}")
        if not eng.aot_loaded:
            raise RuntimeError(f"warm start fell back: {eng.aot_error}")

    return workload


def serve_trace_warm() -> Callable[[], None]:
    """End-to-end request tracing on a warm engine (ISSUE 20): the
    span tracer enabled around greedy, sampled, shared-prefix-hit and
    preempt/restore traffic through the streaming frontend — ZERO
    backend compiles.  Every span is host-side monotonic-clock
    bookkeeping; turning tracing on must never change what the
    accelerator executes."""
    import tempfile

    import numpy as np

    from paddle_tpu.aot.serve import export_engine

    cfg, params, prompts = _tiny_llama()
    aot_dir = tempfile.mkdtemp(prefix="aot_budget_trace_")
    export_engine(_engine(cfg, params), aot_dir)

    def workload():
        from paddle_tpu.inference.serving import ContinuousBatchingEngine
        from paddle_tpu.observability.tracing import TRACER
        from paddle_tpu.serving import AdmissionConfig, ServingFrontend
        from paddle_tpu.serving.prefix_cache import PrefixCacheConfig

        eng = ContinuousBatchingEngine(
            cfg, params, max_batch=2, block_size=8, num_blocks=64,
            prefill_buckets=(8,), aot_dir=aot_dir,
            prefix_cache_config=PrefixCacheConfig())
        fe = ServingFrontend(
            eng, admission=AdmissionConfig(max_queue_len=64))
        TRACER.enable()
        TRACER.reset()
        try:
            rng = np.random.default_rng(20)
            shared = rng.integers(0, cfg.vocab_size,
                                  (16,)).astype(np.int32)
            tail = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
            h1 = fe.submit(np.concatenate([shared, tail]), 4)
            while not h1.state.terminal:
                fe.step()                    # registers the prefix
            # shared-prefix hit + a sampled request, both traced
            h2 = fe.submit(np.concatenate([shared, tail[:2]]), 4)
            h3 = fe.submit(tail, 4, temperature=0.7, top_k=8, seed=3)
            while not (h2.state.terminal and h3.state.terminal):
                fe.step()
            # one preempt/restore mid-traffic: spill + restore spans
            h4 = fe.submit(prompts[2], 6)
            fe.step()
            eng.preempt(next(s for s in range(eng.B)
                             if eng.slots[s] is not None))
            while not h4.state.terminal:
                fe.step()
            if eng.prefix_stats()["hits"] < 1:
                raise RuntimeError("scenario never hit the prefix cache")
            if eng.resilience["restores"] < 1:
                raise RuntimeError("scenario never restored a preempted "
                                   "request")
            done = TRACER.done_traces()
            if len(done) != 4:
                raise RuntimeError(
                    f"expected 4 finished traces, got {len(done)}")
            names = {s.name for t in done for s in t.snapshot()}
            for need in ("queue_wait", "prefill", "decode_step",
                         "preempt_spill", "preempt_restore"):
                if need not in names:
                    raise RuntimeError(f"no {need} span traced: {names}")
            rep = eng.kv_leak_report()
            if rep["leaked"] or rep["unaccounted"]:
                raise RuntimeError(f"scenario leaked KV blocks: {rep}")
            if not eng.aot_loaded:
                raise RuntimeError(
                    f"warm start fell back: {eng.aot_error}")
        finally:
            TRACER.disable()
            TRACER.reset()

    return workload


def serve_quant_warm() -> Callable[[], None]:
    """Quantized serving on a warm engine (ISSUE 16): int8 weight-only
    matmuls + int8 paged-KV pool (per-token scales), warm-started from
    an AOT artifact exported at the SAME quant config — greedy AND
    sampled traffic, a shared-prefix cache hit, and one priority
    preempt/restore cycle through the quantized spill format.  ZERO
    backend compiles: dequant runs inside the exported programs and
    every spill/restore copy is the pool-shaped op pre-warmed at
    construction."""
    import tempfile

    import numpy as np

    from paddle_tpu.aot.serve import export_engine
    from paddle_tpu.quantization import ServeQuantConfig

    cfg, params, _prompts = _tiny_llama()
    qc = ServeQuantConfig(weight_dtype="int8", kv_dtype="int8")

    def build(aot_dir=None):
        from paddle_tpu.inference.serving import ContinuousBatchingEngine
        return ContinuousBatchingEngine(
            cfg, params, max_batch=2, block_size=8, num_blocks=64,
            prefill_buckets=(8,), aot_dir=aot_dir, quant_config=qc)

    aot_dir = tempfile.mkdtemp(prefix="aot_budget_quant_")
    export_engine(build(), aot_dir)

    def workload():
        eng = build(aot_dir=aot_dir)
        rng = np.random.default_rng(7)
        shared = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
        tail = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
        eng.add_request(np.concatenate([shared, tail]), 4)
        eng.run_to_completion()             # registers the prefix
        # shared-prefix hit + a sampled request, both on int8 KV pages
        eng.add_request(np.concatenate([shared, tail[:2]]), 4)
        eng.add_request(tail, 6, temperature=0.7, top_k=8, seed=3)
        eng.step()
        # one preempt/restore through the quantized (codes + scales)
        # spill format mid-traffic
        slot = next(s for s in range(eng.B)
                    if eng.slots[s] is not None)
        eng.preempt(slot)
        eng.run_to_completion()
        if eng.prefix_stats()["hits"] < 1:
            raise RuntimeError("scenario never hit the prefix cache")
        if eng.resilience["restores"] < 1:
            raise RuntimeError("scenario never restored a preempted "
                               "request")
        rep = eng.kv_leak_report()
        if rep["leaked"] or rep["unaccounted"]:
            raise RuntimeError(f"scenario leaked KV blocks: {rep}")
        if not eng.aot_loaded:
            raise RuntimeError(f"warm start fell back: {eng.aot_error}")

    return workload


def train_elastic_warm() -> Callable[[], None]:
    """Elastic-training warm rebuild (ISSUE 17): an ElasticTrainer
    resumed at a previously-seen mesh loads its per-topology AOT entry
    — then survives a worker kill whose survivor mesh has ALSO been
    seen.  Budget is ZERO backend compiles for BOTH: the same-topology
    resume and the reshape onto an already-exported survivor entry.
    Setup pays the two bounded cold exports (dp2, then the dp1
    survivor mesh via an injected loss); the workload replays the whole
    resume-kill-reshape-continue sequence warm."""
    import tempfile

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import nn
    from paddle_tpu.parallel import ElasticTrainer, WorkerLostError
    from paddle_tpu.parallel.topology import HybridTopology, set_topology

    def data_fn(step):
        r = np.random.default_rng(1000 + step)
        return (r.standard_normal((12, 16)).astype("float32"),
                r.integers(0, 4, (12,)).astype("int64"))

    def make_trainer(aot_dir):
        topo = HybridTopology(dp=2)
        set_topology(topo)
        pt.seed(11)
        net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                            nn.Linear(32, 4))
        opt = pt.optimizer.Adam(parameters=net.parameters(),
                                learning_rate=1e-2)
        return ElasticTrainer(net, opt, nn.CrossEntropyLoss(), data_fn,
                              topology=topo, sharding_stage=2,
                              rng_seed=7, aot_dir=aot_dir)

    def kill_and_continue(tr):
        eng, real = tr.engine, tr.engine.train_batch
        fired = [0]

        def patched(inputs, labels=None, rng=None):
            if eng._step_count == 2 and not fired[0]:
                fired[0] = 1
                raise WorkerLostError("injected device loss",
                                      lost_index=1, axis="dp")
            return real(inputs, labels, rng=rng)

        eng.train_batch = patched
        tr.run(2)                    # step 2 killed → dp1, steps 2,3

    aot_dir = tempfile.mkdtemp(prefix="aot_budget_elastic_")
    try:
        tr = make_trainer(aot_dir)   # cold: exports the dp2 entry,
        tr.run(2)                    # then the dp1 survivor entry
        kill_and_continue(tr)
    finally:
        set_topology(HybridTopology())

    def workload():
        try:
            tr = make_trainer(aot_dir)
            tr.run(2)                # warm same-topology resume
            kill_and_continue(tr)    # reshape onto the seen survivor
            if tr.reshapes != 1 or tr.topo.world_size != 1:
                raise RuntimeError(
                    f"scenario never reshaped: reshapes={tr.reshapes} "
                    f"world_size={tr.topo.world_size}")
        finally:
            set_topology(HybridTopology())

    return workload


SCENARIOS: Dict[str, Callable[[], Callable[[], None]]] = {
    "gpt_train": gpt_train,
    "serve_fresh": serve_fresh,
    "serve_aot_warm": serve_aot_warm,
    "serve_aot_warm_sampled": serve_aot_warm_sampled,
    "serve_spec_warm": serve_spec_warm,
    "serve_recovery_warm": serve_recovery_warm,
    "fleet_warm": fleet_warm,
    "serve_http_warm": serve_http_warm,
    "serve_prefix_warm": serve_prefix_warm,
    "serve_prefill_warm": serve_prefill_warm,
    "serve_trace_warm": serve_trace_warm,
    "serve_quant_warm": serve_quant_warm,
    "train_elastic_warm": train_elastic_warm,
}


def measure(names: Optional[List[str]] = None,
            inject: int = 0) -> Dict[str, int]:
    """Run scenarios (fixed declaration order) and return their
    backend-compile counts; ``inject`` adds synthetic compiles to every
    count (ratchet self-test)."""
    from paddle_tpu.observability import CompileMonitor

    out: Dict[str, int] = {}
    for name, setup in SCENARIOS.items():
        if names is not None and name not in names:
            continue
        workload = setup()
        monitor = CompileMonitor()
        monitor.install()
        try:
            workload()
        finally:
            monitor.uninstall()
        out[name] = monitor.n_compiles + inject
    return out


# ---------------------------------------------------------------------
# ledger
# ---------------------------------------------------------------------
def render_md(counts: Dict[str, int]) -> str:
    lines = [
        "# compile budget",
        "",
        "Per-scenario backend-compile budgets "
        "(`tools/compile_budget.py`); the ratchet "
        "(`tests/test_compile_budget.py`, or `python "
        "tools/compile_budget.py --check`) fails when any scenario "
        "COMPILES MORE than its committed budget — recompile "
        "regressions (shape churn, cache bugs, a warm start silently "
        "tracing) fail loudly instead of shipping as latency.",
        "",
        "Budgets are CPU tier-1 numbers; `serve_aot_warm` is the ISSUE 6"
        " acceptance row, `serve_aot_warm_sampled` the ISSUE 7 one, "
        "`serve_spec_warm` the ISSUE 8 one, `serve_recovery_warm` the "
        "ISSUE 11 one, `fleet_warm` the ISSUE 12 one, "
        "`serve_http_warm` the ISSUE 13 one, `serve_prefix_warm` the "
        "ISSUE 14 one, and `serve_quant_warm` the ISSUE 16 one: an "
        "AOT-warm engine start must be ZERO backend compiles — greedy, "
        "sampled, speculative, rebuilt mid-traffic by crash recovery "
        "(replay included), serving as a fleet replica through a "
        "replica kill, cross-replica re-placement, and a graceful "
        "drain, serving real sockets through the HTTP front door with "
        "a mid-stream disconnect and a graceful shutdown, serving "
        "shared-prefix traffic through the cross-request prefix cache "
        "with hits, an eviction-to-offload, and an offload restore, "
        "serving int8-quantized weights and KV pages end-to-end with a "
        "preempt/restore through the codes+scales spill format, or — "
        "`serve_prefill_warm`, the ISSUE 18 row — serving the "
        "chunked-prefill path through bucketed fills, a "
        "prefix-cache suffix fill, and a preempt/restore.  "
        "`serve_trace_warm` is the ISSUE 20 row: the request span "
        "tracer enabled around greedy, sampled, prefix-hit and "
        "preempt/restore traffic adds zero backend compiles — spans "
        "are host-side bookkeeping, never a shape change.  "
        "`train_elastic_warm` is the ISSUE 17 training-side row: an "
        "elastic trainer resumed at a previously-seen mesh — and "
        "reshaped by a worker kill onto an already-exported survivor "
        "mesh — performs zero backend compiles for both transitions.",
        "",
    ]
    for name, n in counts.items():
        doc = (SCENARIOS[name].__doc__ or "").strip().split("\n")[0]
        lines.append(f"- `{name}`: **{n}** backend compiles — {doc}")
    lines += [
        "",
        f"<!-- {MAGIC}",
        json.dumps({"platform": _platform(), "budgets": counts},
                   sort_keys=True),
        "-->",
        "",
    ]
    return "\n".join(lines)


def _platform() -> str:
    import jax
    return jax.default_backend()


def load_ledger() -> Dict:
    with open(LEDGER, encoding="utf-8") as f:
        text = f.read()
    m = re.search(rf"<!-- {re.escape(MAGIC)}\n(.*?)\n-->", text, re.S)
    if m is None:
        raise ValueError(f"{LEDGER}: no '{MAGIC}' machine block")
    return json.loads(m.group(1))


def compare(measured: Dict[str, int], ledger: Dict) -> List[str]:
    budgets = ledger.get("budgets", {})
    regressions = []
    for name, n in sorted(measured.items()):
        if name not in budgets:
            regressions.append(f"{name}: no committed budget (measured "
                               f"{n}) — regenerate the ledger")
        elif n > budgets[name]:
            regressions.append(f"{name}: {n} backend compiles > budget "
                               f"{budgets[name]}")
    return regressions


# ---------------------------------------------------------------------
def generate(names: Optional[List[str]]) -> int:
    if names is not None:
        print("refusing to regenerate a PARTIAL ledger (--scenarios is "
              "--check-only)")
        return 1
    counts = measure()
    with open(LEDGER, "w", encoding="utf-8") as f:
        f.write(render_md(counts))
    print(f"wrote {os.path.relpath(LEDGER, REPO)}: {counts}")
    return 0


def check(names: Optional[List[str]], inject: int) -> int:
    try:
        ledger = load_ledger()
    except (OSError, ValueError) as e:
        print(f"BUDGET FAIL: cannot load ledger: {e}")
        return 1
    if ledger.get("platform") != _platform():
        print(f"budget SKIP: ledger is for platform "
              f"{ledger.get('platform')!r}, this is {_platform()!r} "
              "(the ratchet is a CPU tier-1 gate)")
        return 0
    measured = measure(names, inject=inject)
    regressions = compare(measured, ledger)
    if regressions:
        print(f"BUDGET FAIL: {len(regressions)} scenario(s) above the "
              "committed COMPILE_BUDGET.md:")
        for r in regressions:
            print(f"  {r}")
        print("find the new compile (CompileMonitor per-label counts "
              "attribute it), or — with reviewer sign-off — regenerate "
              "via `python tools/compile_budget.py`.")
        return 1
    print(f"budget OK: {measured} at or below budget")
    return 0


def main(argv: List[str]) -> int:
    names: Optional[List[str]] = None
    inject = 0
    if "--scenarios" in argv:
        names = [s for s in
                 argv[argv.index("--scenarios") + 1].split(",") if s]
        unknown = set(names) - set(SCENARIOS)
        if unknown:
            print(f"unknown scenarios: {sorted(unknown)} "
                  f"(have {sorted(SCENARIOS)})")
            return 1
    if "--inject" in argv:
        inject = int(argv[argv.index("--inject") + 1])
    if "--check" in argv:
        return check(names, inject)
    return generate(names)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
