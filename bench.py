"""Benchmark harness — prints ONE JSON line:
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Measures GPT causal-LM training throughput (tokens/sec/chip) and MFU on
the process's JAX backend.  vs_baseline is MFU / 0.45 (the north-star
>= 45% MFU target), since the reference publishes no absolute numbers.

The parent process stays off JAX and runs ONE measurement child under a
wall-clock watchdog (a chip belongs to one process at a time).  There is
no CPU fallback: a backend that fails to initialise fails the row.  A
row measured on an explicitly chosen CPU backend (``JAX_PLATFORMS=cpu``,
liveness shapes) is stamped ``"fallback": true`` — it is never hardware
evidence.  (ROADMAP S0 replaces this file with a benchmark of cells.)
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

import numpy as np

# process-start origin for the cold-start metrics (bench.py is __main__
# in the measurement child, so this runs before jax/framework imports —
# TTFT/time-to-first-step "from process start" includes import+init cost)
_PROC_T0 = time.perf_counter()


def peak_flops_per_chip(device) -> float:
    """bf16 peak FLOP/s for the local accelerator (single source of
    truth: observability/hw.py — Model.fit's MFU telemetry uses the
    same table)."""
    from paddle_tpu.observability.hw import peak_flops_per_chip as _pf
    return _pf(device)


def _layer_train_bench(net, x, y, steps: int, items_per_step: float,
                       unit: str, metric: str, devices):
    """Measure a jitted functional AdamW train step over an eager Layer
    (the Model.fit compute path, jit-compiled once).  The update runs
    through the optimizer's FUSED multi-tensor apply (one bucketed kernel
    per dtype group, flat moments donated in place) and the input batch
    is staged host→device by the io device-prefetch pipeline."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nn import functional_call_with_buffers, state_arrays
    from paddle_tpu.nn import functional as F
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.io import device_prefetch_iterator
    import paddle_tpu as pt

    # differentiate ONLY trainable params; buffers (BN running stats)
    # thread through the aux output, never through Adam
    params = state_arrays(net, trainable_only=True)
    buffers = {k: v for k, v in state_arrays(net).items()
               if k not in params}
    opt = AdamW(learning_rate=1e-3, weight_decay=0.0)

    import functools

    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def step(params, buffers, opt_state, step_no, xv, yv):
        def loss_fn(p):
            logits, new_buf = functional_call_with_buffers(
                net, {**buffers, **p}, pt.Tensor(xv))
            loss = F.cross_entropy(logits, pt.Tensor(yv))
            return getattr(loss, "_value", loss).astype(jnp.float32), \
                new_buf

        (loss, new_buf), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        new_p, new_state = opt.apply_gradients_fused(
            params, grads, opt_state, 1e-3, step_no)
        new_buffers = {k: new_buf.get(k, val)
                       for k, val in buffers.items()}
        return new_p, new_buffers, new_state, loss

    opt_state = opt.init_state(params)
    params, buffers, opt_state, loss = step(params, buffers, opt_state,
                                            1, x, y)   # compile (1/2)
    # second compile: opt_state is now in fused (flat) form
    params, buffers, opt_state, loss = step(params, buffers, opt_state,
                                            2, x, y)

    jax.device_get(loss)
    t0 = time.perf_counter()
    sn = 3
    for xv, yv in device_prefetch_iterator([(x, y)] * steps, size=2):
        params, buffers, opt_state, loss = step(params, buffers,
                                                opt_state, sn, xv, yv)
        sn += 1
    loss_val = float(np.asarray(jax.device_get(loss)))
    dt = time.perf_counter() - t0
    rate = items_per_step * steps / dt
    return {
        "metric": metric, "value": round(rate, 1), "unit": unit,
        "vs_baseline": 0.0,   # no reference-published number
        "extra": {"steps": steps, "loss": loss_val,
                  "optimizer_fused": True, "device_prefetch": True,
                  "device": str(devices[0])},
    }


def _serve_aot_warm_extra(cfg, params, eng, ttft_cold, *, mb, nb, t0,
                          new, rng, aot_dir_out=None):
    """Cold-vs-warm start measurement for the serve row (ISSUE 6):
    export the engine's compile artifacts, warm-start a second engine
    from them, and report TTFT + backend-compile counts + bucket
    hit/miss for both.  ``aot_dir_out`` (a dict) receives the export
    directory so later rows (extra.resilience) reuse the artifacts
    instead of re-exporting.  Never fails the row — errors land in
    extra.aot_error."""
    try:
        import tempfile
        from paddle_tpu.aot.serve import export_engine
        from paddle_tpu.inference.serving import ContinuousBatchingEngine
        from paddle_tpu.observability import CompileMonitor

        aot_dir = tempfile.mkdtemp(prefix="bench_aot_serve_")
        export_engine(eng, aot_dir)
        if aot_dir_out is not None:
            aot_dir_out["dir"] = aot_dir
        monitor = CompileMonitor().install()
        try:
            t_w = time.perf_counter()
            weng = ContinuousBatchingEngine(
                cfg, params, max_batch=mb, block_size=16,
                num_blocks=nb, aot_dir=aot_dir)
            weng.add_request(
                rng.integers(0, cfg.vocab_size, (t0,)).astype(np.int32),
                new)
            weng.step()                      # first token produced
            ttft_warm = time.perf_counter() - t_w
        finally:
            monitor.uninstall()
        return {"aot_warm": {
            "loaded": weng.aot_loaded,
            "ttft_cold_from_proc_start_s": round(ttft_cold, 3),
            "ttft_warm_engine_start_s": round(ttft_warm, 3),
            "warm_backend_compiles": monitor.n_compiles,
            "cold": eng.aot_stats(),          # bucket hits/misses, cold
            "warm": weng.aot_stats(),
        }}
    except Exception as e:
        return {"aot_error": f"{type(e).__name__}: {e}"}


def _serve_loadgen_extra(eng, on_accel, *, t0, new):
    """Poisson-load row for the serve config (ISSUE 7): open-loop
    seeded arrivals through the streaming front-end, reporting p50/p99
    TTFT, per-output-token latency, tokens/s, goodput-under-SLO, and
    the zero-leak check.  Reuses the drained (compile-warm) engine so
    the row measures the serve loop, not tracing.  Never fails the row —
    errors land in extra.loadgen_error."""
    try:
        from paddle_tpu.serving import (AdmissionConfig, LoadGenConfig,
                                        PoissonLoadGenerator,
                                        ServingFrontend)

        if on_accel:
            lg = LoadGenConfig(n_requests=32, rate_rps=8.0, seed=0,
                               prompt_len=(t0 // 4, t0),
                               max_new_tokens=(new // 3, new),
                               sampled_fraction=0.25,
                               cancel_fraction=0.1,
                               slo_ttft_s=2.0, slo_tpot_s=0.25)
        else:
            lg = LoadGenConfig(n_requests=16, rate_rps=100.0, seed=0,
                               prompt_len=(3, t0),
                               max_new_tokens=(3, new),
                               sampled_fraction=0.25,
                               cancel_fraction=0.1,
                               slo_ttft_s=5.0, slo_tpot_s=1.0)
        fe = ServingFrontend(eng,
                             admission=AdmissionConfig(max_queue_len=64))
        report = PoissonLoadGenerator(fe, lg).run()
        return {"loadgen": report.to_dict()}
    except Exception as e:
        return {"loadgen_error": f"{type(e).__name__}: {e}"}


def _serve_spec_extra(cfg, params, eng_off, *, mb, nb, on_accel, t0,
                      new):
    """Speculative-decode A/B for the serve row (ISSUE 8): the same
    seeded Poisson load (mid-stream cancels included) through a
    speculating engine and the drained baseline engine.  Reports
    acceptance rate, per-slot engine-steps-per-token (baseline == 1.0
    by construction; < 1.0 is the speculation win), tokens/s both ways,
    rollback pages, and the zero-leak check.  The draft here is the
    target model itself (self-draft, window-limited) — the honest
    upper-band acceptance a same-family small draft approaches.  Never
    fails the row — errors land in extra.spec_error."""
    try:
        from paddle_tpu.inference.serving import ContinuousBatchingEngine
        from paddle_tpu.serving import (AdmissionConfig, LoadGenConfig,
                                        PoissonLoadGenerator,
                                        ServingFrontend)
        from paddle_tpu.spec_decode import SpecDecodeConfig

        lg = LoadGenConfig(
            n_requests=16 if not on_accel else 32,
            rate_rps=100.0 if not on_accel else 8.0, seed=1,
            prompt_len=(3, t0), max_new_tokens=(3, new),
            sampled_fraction=0.25, cancel_fraction=0.15,
            slo_ttft_s=60.0, slo_tpot_s=30.0)
        spec_eng = ContinuousBatchingEngine(
            cfg, params, max_batch=mb, block_size=16, num_blocks=nb,
            prefill_buckets=(t0,),
            spec_config=SpecDecodeConfig(draft_cfg=cfg,
                                         draft_params=params,
                                         k=3, window=16))
        # compile-warm the draft/verify programs so the row measures
        # the serve loop, not tracing (same convention as the loadgen
        # row reusing the drained engine)
        spec_eng.add_request(np.arange(1, t0 + 1, dtype=np.int32), 4)
        spec_eng.run_to_completion()
        fe_on = ServingFrontend(spec_eng,
                                admission=AdmissionConfig(max_queue_len=64))
        rep_on = PoissonLoadGenerator(fe_on, lg).run()
        fe_off = ServingFrontend(eng_off,
                                 admission=AdmissionConfig(max_queue_len=64))
        rep_off = PoissonLoadGenerator(fe_off, lg).run()
        stats = spec_eng.spec_stats()
        return {"spec": {
            "k": stats["k"],
            "acceptance_rate": None if stats["acceptance_rate"] is None
            else round(stats["acceptance_rate"], 4),
            "engine_steps_per_token": None
            if stats["engine_steps_per_token"] is None
            else round(stats["engine_steps_per_token"], 4),
            "rollback_pages": stats["rollback_pages"],
            "tokens_per_s_spec_on": rep_on.to_dict()["tokens_per_s"],
            "tokens_per_s_spec_off": rep_off.to_dict()["tokens_per_s"],
            "kv_leaked_blocks": rep_on.to_dict()["kv_leaked_blocks"],
            # the CPU proxy is COMPUTE-bound and the self-draft costs as
            # much as the target per call, so spec-on wall clock loses
            # here even as steps-per-token wins; the wall-clock flip
            # needs a genuinely small draft on dispatch-latency-bound
            # hardware (docs/spec_decode.md)
            "note": "self-draft CPU proxy: steps/token is the signal, "
                    "wall-clock favors spec only with a small draft "
                    "on accelerators",
        }}
    except Exception as e:
        return {"spec_error": f"{type(e).__name__}: {e}"}


def _serve_resilience_extra(cfg, params, *, mb, nb, on_accel, t0, new,
                            aot_dir):
    """Resilience row for the serve config (ISSUE 11), all on
    compile-warm engines (reusing the artifacts the aot_warm row
    exported): crash-recovery time-to-resume (AOT-warm rebuild +
    replay, zero backend compiles — the serve_recovery_warm budget
    row), preemption spill/restore seconds, and high-priority goodput
    with vs without injected chaos.  Never fails the row — errors land
    in extra.resilience_error."""
    try:
        from paddle_tpu.aot.serve import warm_engine_factory
        from paddle_tpu.observability import CompileMonitor
        from paddle_tpu.serving import (AdmissionConfig, LoadGenConfig,
                                        PoissonLoadGenerator,
                                        RetryPolicy, ServingFrontend,
                                        SupervisedEngine)

        if aot_dir is None:
            raise RuntimeError("no AOT artifacts from the aot_warm row")
        rng = np.random.default_rng(3)
        factory = warm_engine_factory(cfg, params, aot_dir=aot_dir,
                                      max_batch=mb, block_size=16,
                                      num_blocks=nb)

        # -- crash-recovery time-to-resume on a warm fleet ------------
        sup = SupervisedEngine(factory,
                               policy=RetryPolicy(backoff_base_s=0.0),
                               sleep=lambda s: None)
        for i in range(min(3, mb + 1)):
            sup.add_request(
                rng.integers(0, cfg.vocab_size, (t0,)).astype(np.int32),
                new, temperature=0.7 if i == 0 else 0.0,
                top_k=8 if i == 0 else None, seed=i + 1)
        sup.step()
        sup.step()
        inner, real = sup.engine, sup.engine.step

        def crash_once():
            inner.step = real
            raise RuntimeError("bench-injected crash")

        inner.step = crash_once
        monitor = CompileMonitor().install()
        try:
            t_c = time.perf_counter()
            sup.step()                    # teardown + rebuild + replay
            sup.step()                    # first post-recovery tokens
            time_to_resume = time.perf_counter() - t_c
        finally:
            monitor.uninstall()
        recovery_compiles = monitor.n_compiles
        sup.run_to_completion()

        # -- preemption save/restore under forced page pressure -------
        small = factory()                 # warm engine, tight by theft
        small.add_request(
            rng.integers(0, cfg.vocab_size, (t0,)).astype(np.int32),
            new, priority=0)
        small.step()
        stolen = small.alloc.acquire(small.alloc.free_blocks)
        try:
            small.add_request(
                rng.integers(0, cfg.vocab_size, (t0,)).astype(np.int32),
                new, priority=5)
            small.step()                  # saturated: must preempt
        finally:
            if stolen:
                small.alloc.release(stolen)
        small.run_to_completion()
        pstats = small.resilience_stats()

        # -- high-priority goodput: chaos A/B -------------------------
        lg = LoadGenConfig(
            n_requests=12 if not on_accel else 32,
            rate_rps=100.0 if not on_accel else 8.0, seed=4,
            prompt_len=(3, t0), max_new_tokens=(3, new),
            sampled_fraction=0.25, cancel_fraction=0.1,
            priorities=(0, 10), priority_weights=(0.6, 0.4),
            slo_ttft_s=5.0 if not on_accel else 2.0,
            slo_tpot_s=1.0 if not on_accel else 0.25)

        def run_chaos(chaos):
            s = SupervisedEngine(
                factory, policy=RetryPolicy(backoff_base_s=0.0),
                sleep=lambda x: None)
            fe = ServingFrontend(
                s, admission=AdmissionConfig(max_queue_len=64))
            if chaos:
                eng, step = s.engine, s.engine.step
                state = {"n": 0}

                def flaky():
                    state["n"] += 1
                    if state["n"] == 5:
                        raise RuntimeError("bench chaos crash")
                    return step()

                eng.step = flaky
            rep = PoissonLoadGenerator(fe, lg).run()
            return rep, s

        rep_chaos, s_chaos = run_chaos(True)
        rep_calm, _ = run_chaos(False)
        hi_chaos = (rep_chaos.by_priority or {}).get(10, {})
        hi_calm = (rep_calm.by_priority or {}).get(10, {})
        return {"resilience": {
            "recovery_time_to_resume_s": round(time_to_resume, 4),
            "recovery_backend_compiles": recovery_compiles,
            "recoveries": sup.stats["recoveries"],
            "replayed_requests": sup.stats["replayed_requests"],
            "preemptions": pstats["preemptions"],
            "restores": pstats["restores"],
            "preempt_save_secs": round(pstats["spill_save_secs"], 4),
            "preempt_restore_secs": round(
                pstats["spill_restore_secs"], 4),
            "hi_goodput_rps_chaos": hi_chaos.get("goodput_rps"),
            "hi_goodput_rps_calm": hi_calm.get("goodput_rps"),
            "chaos_recoveries": s_chaos.stats["recoveries"],
            "chaos_kv_leaked_blocks":
                rep_chaos.to_dict()["kv_leaked_blocks"],
        }}
    except Exception as e:
        return {"resilience_error": f"{type(e).__name__}: {e}"}


def _serve_fleet_extra(cfg, params, *, mb, nb, on_accel, t0, new,
                       aot_dir):
    """Fleet row for the serve config (ISSUE 12), on compile-warm
    replicas reusing the aot_warm row's artifacts: goodput of N=2/4
    data-parallel replicas vs a single supervised engine under the
    same seeded load, re-placement recovery-time-to-resume after a
    replica kill, fleet backend-compile count (must be zero — the
    fleet_warm budget row), and the zero-leak check.  Never fails the
    row — errors land in extra.fleet_error."""
    try:
        from paddle_tpu.aot.serve import warm_engine_factory
        from paddle_tpu.observability import CompileMonitor
        from paddle_tpu.serving import (AdmissionConfig, EngineRouter,
                                        LoadGenConfig,
                                        PoissonLoadGenerator,
                                        RetryPolicy, ServingFrontend,
                                        SupervisedEngine)

        if aot_dir is None:
            raise RuntimeError("no AOT artifacts from the aot_warm row")
        rng = np.random.default_rng(6)
        factory = warm_engine_factory(cfg, params, aot_dir=aot_dir,
                                      max_batch=mb, block_size=16,
                                      num_blocks=nb)
        lg = LoadGenConfig(
            n_requests=16 if not on_accel else 48,
            rate_rps=150.0 if not on_accel else 16.0, seed=8,
            prompt_len=(3, t0), max_new_tokens=(3, new),
            sampled_fraction=0.25, cancel_fraction=0.1,
            burst_rate_rps=600.0 if not on_accel else 64.0,
            burst_fraction=0.25,
            slo_ttft_s=5.0 if not on_accel else 2.0,
            slo_tpot_s=1.0 if not on_accel else 0.25)

        def run_fleet(n):
            if n == 1:
                eng = SupervisedEngine(
                    factory, policy=RetryPolicy(backoff_base_s=0.0),
                    sleep=lambda s: None)
            else:
                eng = EngineRouter(
                    [factory] * n,
                    policy=RetryPolicy(backoff_base_s=0.0),
                    sleep=lambda s: None)
            fe = ServingFrontend(
                eng, admission=AdmissionConfig(max_queue_len=64))
            rep = PoissonLoadGenerator(fe, lg).run()
            leaks = rep.to_dict()["kv_leaked_blocks"]
            return rep, eng, leaks

        monitor = CompileMonitor().install()
        try:
            rep1, _, leaks1 = run_fleet(1)
            rep2, r2, leaks2 = run_fleet(2)
            rep4, r4, leaks4 = run_fleet(4)
        finally:
            monitor.uninstall()
        fleet_compiles = monitor.n_compiles

        # -- re-placement recovery-time-to-resume ---------------------
        router = EngineRouter([factory, factory],
                              policy=RetryPolicy(backoff_base_s=0.0),
                              sleep=lambda s: None)
        rids = [router.add_request(
            rng.integers(0, cfg.vocab_size, (t0,)).astype(np.int32),
            new, temperature=0.7 if i == 0 else 0.0,
            top_k=8 if i == 0 else None, seed=i + 1)
            for i in range(min(3, mb + 1))]
        router.step()
        router.step()
        victim = next(p.replica for p in router._placements.values())
        moved = [rid for rid, p in router._placements.items()
                 if p.replica == victim]
        before = {rid: len(router._placements[rid].req.out)
                  for rid in moved}
        t_k = time.perf_counter()
        router.kill_replica(victim, "bench replica kill")
        while any(rid in router._placements
                  and len(router._placements[rid].req.out)
                  <= before[rid] for rid in moved):
            router.step()
        time_to_resume = time.perf_counter() - t_k
        router.run_to_completion()
        assert rids

        return {"fleet": {
            "replicas": [1, 2, 4],
            "tokens_per_s": [round(rep1.tokens_per_s, 2),
                             round(rep2.tokens_per_s, 2),
                             round(rep4.tokens_per_s, 2)],
            "goodput_rps": [round(rep1.goodput_rps, 3),
                            round(rep2.goodput_rps, 3),
                            round(rep4.goodput_rps, 3)],
            "fleet_backend_compiles": fleet_compiles,
            "replacement_time_to_resume_s": round(time_to_resume, 4),
            "replaced_requests": len(moved),
            "kv_leaked_blocks": leaks1 + leaks2 + leaks4,
            "by_replica_n2": rep2.by_replica,
            "deaths": router.stats["deaths"],
            "replacements": router.stats["replacements"],
            "note": "CPU proxy replicas share one core, so N>1 cannot "
                    "beat N=1 wall-clock here; the fleet win on real "
                    "hardware is N devices — this row proves zero "
                    "compiles, placement spread, and re-placement "
                    "latency, not CPU throughput",
        }}
    except Exception as e:
        return {"fleet_error": f"{type(e).__name__}: {e}"}


def _serve_http_extra(cfg, params, *, mb, nb, on_accel, t0, new,
                      aot_dir):
    """HTTP/SSE wire row for the serve config (ISSUE 13), on a
    compile-warm engine reusing the aot_warm row's artifacts: the SAME
    seeded loadgen run in-process vs over real localhost sockets (the
    wire tax on goodput/ttft), a disconnect storm riding the wire run
    (drained at zero leaks), and the wire backend-compile count (must
    be zero — the serve_http_warm budget row).  Never fails the row —
    errors land in extra.http_error."""
    try:
        import socket

        from paddle_tpu.observability import CompileMonitor
        from paddle_tpu.serving import (AdmissionConfig,
                                        HttpServingServer, LoadGenConfig,
                                        PoissonLoadGenerator,
                                        ServingFrontend)
        from paddle_tpu.serving.http import HttpTransport
        from paddle_tpu.inference.serving import ContinuousBatchingEngine

        if aot_dir is None:
            raise RuntimeError("no AOT artifacts from the aot_warm row")
        rng = np.random.default_rng(9)
        lg = LoadGenConfig(
            n_requests=16 if not on_accel else 48,
            rate_rps=150.0 if not on_accel else 16.0, seed=9,
            prompt_len=(3, t0), max_new_tokens=(3, new),
            sampled_fraction=0.25, cancel_fraction=0.1,
            slo_ttft_s=5.0 if not on_accel else 2.0,
            slo_tpot_s=1.0 if not on_accel else 0.25)

        def warm_engine():
            return ContinuousBatchingEngine(
                cfg, params, max_batch=mb, block_size=16,
                num_blocks=nb, prefill_buckets=(t0,), aot_dir=aot_dir)

        # in-process baseline
        fe1 = ServingFrontend(warm_engine(),
                              admission=AdmissionConfig(max_queue_len=64))
        rep_inproc = PoissonLoadGenerator(fe1, lg).run()

        # the same plan over real sockets + a disconnect storm
        monitor = CompileMonitor().install()
        try:
            fe2 = ServingFrontend(
                warm_engine(),
                admission=AdmissionConfig(max_queue_len=64))
            srv = HttpServingServer(fe2, heartbeat_s=0.02,
                                    retry_grace_s=0.0).start()
            try:
                tp = HttpTransport("127.0.0.1", srv.port, server=srv)
                gen = PoissonLoadGenerator(None, lg, transport=tp)
                import threading

                def storm():
                    for i in range(4):
                        body = json.dumps({
                            "prompt_ids": rng.integers(
                                0, cfg.vocab_size,
                                (3,)).astype(np.int32).tolist(),
                            "max_new_tokens": new}).encode()
                        try:
                            s = socket.create_connection(
                                ("127.0.0.1", srv.port), timeout=10)
                            s.sendall(
                                b"POST /v1/generate HTTP/1.1\r\n"
                                b"Host: b\r\nContent-Type: "
                                b"application/json\r\nContent-Length: "
                                + str(len(body)).encode()
                                + b"\r\nConnection: close\r\n\r\n"
                                + body)
                            s.recv(128)
                            s.close()
                        except OSError:
                            return
                st = threading.Thread(target=storm, daemon=True)
                st.start()
                rep_wire = gen.run()
                st.join(timeout=30.0)
                shutdown = srv.begin_shutdown(reason="bench done")
            finally:
                srv._httpd.server_close()
        finally:
            monitor.uninstall()

        return {"http": {
            "tokens_per_s": {
                "inproc": round(rep_inproc.tokens_per_s, 2),
                "wire": round(rep_wire.tokens_per_s, 2)},
            "goodput_rps": {
                "inproc": round(rep_inproc.goodput_rps, 3),
                "wire": round(rep_wire.goodput_rps, 3)},
            "ttft_p50_s": {
                "inproc": None if rep_inproc.ttft_s is None
                else rep_inproc.ttft_s["p50"],
                "wire": None if rep_wire.ttft_s is None
                else rep_wire.ttft_s["p50"]},
            "wire_backend_compiles": monitor.n_compiles,
            "kv_leaked_blocks": rep_wire.to_dict()["kv_leaked_blocks"],
            "shutdown_drain_secs": shutdown["drain_secs"],
            "shutdown_kv_leaked_blocks": shutdown["kv_leaked_blocks"],
            "disconnect_storm_conns": 4,
            "note": "wire and in-process runs offer the identical "
                    "seeded request sequence (pinned by "
                    "test_serving_http) — deltas are the HTTP/SSE tax "
                    "plus CPU contention from the storm, not workload "
                    "drift",
        }}
    except Exception as e:
        return {"http_error": f"{type(e).__name__}: {e}"}


def _serve_prefix_extra(cfg, params, *, mb, nb, on_accel, t0, new,
                        aot_dir):
    """Cross-request prefix-cache A/B for the serve config (ISSUE 14),
    on compile-warm engines reusing the aot_warm row's artifacts: the
    SAME seeded multi-tenant shared-prefix loadgen run with the cache
    on vs off, reporting TTFT p50/p99, prefill-tokens-computed (the
    direct FLOP savings), hit rate, offload/restore counts, and the
    zero-leak check.  Never fails the row — errors land in
    extra.prefix_cache_error."""
    try:
        from paddle_tpu.inference.serving import ContinuousBatchingEngine
        from paddle_tpu.observability import CompileMonitor
        from paddle_tpu.serving import (AdmissionConfig, LoadGenConfig,
                                        PoissonLoadGenerator,
                                        ServingFrontend)
        from paddle_tpu.serving.prefix_cache import PrefixCacheConfig

        if aot_dir is None:
            raise RuntimeError("no AOT artifacts from the aot_warm row")
        lg = LoadGenConfig(
            n_requests=16 if not on_accel else 48,
            rate_rps=150.0 if not on_accel else 16.0, seed=14,
            prompt_len=(3, t0), max_new_tokens=(3, new),
            sampled_fraction=0.25, cancel_fraction=0.1,
            # tenant prefixes must span >= 1 full 16-token KV block or
            # nothing is block-aligned enough to cache
            tenants=3, tenant_prefix_len=(2 * t0, 4 * t0),
            tenant_reuse_prob=0.8,
            slo_ttft_s=5.0 if not on_accel else 2.0,
            slo_tpot_s=1.0 if not on_accel else 0.25)

        def run(cache_on):
            eng = ContinuousBatchingEngine(
                cfg, params, max_batch=mb, block_size=16,
                num_blocks=nb, prefill_buckets=(t0,), aot_dir=aot_dir,
                enable_prefix_caching=cache_on,
                prefix_cache_config=PrefixCacheConfig(
                    offload_capacity_bytes=1 << 26) if cache_on
                else None)
            fe = ServingFrontend(
                eng, admission=AdmissionConfig(max_queue_len=64))
            rep = PoissonLoadGenerator(fe, lg).run()
            return rep, eng

        monitor = CompileMonitor().install()
        try:
            rep_on, eng_on = run(True)
        finally:
            monitor.uninstall()
        rep_off, _ = run(False)
        ps = eng_on.prefix_stats()
        d_on, d_off = rep_on.to_dict(), rep_off.to_dict()
        return {"prefix_cache": {
            "ttft_p50_s": {
                "cache_on": None if rep_on.ttft_s is None
                else rep_on.ttft_s["p50"],
                "cache_off": None if rep_off.ttft_s is None
                else rep_off.ttft_s["p50"]},
            "ttft_p99_s": {
                "cache_on": None if rep_on.ttft_s is None
                else rep_on.ttft_s["p99"],
                "cache_off": None if rep_off.ttft_s is None
                else rep_off.ttft_s["p99"]},
            "prefill_tokens_computed": {
                "cache_on": (rep_on.prefix or {}).get(
                    "prefill_tokens_computed"),
                "cache_off": (rep_off.prefix or {}).get(
                    "prefill_tokens_computed")},
            "hit_rate": (rep_on.prefix or {}).get("hit_rate"),
            "hit_tokens": (rep_on.prefix or {}).get("hit_tokens"),
            "offloads": ps["offloads"], "restores": ps["restores"],
            "goodput_rps": {"cache_on": d_on["goodput_rps"],
                            "cache_off": d_off["goodput_rps"]},
            "by_tenant": d_on.get("by_tenant"),
            "cache_backend_compiles": monitor.n_compiles,
            "kv_leaked_blocks": d_on["kv_leaked_blocks"],
            "note": "one-core CPU proxy: prefill-tokens-computed and "
                    "hit rate are the signal; TTFT deltas only track "
                    "them loosely when the whole run shares one core",
        }}
    except Exception as e:
        return {"prefix_cache_error": f"{type(e).__name__}: {e}"}


def _serve_quant_extra(cfg, params, *, mb, nb, on_accel, t0, new):
    """Quantized-serving A/B for the serve config (ISSUE 16): the SAME
    seeded request sequence through three engines — bf16 (baseline),
    int8 weight-only, and int8 weights + int8 paged-KV — reporting
    tokens/s and the modelled HBM bytes/token both for weights (the
    decode is weight-bandwidth-bound) and per KV page, plus a CAPACITY
    row: at an identical pool byte budget, how many sequences can run
    concurrently on bf16 vs int8 KV pages (the ~2x admission win that
    motivates KV quantization).  Never fails the row — errors land in
    extra.quant_error."""
    try:
        import jax
        import jax.numpy as jnp
        from paddle_tpu.analysis.kernel.cost import \
            decode_block_weight_bytes
        from paddle_tpu.inference.serving import ContinuousBatchingEngine
        from paddle_tpu.models.llama import init_llama_params, llama_tiny
        from paddle_tpu.ops.paged_kv import kv_page_bytes
        from paddle_tpu.quantization import ServeQuantConfig
        from paddle_tpu import parallel as dist

        def run(qc):
            eng = ContinuousBatchingEngine(
                cfg, params, max_batch=mb, block_size=16,
                num_blocks=nb, prefill_buckets=(t0,), quant_config=qc)
            r = np.random.default_rng(16)
            for _ in range(3 if not on_accel else 8):
                eng.add_request(
                    r.integers(0, cfg.vocab_size, (t0,)).astype(
                        np.int32), new)
            eng.step()                    # compile warm-up iteration
            warm = sum(len(q.out) for q in eng.slots if q is not None)
            t_start = time.perf_counter()
            res = eng.run_to_completion()
            dt = time.perf_counter() - t_start
            toks = sum(len(v) - t0 for v in res.values()) - warm
            rep = eng.kv_leak_report()
            if rep["leaked"] or rep["unaccounted"]:
                raise RuntimeError(f"quant A/B leaked KV: {rep}")
            return round(toks / dt, 1)

        def wbytes(weight_dtype):
            per_layer = decode_block_weight_bytes(
                hidden=cfg.hidden_size, num_heads=cfg.num_heads,
                kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
                ffn_hidden=cfg.intermediate_size, arch="llama",
                weight_dtype=weight_dtype,
                itemsize_=jnp.dtype(cfg.dtype).itemsize)
            return per_layer * cfg.num_layers

        # the baseline column is labelled by the config's ACTUAL dtype
        # (the CPU-proxy serve row runs fp32) so the bytes columns
        # never overclaim the compression ratio
        base = str(jnp.dtype(cfg.dtype))
        kv_isz = jnp.dtype(cfg.dtype).itemsize
        ab = {
            "baseline_dtype": base,
            "tokens_per_s": {
                base: run(None),
                "int8_weights": run(ServeQuantConfig(
                    weight_dtype="int8")),
                "int8_weights_int8_kv": run(ServeQuantConfig(
                    weight_dtype="int8", kv_dtype="int8"))},
            "weight_bytes_per_token": {
                base: wbytes(None), "int8": wbytes("int8"),
                "int4": wbytes("int4")},
            "kv_bytes_per_page_per_layer": {
                base: kv_page_bytes(16, cfg.kv_heads, cfg.head_dim,
                                    dtype_itemsize=kv_isz),
                "int8": kv_page_bytes(16, cfg.kv_heads, cfg.head_dim,
                                      dtype_itemsize=kv_isz,
                                      kv_quant=True)},
        }

        # capacity at FIXED pool bytes: head_dim-64 geometry (the
        # serving-relevant regime — at tiny head_dim the fp32 scale
        # overhead eats the win, docs/performance.md has the math)
        ccfg = llama_tiny(hidden_size=128, num_heads=2, num_kv_heads=2,
                          num_layers=2, dtype="bfloat16")
        topo = dist.init_topology(devices=jax.devices()[:1])
        cparams = init_llama_params(ccfg, topo, 0)
        page_bf16 = kv_page_bytes(16, ccfg.kv_heads, ccfg.head_dim,
                                  dtype_itemsize=2)
        page_int8 = kv_page_bytes(16, ccfg.kv_heads, ccfg.head_dim,
                                  dtype_itemsize=2, kv_quant=True)
        budget = 16 * page_bf16 * ccfg.num_layers * 2   # 16 bf16 pages

        def capacity(kv_quant):
            # 24-token prompts + 8 new tokens = exactly 2 blocks per
            # sequence held across 8 decode steps, so peak concurrency
            # is block-bound, not batch-bound: min(16, blocks // 2)
            page = page_int8 if kv_quant else page_bf16
            blocks = budget // (page * ccfg.num_layers * 2)
            qc = ServeQuantConfig(kv_dtype="int8") if kv_quant else None
            eng = ContinuousBatchingEngine(
                ccfg, cparams, max_batch=16, block_size=16,
                num_blocks=int(blocks), prefill_buckets=(32,),
                quant_config=qc)
            r = np.random.default_rng(8)
            for _ in range(16):
                eng.add_request(
                    r.integers(0, ccfg.vocab_size, (24,)).astype(
                        np.int32), 8)
            peak = 0
            while eng.queue or eng.finished \
                    or any(s is not None for s in eng.slots):
                eng.step()
                peak = max(peak, eng.active_requests)
            rep = eng.kv_leak_report()
            if rep["leaked"] or rep["unaccounted"]:
                raise RuntimeError(f"capacity row leaked KV: {rep}")
            return int(blocks), peak

        blk_b, conc_b = capacity(False)
        blk_q, conc_q = capacity(True)
        ab["capacity_at_fixed_pool_bytes"] = {
            "pool_bytes": budget, "head_dim": ccfg.head_dim,
            "blocks": {"bf16": blk_b, "int8_kv": blk_q},
            "concurrent_seqs": {"bf16": conc_b, "int8_kv": conc_q},
            "ratio": round(conc_q / conc_b, 2),
        }
        ab["kv_leaked_blocks"] = 0
        ab["note"] = ("one-core CPU proxy: the bytes/token and "
                      "capacity columns are the memory-bound-hardware "
                      "claim; CPU tokens/s deltas mostly measure "
                      "dequant FLOPs, not the HBM streaming win")
        return {"quant": ab}
    except Exception as e:
        return {"quant_error": f"{type(e).__name__}: {e}"}


def _serve_decode_block_extra(cfg, params, eng_fused, *, mb, nb, on_accel,
                              t0, new):
    """Fused-vs-per-op decode A/B for the serve row (ISSUE 9): the same
    seeded Poisson load through the (drained, compile-warm) fused
    engine and a per-op engine (``fused_decode_block=False``), reporting
    tpot and goodput-under-SLO both ways plus the HBM-traffic model.
    Never fails the row — errors land in extra.decode_block_error."""
    try:
        from paddle_tpu.inference.serving import ContinuousBatchingEngine
        from paddle_tpu.ops.decode_block import (decode_block_spec,
                                                 hbm_traffic_per_token)
        from paddle_tpu.serving import (AdmissionConfig, LoadGenConfig,
                                        PoissonLoadGenerator,
                                        ServingFrontend)

        lg = LoadGenConfig(
            n_requests=16 if not on_accel else 32,
            rate_rps=100.0 if not on_accel else 8.0, seed=2,
            prompt_len=(3, t0), max_new_tokens=(3, new),
            sampled_fraction=0.25, cancel_fraction=0.1,
            slo_ttft_s=5.0 if not on_accel else 2.0,
            slo_tpot_s=1.0 if not on_accel else 0.25)
        eng_off = ContinuousBatchingEngine(
            cfg, params, max_batch=mb, block_size=16, num_blocks=nb,
            prefill_buckets=(t0,), fused_decode_block=False)
        # compile-warm decode, bucket fill AND the sampler so the A/B
        # measures the decode loop, not tracing (the fused engine
        # arrives fully warm from the earlier loadgen row)
        eng_off.add_request(np.arange(1, t0 + 1, dtype=np.int32), 4)
        eng_off.add_request(np.arange(1, t0 + 1, dtype=np.int32), 4,
                            temperature=0.7, top_k=8, seed=1)
        eng_off.run_to_completion()
        rep_on = PoissonLoadGenerator(
            ServingFrontend(eng_fused,
                            admission=AdmissionConfig(max_queue_len=64)),
            lg).run().to_dict()
        rep_off = PoissonLoadGenerator(
            ServingFrontend(eng_off,
                            admission=AdmissionConfig(max_queue_len=64)),
            lg).run().to_dict()
        spec = decode_block_spec(cfg, 16)
        model = hbm_traffic_per_token(spec, cfg.intermediate_size, mb,
                                      np.dtype(cfg.dtype).itemsize)
        return {"decode_block": {
            "fused_default": bool(eng_fused.fused_decode_block),
            "tpot_p50_fused": (rep_on["tpot_s"] or {}).get("p50"),
            "tpot_p50_per_op": (rep_off["tpot_s"] or {}).get("p50"),
            "goodput_tokens_per_s_fused": rep_on["goodput_tokens_per_s"],
            "goodput_tokens_per_s_per_op": rep_off["goodput_tokens_per_s"],
            "tokens_per_s_fused": rep_on["tokens_per_s"],
            "tokens_per_s_per_op": rep_off["tokens_per_s"],
            "kv_leaked_blocks": rep_on["kv_leaked_blocks"],
            "hbm_model_per_layer": model,
            # the CPU proxy runs the SAME XLA ops both ways (the fused
            # op's reference tier IS the per-op chain), so wall clock is
            # ~1:1 here; the modelled stream-bytes gap is the
            # memory-bound-hardware-facing win (docs/performance.md)
            "note": "CPU proxy is compute-bound and bit-identical both "
                    "ways; the fused win is the modelled HBM stream "
                    "traffic, realized on memory-bound accelerators",
        }}
    except Exception as e:
        return {"decode_block_error": f"{type(e).__name__}: {e}"}


def _serve_prefill_extra(cfg, params, *, mb, nb, on_accel, t0, new):
    """Fused-vs-per-op chunked-prefill A/B for the serve row (ISSUE 18):
    the same seeded Poisson load through a compile-warm fused-prefill
    engine (``fused_prefill=True``, the default) and a per-op one,
    reporting TTFT p50/p99 both ways plus the per-chunk HBM-traffic
    model.  Never fails the row — errors land in extra.prefill_error."""
    try:
        from paddle_tpu.inference.serving import ContinuousBatchingEngine
        from paddle_tpu.ops.decode_block import (decode_block_spec,
                                                 hbm_traffic_per_chunk)
        from paddle_tpu.serving import (AdmissionConfig, LoadGenConfig,
                                        PoissonLoadGenerator,
                                        ServingFrontend)

        lg = LoadGenConfig(
            n_requests=16 if not on_accel else 32,
            rate_rps=100.0 if not on_accel else 8.0, seed=18,
            prompt_len=(3, t0), max_new_tokens=(3, new),
            sampled_fraction=0.25, cancel_fraction=0.1,
            slo_ttft_s=5.0 if not on_accel else 2.0,
            slo_tpot_s=1.0 if not on_accel else 0.25)

        def warm_engine(fused):
            eng = ContinuousBatchingEngine(
                cfg, params, max_batch=mb, block_size=16, num_blocks=nb,
                prefill_buckets=(t0,), fused_prefill=fused)
            # compile-warm the bucket fill, decode and the sampler so
            # the A/B measures serving, not tracing
            eng.add_request(np.arange(1, t0 + 1, dtype=np.int32), 4)
            eng.add_request(np.arange(1, t0 + 1, dtype=np.int32), 4,
                            temperature=0.7, top_k=8, seed=1)
            eng.run_to_completion()
            return eng

        reps = {}
        for fused in (True, False):
            eng = warm_engine(fused)
            reps[fused] = PoissonLoadGenerator(
                ServingFrontend(eng, admission=AdmissionConfig(
                    max_queue_len=64)), lg).run().to_dict()
            rep = eng.kv_leak_report()
            if rep["leaked"] or rep["unaccounted"]:
                raise RuntimeError(f"prefill A/B leaked KV: {rep}")
        spec = decode_block_spec(cfg, 16)
        model = hbm_traffic_per_chunk(
            spec, cfg.intermediate_size, t0, nb // max(mb, 1),
            np.dtype(cfg.dtype).itemsize)
        return {"prefill": {
            "ttft_p50_fused": (reps[True]["ttft_s"] or {}).get("p50"),
            "ttft_p99_fused": (reps[True]["ttft_s"] or {}).get("p99"),
            "ttft_p50_per_op": (reps[False]["ttft_s"] or {}).get("p50"),
            "ttft_p99_per_op": (reps[False]["ttft_s"] or {}).get("p99"),
            "tokens_per_s_fused": reps[True]["tokens_per_s"],
            "tokens_per_s_per_op": reps[False]["tokens_per_s"],
            "kv_leaked_blocks": reps[True]["kv_leaked_blocks"],
            "hbm_model_per_layer_per_chunk": model,
            # the CPU proxy runs the SAME XLA ops both ways (the fused
            # op's reference tier IS the per-op chain) on one core, so
            # TTFT is ~1:1 here; the modelled stream-bytes gap is the
            # memory-bound-hardware-facing win (docs/performance.md)
            "note": "CPU proxy is compute-bound and bit-identical both "
                    "ways; the fused win is the modelled HBM stream "
                    "traffic, realized on memory-bound accelerators",
        }}
    except Exception as e:
        return {"prefill_error": f"{type(e).__name__}: {e}"}


def _serve_tracing_extra(cfg, params, *, mb, nb, on_accel, t0, new):
    """Span-tracer overhead A/B for the serve row (ISSUE 20): the same
    seeded Poisson load through a compile-warm engine with the request
    tracer off and on, reporting tokens/s and TTFT p50 both ways plus
    the traced run's per-phase TTFT/TPOT attribution.  The acceptance
    bar is <2% throughput overhead (docs/observability.md).  Never
    fails the row — errors land in extra.tracing_error."""
    from paddle_tpu.observability.tracing import TRACER

    was_enabled = TRACER.enabled
    try:
        from paddle_tpu.inference.serving import ContinuousBatchingEngine
        from paddle_tpu.serving import (AdmissionConfig, LoadGenConfig,
                                        PoissonLoadGenerator,
                                        ServingFrontend)

        lg = LoadGenConfig(
            n_requests=16 if not on_accel else 32,
            rate_rps=100.0 if not on_accel else 8.0, seed=20,
            prompt_len=(3, t0), max_new_tokens=(3, new),
            sampled_fraction=0.25, cancel_fraction=0.1,
            slo_ttft_s=5.0 if not on_accel else 2.0,
            slo_tpot_s=1.0 if not on_accel else 0.25)

        def run_once(traced):
            eng = ContinuousBatchingEngine(
                cfg, params, max_batch=mb, block_size=16, num_blocks=nb,
                prefill_buckets=(t0,))
            # compile-warm the bucket fill, decode and the sampler so
            # the A/B measures serving, not XLA compiles
            eng.add_request(np.arange(1, t0 + 1, dtype=np.int32), 4)
            eng.add_request(np.arange(1, t0 + 1, dtype=np.int32), 4,
                            temperature=0.7, top_k=8, seed=1)
            eng.run_to_completion()
            if traced:
                TRACER.enable()
                TRACER.reset()
            else:
                TRACER.disable()
            rep = PoissonLoadGenerator(
                ServingFrontend(eng, admission=AdmissionConfig(
                    max_queue_len=64)), lg).run().to_dict()
            leak = eng.kv_leak_report()
            if leak["leaked"] or leak["unaccounted"]:
                raise RuntimeError(f"tracing A/B leaked KV: {leak}")
            return rep

        rep_off = run_once(False)
        rep_on = run_once(True)
        tps_off = rep_off["tokens_per_s"]
        tps_on = rep_on["tokens_per_s"]
        return {"tracing": {
            "tokens_per_s_off": tps_off,
            "tokens_per_s_on": tps_on,
            "overhead_pct": round(
                (tps_off - tps_on) / tps_off * 100.0, 2)
            if tps_off else None,
            "ttft_p50_off": (rep_off["ttft_s"] or {}).get("p50"),
            "ttft_p50_on": (rep_on["ttft_s"] or {}).get("p50"),
            "kv_leaked_blocks": rep_on["kv_leaked_blocks"],
            "attribution": rep_on.get("attribution"),
        }}
    except Exception as e:
        return {"tracing_error": f"{type(e).__name__}: {e}"}
    finally:
        if was_enabled:
            TRACER.enable()
        else:
            TRACER.disable()


def _train_aot_warm_extra(step_fn, state, ids, labels, ttfs_cold):
    """Cold-vs-warm for the llama train row: serialize the (undonated
    re-jit of the) train step, deserialize, and time load + first step
    with the compile counter attached.  Never fails the row."""
    try:
        import jax
        import tempfile
        from paddle_tpu.aot.artifact import ArtifactStore, export_compiled
        from paddle_tpu.observability import CompileMonitor

        wrapped = getattr(step_fn, "__wrapped__", None)
        if wrapped is None:
            return {"aot_error": "train step exposes no __wrapped__ to "
                                 "re-jit undonated"}
        # undonated: the deserialized-donated path is gated on jax
        # 0.4.37 CPU (aot/artifact.py), and the warm metric is about
        # load time, not steady-state memory
        aot_dir = tempfile.mkdtemp(prefix="bench_aot_train_")
        export_compiled(aot_dir, "llama_train_step", jax.jit(wrapped),
                        (state, ids, labels),
                        config={"kind": "bench_llama_train"})
        monitor = CompileMonitor().install()
        try:
            t_w = time.perf_counter()
            loaded = ArtifactStore(aot_dir).get("llama_train_step")
            _, loss = loaded(state, ids, labels)
            jax.device_get(loss)
            warm_first = time.perf_counter() - t_w
        finally:
            monitor.uninstall()
        return {"aot_warm": {
            "time_to_first_step_cold_from_proc_start_s":
                round(ttfs_cold, 3),
            "load_plus_first_step_s": round(warm_first, 3),
            "warm_backend_compiles": monitor.n_compiles,
        }}
    except Exception as e:
        return {"aot_error": f"{type(e).__name__}: {e}"}


def _train_elastic_bench(devices, on_accel, rng):
    """`--config train` (ISSUE 17): elastic-training recovery after a
    mid-run worker kill on a dp-N mesh — time-to-resume cold (fresh
    reshape compile + export) vs AOT-warm (per-topology artifact
    deserialize), throughput before the kill and after the dp N→N−1
    reshape, and the carryover accounting (steps lost/replayed)."""
    import tempfile

    import jax

    n = len(jax.devices())
    if not on_accel and n < 8:
        # a 1-device parent can't measure an 8→7 reshape: re-exec the
        # measurement in a forced-8-virtual-device child (the tier-1
        # simulation mesh) and pass its row through
        import subprocess
        env = dict(os.environ, _BENCH_CHILD="1", BENCH_CONFIG="train",
                   JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=8 "
                             "--xla_cpu_enable_concurrency_optimized_"
                             "scheduler=false")
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            capture_output=True, text=True, env=env, timeout=600)
        line = next((ln for ln in reversed(proc.stdout.splitlines())
                     if ln.startswith("{")), None)
        if line is None:
            raise RuntimeError(
                f"8-device elastic child produced no row (rc="
                f"{proc.returncode}): {proc.stderr[-300:]}")
        return json.loads(line)

    import paddle_tpu as pt
    from paddle_tpu import nn
    from paddle_tpu.observability import CompileMonitor
    from paddle_tpu.parallel import ElasticTrainer, WorkerLostError
    from paddle_tpu.parallel.topology import HybridTopology, set_topology

    dp = min(8, n)
    batch = dp * (dp - 1)          # divisible by dp AND dp-1 (8→7: 56)
    feat, hidden, classes = 64, 128, 10

    def data_fn(step):
        r = np.random.default_rng(1000 + step)
        return (r.standard_normal((batch, feat)).astype("float32"),
                r.integers(0, classes, (batch,)).astype("int64"))

    def make_trainer(aot_dir):
        topo = HybridTopology(dp=dp, devices=jax.devices()[:dp])
        set_topology(topo)
        pt.seed(11)
        net = nn.Sequential(nn.Linear(feat, hidden), nn.ReLU(),
                            nn.Linear(hidden, classes))
        opt = pt.optimizer.Adam(parameters=net.parameters(),
                                learning_rate=1e-2)
        return ElasticTrainer(net, opt, nn.CrossEntropyLoss(), data_fn,
                              topology=topo, sharding_stage=2,
                              rng_seed=7, aot_dir=aot_dir)

    def arm_kill(tr):
        eng, real = tr.engine, tr.engine.train_batch
        at = eng._step_count

        def patched(inputs, labels=None, rng=None):
            if eng._step_count == at:
                eng.train_batch = real
                raise WorkerLostError("bench kill", lost_index=dp - 1,
                                      axis="dp")
            return real(inputs, labels, rng=rng)

        eng.train_batch = patched

    def rate(tr, steps=3):
        t0 = time.perf_counter()
        tr.run(steps)
        return steps * batch / (time.perf_counter() - t0)

    aot_dir = tempfile.mkdtemp(prefix="bench_elastic_")
    try:
        # phase 1 — COLD: empty store, so the post-kill reshape pays
        # the fresh compile (+ export, which seeds phase 2's warm path)
        tr = make_trainer(aot_dir)
        tr.run(2)
        before = rate(tr)
        arm_kill(tr)
        tr.step()                    # kill → reshape → re-run the step
        recovery_cold = tr.last_recovery_s
        after = rate(tr)
        steps_lost = tr.steps_replayed
        carry = tr.steps_replayed == 0

        # phase 2 — AOT-WARM: both meshes' entries exist; the resume
        # and the reshape must be pure deserializes (zero compiles)
        tr2 = make_trainer(aot_dir)
        with CompileMonitor() as mon:
            tr2.run(2)
            arm_kill(tr2)
            tr2.step()
        recovery_warm = tr2.last_recovery_s
        warm_compiles = mon.n_compiles
    finally:
        set_topology(HybridTopology())

    return {
        "metric": "elastic_train_samples_per_sec",
        "value": round(after, 1),
        "unit": "samples/s", "vs_baseline": 0.0,
        "extra": {
            "device": str(devices[0]), "batch": batch,
            "mesh": f"dp{dp}->dp{dict(tr.topo.degrees)['dp']}",
            "elastic": {
                "samples_per_s_before_kill": round(before, 1),
                "samples_per_s_after_reshape": round(after, 1),
                "recovery_time_to_resume_s_cold": round(recovery_cold, 3),
                "recovery_time_to_resume_s_aot_warm":
                    round(recovery_warm, 3),
                "warm_backend_compiles": warm_compiles,
                "steps_lost": steps_lost,
                "carryover": carry,
                "note": "virtual XLA host devices share ONE CPU core: "
                        "the per-step rates measure framework+XLA "
                        "overhead (a smaller mesh can even be faster), "
                        "not chip throughput; the accelerator-facing "
                        "numbers are the cold-vs-warm recovery gap "
                        "(compile vs deserialize) and "
                        "warm_backend_compiles=0",
            }}}


def run_config_bench(config: str):
    """The per-subsystem rows: full shapes on a TPU, scaled-down
    liveness shapes on a CPU backend (the ``on_accel`` forks go with
    ROADMAP S0)."""
    import jax

    devices = jax.devices()
    on_accel = devices[0].platform == "tpu"
    rng = np.random.default_rng(0)

    if config == "lenet":
        from paddle_tpu.models.lenet import LeNet
        net = LeNet()
        b = 256 if on_accel else 32
        x = rng.standard_normal((b, 1, 28, 28)).astype(np.float32)
        y = rng.integers(0, 10, (b,)).astype(np.int32)
        out = _layer_train_bench(net, x, y, 10 if on_accel else 3, b,
                                 "samples/s/chip",
                                 "lenet_train_samples_per_sec", devices)
    elif config == "resnet50":
        from paddle_tpu.vision import models
        if on_accel:
            net, b, hw = models.resnet50(), 64, 224
        else:
            net, b, hw = models.resnet18(), 4, 32   # CPU liveness shapes
        net.train()
        x = rng.standard_normal((b, 3, hw, hw)).astype(np.float32)
        y = rng.integers(0, 1000, (b,)).astype(np.int32)
        out = _layer_train_bench(net, x, y, 5 if on_accel else 2, b,
                                 "samples/s/chip",
                                 "resnet50_train_samples_per_sec", devices)
        if not on_accel:
            out["extra"]["model"] = "resnet18@32px CPU-liveness proxy"
    elif config == "bert":
        from paddle_tpu.models.bert import (BertForSequenceClassification,
                                            bert_base, bert_tiny)
        cfg = bert_base() if on_accel else bert_tiny()
        net = BertForSequenceClassification(cfg, num_classes=2)
        b, s = (32, 128) if on_accel else (2, 32)
        x = rng.integers(0, cfg.vocab_size, (b, s)).astype(np.int32)
        y = rng.integers(0, 2, (b,)).astype(np.int32)
        out = _layer_train_bench(net, x, y, 5 if on_accel else 2, b * s,
                                 "tokens/s/chip",
                                 "bert_finetune_tokens_per_sec", devices)
        if not on_accel:
            out["extra"]["model"] = "bert_tiny CPU-liveness proxy"
    elif config == "llama":
        from paddle_tpu.models.llama import (build_llama_train_step,
                                             llama_7b, llama_tiny)
        from paddle_tpu import parallel as dist
        # full 7B needs ~56GB of fp32 Adam moments — multi-chip
        # territory.  A single chip measures the TRUE 7B layer width on
        # a 3-layer stack: compiled for a v5e (15.75 GiB), 4 layers peak
        # at 15.47 GiB and 3 at 12.5 GiB (memory_analysis, PR 22) — the
        # shape chip_smoke.py trains.
        if on_accel:
            cfg = llama_7b(dtype="bfloat16", num_layers=3)
            b, s, steps = 4, 2048, 5
        else:
            cfg = llama_tiny()
            b, s, steps = 2, 128, 2
        topo = dist.init_topology(devices=devices[:1])
        step_fn, init_fn = build_llama_train_step(
            cfg, topo, num_microbatches=1, remat=True, sharding_stage=2)
        state = init_fn(0)
        ids = rng.integers(0, cfg.vocab_size, (b, s)).astype(np.int32)
        labels = np.roll(ids, -1, axis=1)
        state, loss = step_fn(state, ids, labels)
        jax.device_get(loss)
        ttfs_cold = time.perf_counter() - _PROC_T0
        t0 = time.perf_counter()
        for _ in range(steps):
            state, loss = step_fn(state, ids, labels)
        loss_val = float(np.asarray(jax.device_get(loss)))
        dt = time.perf_counter() - t0
        out = {
            "metric": "llama_train_tokens_per_sec_per_chip",
            "value": round(b * s * steps / dt, 1),
            "unit": "tokens/s/chip", "vs_baseline": 0.0,
            "extra": {"steps": steps, "loss": loss_val,
                      "device": str(devices[0]),
                      "model": "llama_7b-width L3 proxy (full 7B is "
                               "a sharding8 config)" if on_accel
                               else "llama_tiny CPU-liveness proxy"},
        }
        out["extra"].update(_train_aot_warm_extra(step_fn, state, ids,
                                                  labels, ttfs_cold))
    elif config == "moe":
        # GPT-MoE: single-chip measurement of the expert FFN path (scatter
        # dispatch + batched expert einsums + top-2 routing); multi-chip
        # EP adds one all_to_all each way over dp (dryrun-gated)
        from paddle_tpu.models.gpt import GPTConfig, build_gpt_train_step
        from paddle_tpu import parallel as dist
        if on_accel:
            cfg = GPTConfig(vocab_size=32768, hidden_size=768,
                            num_layers=12, num_heads=12,
                            max_position_embeddings=1024, dtype="bfloat16",
                            moe_num_experts=8)
            b, s, steps = 8, 1024, 10
        else:
            cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                            num_heads=4, max_position_embeddings=128,
                            moe_num_experts=4)
            b, s, steps = 2, 64, 2
        topo = dist.init_topology(devices=devices[:1])
        step_fn, init_fn = build_gpt_train_step(
            cfg, topo, num_microbatches=1, remat=not on_accel)
        state = init_fn(0)
        ids = rng.integers(0, cfg.vocab_size, (b, s)).astype(np.int32)
        labels = np.roll(ids, -1, axis=1)
        state, loss = step_fn(state, ids, labels)
        jax.device_get(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            state, loss = step_fn(state, ids, labels)
        loss_val = float(np.asarray(jax.device_get(loss)))
        dt = time.perf_counter() - t0
        out = {
            "metric": "gpt_moe_train_tokens_per_sec_per_chip",
            "value": round(b * s * steps / dt, 1),
            "unit": "tokens/s/chip", "vs_baseline": 0.0,
            "extra": {"steps": steps, "loss": loss_val,
                      "experts": cfg.moe_num_experts,
                      "top_k": cfg.moe_top_k,
                      "device": str(devices[0]),
                      "model": f"gpt-moe h{cfg.hidden_size} "
                               f"L{cfg.num_layers} E{cfg.moe_num_experts}"},
        }
    elif config == "serve":
        # continuous-batching engine throughput: staggered requests
        # through the paged-KV scheduler (inference/serving.py) — the
        # serving-side metric the single-rollout decode row doesn't cover
        from paddle_tpu.models.llama import (init_llama_params, llama_7b,
                                             llama_tiny)
        from paddle_tpu.inference.serving import ContinuousBatchingEngine
        from paddle_tpu import parallel as dist
        if on_accel:
            cfg = llama_7b(dtype="bfloat16", num_layers=4)
            n_req, t0, new, mb = 8, 128, 96, 4
        else:
            cfg = llama_tiny()
            n_req, t0, new, mb = 3, 8, 6, 2
        topo = dist.init_topology(devices=devices[:1])
        params = init_llama_params(cfg, topo, 0)
        nb = max(64, mb * ((t0 + new) // 16 + 2))
        # declared-bucket prefill (aot/buckets.py): the prompt length is
        # the single declared bucket, so admissions are exact-hit fills
        # and the same code path serves the AOT warm-start comparison
        eng = ContinuousBatchingEngine(
            cfg, params, max_batch=mb, block_size=16, num_blocks=nb,
            prefill_buckets=(t0,))
        for i in range(n_req):
            eng.add_request(
                rng.integers(0, cfg.vocab_size, (t0,)).astype(np.int32),
                new)
        # warm the compiles with one scheduler iteration; tokens
        # produced before t_start are excluded from the rate
        eng.step()
        ttft_cold = time.perf_counter() - _PROC_T0
        warm = sum(len(r.out) for r in eng.slots if r is not None)
        t_start = time.perf_counter()
        results = eng.run_to_completion()
        dt = time.perf_counter() - t_start
        total_new = sum(len(v) - t0 for v in results.values()) - warm
        out = {
            "metric": "llama_serve_tokens_per_sec_per_chip",
            "value": round(total_new / dt, 1),
            "unit": "tokens/s/chip", "vs_baseline": 0.0,
            "extra": {"requests": n_req, "prompt": t0, "new_tokens": new,
                      "max_batch": mb, "device": str(devices[0]),
                      "model": "llama_7b-width L4 proxy serving"
                               if on_accel else "llama_tiny CPU proxy"},
        }
        aot_dir_out = {}
        out["extra"].update(_serve_aot_warm_extra(
            cfg, params, eng, ttft_cold, mb=mb, nb=nb, t0=t0, new=new,
            rng=rng, aot_dir_out=aot_dir_out))
        out["extra"].update(_serve_loadgen_extra(eng, on_accel, t0=t0,
                                                 new=new))
        out["extra"].update(_serve_decode_block_extra(
            cfg, params, eng, mb=mb, nb=nb, on_accel=on_accel, t0=t0,
            new=new))
        out["extra"].update(_serve_spec_extra(
            cfg, params, eng, mb=mb, nb=nb, on_accel=on_accel, t0=t0,
            new=new))
        out["extra"].update(_serve_resilience_extra(
            cfg, params, mb=mb, nb=nb, on_accel=on_accel, t0=t0,
            new=new, aot_dir=aot_dir_out.get("dir")))
        out["extra"].update(_serve_fleet_extra(
            cfg, params, mb=mb, nb=nb, on_accel=on_accel, t0=t0,
            new=new, aot_dir=aot_dir_out.get("dir")))
        out["extra"].update(_serve_http_extra(
            cfg, params, mb=mb, nb=nb, on_accel=on_accel, t0=t0,
            new=new, aot_dir=aot_dir_out.get("dir")))
        out["extra"].update(_serve_prefix_extra(
            cfg, params, mb=mb, nb=nb, on_accel=on_accel, t0=t0,
            new=new, aot_dir=aot_dir_out.get("dir")))
        out["extra"].update(_serve_quant_extra(
            cfg, params, mb=mb, nb=nb, on_accel=on_accel, t0=t0,
            new=new))
        out["extra"].update(_serve_prefill_extra(
            cfg, params, mb=mb, nb=nb, on_accel=on_accel, t0=t0,
            new=new))
        out["extra"].update(_serve_tracing_extra(
            cfg, params, mb=mb, nb=nb, on_accel=on_accel, t0=t0,
            new=new))
    elif config == "decode":
        # inference: autoregressive decode through the KV-cache decoder
        # (prefill + lax.scan step loop; Pallas MMHA on TPU) — the
        # serving-side metric the train rows don't cover
        from paddle_tpu.models.llama import (init_llama_params, llama_7b,
                                             llama_tiny)
        from paddle_tpu.models.generation import llama_generate
        from paddle_tpu import parallel as dist
        if on_accel:
            cfg = llama_7b(dtype="bfloat16", num_layers=4)
            b, t0, new, reps = 8, 128, 128, 3
        else:
            cfg = llama_tiny()
            b, t0, new, reps = 2, 8, 8, 1
        topo = dist.init_topology(devices=devices[:1])
        params = init_llama_params(cfg, topo, 0)
        ids = rng.integers(0, cfg.vocab_size, (b, t0)).astype(np.int32)
        got = llama_generate(params, cfg, ids, max_new_tokens=new,
                             temperature=0.0)     # compile + warm
        jax.device_get(got)
        t_start = time.perf_counter()
        for _ in range(reps):
            got = llama_generate(params, cfg, ids, max_new_tokens=new,
                                 temperature=0.0)
        jax.device_get(got)
        dt = time.perf_counter() - t_start
        out = {
            "metric": "llama_decode_tokens_per_sec_per_chip",
            "value": round(b * new * reps / dt, 1),
            "unit": "tokens/s/chip", "vs_baseline": 0.0,
            "extra": {"batch": b, "prompt": t0, "new_tokens": new,
                      "device": str(devices[0]),
                      "model": "llama_7b-width L4 proxy decode" if on_accel
                               else "llama_tiny CPU-liveness proxy"},
        }
    elif config == "loss":
        # fused LM-head loss microbench: naive materialized-logits CE vs
        # the XLA-chunked logits-free head vs the Pallas kernel tier
        # (TPU only — interpret mode is a correctness lane), across
        # vocab sizes.  Measures a full value_and_grad step (the training
        # cost) and reports tokens/s plus the estimated peak activation
        # bytes each path holds for the vocab dimension.
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.fused_cross_entropy import (
            chunked_peak_bytes, default_chunk, linear_cross_entropy,
            naive_peak_bytes)

        H = 768
        if on_accel:
            b, s, reps, dt = 8, 1024, 10, jnp.bfloat16
        else:
            b, s, reps, dt = 2, 256, 3, jnp.float32
        T = b * s
        vocabs = [8192, 32768, 50304]
        rows = {}

        def timeit(fn, *args):
            v = jax.block_until_ready(fn(*args))       # compile + warm
            t0 = time.perf_counter()
            for _ in range(reps):
                v = fn(*args)
            jax.block_until_ready(v)
            return (time.perf_counter() - t0) / reps

        for V in vocabs:
            x = jnp.asarray(rng.standard_normal((b, s, H)), dt) * 0.5
            w = jnp.asarray(rng.standard_normal((V, H)), dt) * 0.05
            labels = jnp.asarray(
                rng.integers(0, V, (b, s)).astype(np.int32))

            def naive_loss(x_, w_):
                z = jnp.einsum("bsh,vh->bsv", x_, w_,
                               preferred_element_type=jnp.float32)
                lp = jax.nn.log_softmax(z, -1)
                return -jnp.mean(jnp.take_along_axis(
                    lp, labels[..., None], -1))

            def chunked_loss(x_, w_):
                return jnp.mean(linear_cross_entropy(
                    x_, w_, labels, backend="xla"))

            def pallas_loss(x_, w_):
                return jnp.mean(linear_cross_entropy(
                    x_, w_, labels, backend="pallas"))

            grad2 = lambda f: jax.jit(jax.value_and_grad(f, (0, 1)))
            t_naive = timeit(grad2(naive_loss), x, w)
            t_chunk = timeit(grad2(chunked_loss), x, w)
            row = {
                "naive_ms": round(t_naive * 1e3, 2),
                "chunked_ms": round(t_chunk * 1e3, 2),
                "chunked_speedup": round(t_naive / t_chunk, 3),
                "naive_tokens_per_s": round(T / t_naive, 1),
                "chunked_tokens_per_s": round(T / t_chunk, 1),
                "naive_peak_act_bytes": naive_peak_bytes(T, V),
                "chunked_peak_act_bytes": chunked_peak_bytes(T, V),
                "chunk": default_chunk(V),
            }
            if on_accel:
                t_pl = timeit(grad2(pallas_loss), x, w)
                row["pallas_ms"] = round(t_pl * 1e3, 2)
                row["pallas_tokens_per_s"] = round(T / t_pl, 1)
            rows[f"V{V}"] = row
        big = rows[f"V{vocabs[-1]}"]
        out = {
            "metric": "loss_head_tokens_per_sec",
            "value": big["chunked_tokens_per_s"],
            "unit": "tokens/s/chip",
            # >1 == the chunked head beats the naive head at the largest
            # vocab.  Expected >1 on memory-bound accelerators (logits
            # traffic dominates); the single-core CPU fallback is
            # compute-bound, where the chunked path's unavoidable 4-vs-3
            # GEMM recompute tax caps it near 0.75-0.9x (it still cuts
            # peak activation bytes ~25x — docs/performance.md).
            "vs_baseline": big["chunked_speedup"],
            "extra": {"rows": rows, "batch": b, "seq": s, "hidden": H,
                      "dtype": str(jnp.dtype(dt)), "grad": True,
                      "fused_head": True, "device": str(devices[0])},
        }
    elif config == "optimizer":
        # fused multi-tensor optimizer microbench (optimizer/fused.py):
        # many small params is exactly where the per-param loop drowns in
        # tiny kernels; the fused path runs one bucketed kernel with flat
        # moments held in place across steps
        import jax
        import jax.numpy as jnp
        from paddle_tpu.optimizer import AdamW

        n_params, reps = (512, 100) if on_accel else (256, 50)
        params = {f"p{i}": jnp.asarray(
            rng.standard_normal(64 + (i % 7) * 16).astype(np.float32))
            for i in range(n_params)}
        grads = {k: jnp.asarray(
            rng.standard_normal(v.shape).astype(np.float32))
            for k, v in params.items()}
        opt = AdamW(learning_rate=1e-3, weight_decay=0.01)
        fused = opt.build_jit_apply(donate=False)
        perparam = jax.jit(opt.apply_gradients)

        def run(fn):
            p = dict(params)
            s = opt.init_state(params)
            p, s = fn(p, grads, s, 1e-3, 1)
            p, s = fn(p, grads, s, 1e-3, 2)     # steady-state structure
            jax.block_until_ready(p)
            t0 = time.perf_counter()
            for i in range(reps):
                p, s = fn(p, grads, s, 1e-3, 3 + i)
            jax.block_until_ready(p)
            return (time.perf_counter() - t0) / reps

        t_fused = run(fused)
        t_pp = run(perparam)
        out = {
            "metric": "optimizer_fused_steps_per_sec",
            "value": round(1.0 / t_fused, 1),
            "unit": "steps/s", "vs_baseline": round(t_pp / t_fused, 4),
            "extra": {"params": n_params, "steps": reps,
                      "fused_us": round(t_fused * 1e6, 1),
                      "per_param_us": round(t_pp * 1e6, 1),
                      "speedup_vs_per_param": round(t_pp / t_fused, 2),
                      "optimizer_fused": True,
                      "device": str(devices[0])},
        }
    elif config == "decode_block":
        # fused decode-step block microbench (ISSUE 9): a jitted
        # L-layer decode step built from ops/decode_block, fused tier
        # vs the per-op reference tier, across decode batch widths.
        # On the CPU proxy both tiers lower to the same XLA ops (the
        # reference tier IS the fused op's CPU path), so wall clock is
        # ~1:1 and the HBM-traffic model carries the claim; on TPU the
        # fused tier dispatches the Pallas megakernel when the layer
        # fits VMEM.
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.decode_block import (DecodeBlockSpec,
                                                 decode_block,
                                                 hbm_traffic_per_token)

        if on_accel:
            H, Hq, Hkv, D, F, L = 2048, 16, 8, 128, 5504, 4
            BS, MB, NB = 16, 64, 512
            batches, reps, dt = (1, 8, 16), 20, jnp.bfloat16
        else:
            H, Hq, Hkv, D, F, L = 64, 4, 2, 16, 128, 2
            BS, MB, NB = 8, 8, 64
            batches, reps, dt = (1, 4, 8), 5, jnp.float32
        max_batch = batches[-1]
        spec = DecodeBlockSpec(hidden=H, num_heads=Hq, kv_heads=Hkv,
                               head_dim=D, block_size=BS, norm="rms",
                               activation="swiglu", eps=1e-5, rope=True)

        def mk(*s):
            return jnp.asarray(
                rng.standard_normal(s).astype(np.float32) * 0.05, dt)

        lp = {"ln1_w": mk(L, H) + 1.0, "q_w": mk(L, H, Hq * D),
              "k_w": mk(L, H, Hkv * D), "v_w": mk(L, H, Hkv * D),
              "o_w": mk(L, Hq * D, H), "ln2_w": mk(L, H) + 1.0,
              "gate_w": mk(L, H, F), "up_w": mk(L, H, F),
              "down_w": mk(L, F, H)}
        pool_k = mk(L, NB, BS, Hkv, D)
        pool_v = mk(L, NB, BS, Hkv, D)

        def build(backend):
            def step(x, lp, pk, pv, bt, lengths, cos, sin):
                def body(carry, inp):
                    x = carry
                    layer, k, v = inp
                    x, k, v = decode_block(x, layer, k, v, bt, lengths,
                                           cos, sin, spec=spec,
                                           backend=backend)
                    return x, (k, v)

                x, (pk2, pv2) = jax.lax.scan(body, x, (lp, pk, pv))
                return x, pk2, pv2

            return jax.jit(step)

        rows = {}
        for b in batches:
            bt = np.full((b, MB), -1, np.int32)
            for i in range(b):
                bt[i, :MB // 2] = rng.permutation(NB)[:MB // 2]
            lengths = rng.integers(1, (MB // 2) * BS - 1,
                                   (b,)).astype(np.int32)
            x = mk(b, H)
            cos, sin = mk(b, D), mk(b, D)
            args = (x, lp, pool_k, pool_v, jnp.asarray(bt),
                    jnp.asarray(lengths), cos, sin)

            def timeit(fn):
                o = fn(*args)
                jax.block_until_ready(o)
                t0 = time.perf_counter()
                for _ in range(reps):
                    o = fn(*args)
                jax.block_until_ready(o)
                return (time.perf_counter() - t0) / reps

            t_op = timeit(build("xla"))
            t_fused = timeit(build(None))
            rows[f"B{b}"] = {
                "per_op_ms": round(t_op * 1e3, 3),
                "fused_ms": round(t_fused * 1e3, 3),
                "speedup": round(t_op / t_fused, 3),
                "fused_tokens_per_s": round(b / t_fused, 1),
            }
        model = hbm_traffic_per_token(spec, F, max_batch,
                                      jnp.dtype(dt).itemsize)
        big = rows[f"B{max_batch}"]
        out = {
            "metric": "decode_block_tokens_per_sec",
            "value": big["fused_tokens_per_s"],
            "unit": "tokens/s/chip",
            "vs_baseline": big["speedup"],
            "extra": {"rows": rows, "layers": L, "hidden": H,
                      "heads": f"{Hq}q/{Hkv}kv", "head_dim": D,
                      "ffn": F, "dtype": str(jnp.dtype(dt)),
                      "hbm_model_per_layer_at_max_batch": model,
                      "device": str(devices[0]),
                      "note": "CPU proxy: both tiers are the same XLA "
                              "program (speedup ~1.0 expected); the "
                              "hbm model is the accelerator-facing win"},
        }
    elif config == "prefill":
        # fused chunked-prefill microbench (ISSUE 18): a jitted L-layer
        # chunk fill built from ops/decode_block.prefill_block, fused
        # tier vs the per-op reference tier, across chunk lengths.  On
        # the CPU proxy both tiers lower to the same XLA ops (the
        # reference tier IS the fused op's CPU path), so wall clock is
        # ~1:1 and the per-chunk HBM-traffic model carries the claim;
        # on TPU the fused tier dispatches the Pallas prefill
        # megakernel with double-buffered page DMA when the layer and
        # chunk fit VMEM.
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.decode_block import (DecodeBlockSpec,
                                                 hbm_traffic_per_chunk,
                                                 prefill_block)

        if on_accel:
            H, Hq, Hkv, D, F, L = 2048, 16, 8, 128, 5504, 4
            BS, MB, NB = 16, 64, 512
            chunks, reps, dt = (64, 128, 256), 10, jnp.bfloat16
        else:
            H, Hq, Hkv, D, F, L = 64, 4, 2, 16, 128, 2
            BS, MB, NB = 8, 16, 64
            chunks, reps, dt = (8, 16, 32), 5, jnp.float32
        spec = DecodeBlockSpec(hidden=H, num_heads=Hq, kv_heads=Hkv,
                               head_dim=D, block_size=BS, norm="rms",
                               activation="swiglu", eps=1e-5, rope=True)

        def mk(*s):
            return jnp.asarray(
                rng.standard_normal(s).astype(np.float32) * 0.05, dt)

        lp = {"ln1_w": mk(L, H) + 1.0, "q_w": mk(L, H, Hq * D),
              "k_w": mk(L, H, Hkv * D), "v_w": mk(L, H, Hkv * D),
              "o_w": mk(L, Hq * D, H), "ln2_w": mk(L, H) + 1.0,
              "gate_w": mk(L, H, F), "up_w": mk(L, H, F),
              "down_w": mk(L, F, H)}
        pool_k = mk(L, NB, BS, Hkv, D)
        pool_v = mk(L, NB, BS, Hkv, D)

        def build(backend, start):
            def fill(x, lp, pk, pv, blk, off, bt_row, mask, cos, sin):
                def body(carry, inp):
                    x = carry
                    layer, k, v = inp
                    x, k, v = prefill_block(
                        x, layer, k, v, blk, off, bt_row, mask, cos,
                        sin, spec=spec, start=start, backend=backend)
                    return x, (k, v)

                x, (pk2, pv2) = jax.lax.scan(body, x, (lp, pk, pv))
                return x, pk2, pv2

            return jax.jit(fill)

        rows = {}
        for Ts in chunks:
            start = Ts                      # one committed chunk ahead
            bt_row = np.full((MB,), -1, np.int32)
            n_blk = -(-(start + Ts) // BS)
            bt_row[:n_blk] = rng.permutation(NB)[:n_blk]
            bt_row = jnp.asarray(bt_row)
            pos = start + jnp.arange(Ts)
            blk = jnp.take(jnp.maximum(bt_row, 0), pos // BS)
            off = pos % BS
            mask = jnp.arange(MB * BS)[None, None, None, :] \
                <= pos[None, None, :, None]
            x = mk(1, Ts, H)
            cos, sin = mk(Ts, D), mk(Ts, D)
            args = (x, lp, pool_k, pool_v, blk, off, bt_row, mask,
                    cos, sin)

            def timeit(fn):
                o = fn(*args)
                jax.block_until_ready(o)
                t0 = time.perf_counter()
                for _ in range(reps):
                    o = fn(*args)
                jax.block_until_ready(o)
                return (time.perf_counter() - t0) / reps

            t_op = timeit(build("xla", start))
            t_fused = timeit(build(None, start))
            hbm = hbm_traffic_per_chunk(spec, F, Ts, MB,
                                        jnp.dtype(dt).itemsize)
            rows[f"T{Ts}"] = {
                "per_op_ms": round(t_op * 1e3, 3),
                "fused_ms": round(t_fused * 1e3, 3),
                "speedup": round(t_op / t_fused, 3),
                "fused_tokens_per_s": round(Ts / t_fused, 1),
                "hbm_bytes_per_chunk_per_op": hbm["per_op_bytes"],
                "hbm_bytes_per_chunk_fused": hbm["fused_bytes"],
            }
        big = rows[f"T{chunks[-1]}"]
        model = hbm_traffic_per_chunk(spec, F, chunks[-1], MB,
                                      jnp.dtype(dt).itemsize)
        out = {
            "metric": "prefill_block_tokens_per_sec",
            "value": big["fused_tokens_per_s"],
            "unit": "tokens/s/chip",
            "vs_baseline": big["speedup"],
            "extra": {"rows": rows, "layers": L, "hidden": H,
                      "heads": f"{Hq}q/{Hkv}kv", "head_dim": D,
                      "ffn": F, "dtype": str(jnp.dtype(dt)),
                      "hbm_model_per_layer_at_max_chunk": model,
                      "device": str(devices[0]),
                      "note": "CPU proxy: both tiers are the same XLA "
                              "program (speedup ~1.0 expected); the "
                              "hbm model is the accelerator-facing win"},
        }
    elif config == "train":
        out = _train_elastic_bench(devices, on_accel, rng)
    else:
        raise SystemExit(f"unknown --config {config!r}")
    return out


def run_bench():
    import jax

    devices = jax.devices()
    on_accel = devices[0].platform == "tpu"
    from paddle_tpu.models.gpt import GPTConfig, build_gpt_train_step
    from paddle_tpu import parallel as dist

    if on_accel:
        cfg = GPTConfig(vocab_size=32768, hidden_size=768, num_layers=12,
                        num_heads=12, max_position_embeddings=1024,
                        dtype="bfloat16")
        batch, seq, steps = 8, 1024, 10
    else:
        cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=4,
                        num_heads=4, max_position_embeddings=256)
        batch, seq, steps = 4, 128, 3

    topo = dist.init_topology(devices=devices[:1])  # single chip
    # remat off on the accelerator: GPT-125M at b8xs1024 bf16 fits HBM
    # with huge margin, and rematerialization would burn ~1/3 extra
    # FLOPs for memory we don't need (pure MFU loss on this config)
    step_fn, init_fn = build_gpt_train_step(cfg, topo, num_microbatches=1,
                                            remat=not on_accel)
    state = init_fn(0)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)

    # warmup / compile (device_get forces a real sync)
    state, loss = step_fn(state, ids, labels)
    jax.device_get(loss)
    state, loss = step_fn(state, ids, labels)
    jax.device_get(loss)

    # measured loop consumes batches staged host→device ahead of compute
    # by the io device-prefetch pipeline (dataloader.py)
    from paddle_tpu.io import device_prefetch_iterator
    t0 = time.perf_counter()
    for ids_d, labels_d in device_prefetch_iterator(
            [(ids, labels)] * steps, size=2):
        state, loss = step_fn(state, ids_d, labels_d)
    loss_val = float(np.asarray(jax.device_get(loss)))
    dt = time.perf_counter() - t0

    tokens = batch * seq * steps
    tps_chip = tokens / dt

    # params (for 6N flops/token) — embeddings included, standard convention
    h, L, V, f = (cfg.hidden_size, cfg.num_layers, cfg.vocab_size,
                  cfg.ffn_size)
    n_params = V * h + cfg.max_position_embeddings * h + L * (
        4 * h * h + 2 * h * f + 9 * h) + 2 * h
    flops_per_token = 6 * n_params + 12 * L * h * seq  # + attention term
    # a CPU has no peak on file: its liveness row carries no MFU
    mfu = tps_chip * flops_per_token / peak_flops_per_chip(devices[0]) \
        if on_accel else None

    out = {
        "metric": "gpt_train_tokens_per_sec_per_chip",
        "value": round(tps_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4) if on_accel else 0.0,
        "extra": {
            "mfu": round(mfu, 4) if on_accel else None,
            "model": f"gpt h{h} L{L} V{V}",
            "batch": batch, "seq": seq, "steps": steps,
            "loss": loss_val,
            "device": str(devices[0]),
            "dtype": cfg.dtype,
            # attribution for BENCH rounds: the GPT step keeps its own
            # in-graph ZeRO leaf Adam (not the optimizer/fused.py path);
            # batches go through the device-prefetch pipeline; the loss
            # runs the logits-free fused CE head (ops/fused_cross_entropy)
            "optimizer_fused": False,
            "device_prefetch": True,
            "fused_head": True,
        },
    }
    if not np.isfinite(loss_val):
        out["extra"]["error"] = (out["extra"].get("error", "")
                                 + " non-finite loss").strip()
    return out


def _bench_telemetry_start():
    """Observability wiring for the measurement child (ISSUE 5): a
    dedicated registry + MemorySink the metric row is routed through,
    and a jax.monitoring CompileMonitor so the row carries the compile-
    time trajectory (extra.n_compiles / extra.compile_secs).  The
    listener only fires during compilation, so the measured steady-state
    loop is untouched.  Optional: BENCH_TELEMETRY_DIR=<dir> additionally
    streams every record to <dir>/bench_metrics.jsonl; BENCH_TELEMETRY=0
    disables the wiring entirely (overhead A/B)."""
    if os.environ.get("BENCH_TELEMETRY") == "0":
        return None
    try:
        from paddle_tpu.observability import (CompileMonitor, JsonlSink,
                                              MemorySink, MetricsRegistry)
    except ImportError:
        return None
    reg = MetricsRegistry(enabled=True)
    sink = MemorySink()
    reg.add_sink(sink)
    jsink = None
    jdir = os.environ.get("BENCH_TELEMETRY_DIR")
    if jdir:
        jsink = JsonlSink(os.path.join(jdir, "bench_metrics.jsonl"))
        reg.add_sink(jsink)
    monitor = CompileMonitor(reg).install()
    return {"registry": reg, "sink": sink, "jsonl": jsink,
            "monitor": monitor}


def _bench_telemetry_finish(tele, out):
    """Stamp compile telemetry onto the row, then route the row itself
    through the registry's event stream — what gets printed is the
    record read back from the sink, so the registry is ON the reporting
    path, not beside it."""
    if tele is None or not isinstance(out, dict):
        return out
    monitor = tele["monitor"]
    monitor.uninstall()
    s = monitor.summary()
    extra = out.setdefault("extra", {})
    extra["n_compiles"] = s["n_compiles"]
    extra["compile_secs"] = s["compile_secs"]
    if s["cache_hits"]:
        extra["compile_cache_hits"] = s["cache_hits"]
    tele["registry"].event("bench_row", **out)
    if tele["jsonl"] is not None:
        tele["jsonl"].close()
    rows = tele["sink"].by_kind("bench_row")
    if rows:
        row = dict(rows[-1])
        row.pop("ts", None)
        row.pop("kind", None)
        return row
    return out


def _child_main() -> None:
    from paddle_tpu.core.device import enable_compile_cache
    enable_compile_cache()
    cfg = os.environ.get("BENCH_CONFIG", "")
    tele = _bench_telemetry_start()
    try:
        out = run_config_bench(cfg) if cfg else run_bench()
        out = _bench_telemetry_finish(tele, out)
    except Exception as e:
        out = {
            "metric": "gpt_train_tokens_per_sec_per_chip",
            "value": 0.0,
            "unit": "tokens/s/chip",
            "vs_baseline": 0.0,
            "failed": True,
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc(limit=5),
        }
    # a CPU-platform measurement is a liveness proxy, never hardware
    # evidence — stamp it unambiguously (VERDICT r4 weak #1)
    dev = str(out.get("extra", {}).get("device", ""))
    if dev and not dev.startswith("TPU"):
        out.setdefault("extra", {})["fallback"] = True
    print(json.dumps(out))


def main() -> None:
    """Watchdog wrapper: run the measurement in ONE subprocess (the
    parent never touches JAX, so the child owns the chip) and bound it
    by wall clock.  Prints exactly one JSON line; a child that fails or
    hangs yields a ``"failed": true`` row and exit code 1."""
    import signal
    import subprocess
    tmo = int(os.environ.get("BENCH_TIMEOUT", "900"))
    env = dict(os.environ, _BENCH_CHILD="1")
    # Popen + new session + killpg: a grandchild that inherited the
    # pipes must not keep communicate() blocked after the child dies
    with open(os.devnull) as devnull:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)],
            stdin=devnull, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, env=env,
            start_new_session=True)
    note = None
    try:
        stdout, stderr = proc.communicate(timeout=tmo)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            pass
        # drain what the child printed before it hung — it may have
        # completed the measurement and hung only at teardown
        stdout, stderr = proc.communicate()
        note = f"bench subprocess timed out ({tmo}s)"
    line = next((ln for ln in reversed(stdout.splitlines())
                 if ln.startswith("{")), None)
    if line:
        try:
            d = json.loads(line)
        except ValueError:
            d = None
        if d is not None:
            if note:
                d.setdefault("extra", {})["watchdog"] = note
            print(json.dumps(d))
            _exit_by_row(d)
    print(json.dumps({
        "metric": "gpt_train_tokens_per_sec_per_chip", "value": 0.0,
        "unit": "tokens/s/chip", "vs_baseline": 0.0, "failed": True,
        "error": note or f"bench subprocess rc={proc.returncode}: "
                         f"{stderr[-400:]}"}))
    sys.exit(1)


def _exit_by_row(d) -> None:
    """A zero-value / errored row must not exit rc=0 (VERDICT r4 weak #5:
    the llama SIGKILL row masqueraded as a measurement)."""
    failed = (not isinstance(d, dict) or d.get("failed")
              or (float(d.get("value") or 0.0) == 0.0 and
                  ("error" in d or "error" in d.get("extra", {}))))
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    # --config lenet|resnet50|bert|llama|moe|serve|decode|optimizer|loss
    #          |train
    # selects a subsystem benchmark row; no flag = the
    # flagship GPT metric (driver contract: ONE JSON line).
    if "--config" in sys.argv:
        os.environ["BENCH_CONFIG"] = sys.argv[sys.argv.index(
            "--config") + 1]
    if os.environ.get("_BENCH_CHILD") == "1":
        _child_main()
    else:
        main()
