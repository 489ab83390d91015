"""Runtime telemetry subsystem tests (ISSUE 5): registry thread-safety,
bounded histogram reservoirs, disabled-mode overhead, compile-counter
behaviour across a forced recompile, flight-recorder dump on an injected
``NonFiniteError``, and the end-to-end ``Model.fit(observe=True)``
acceptance path (JSONL stream with step / loss / tokens-per-second /
compile / checkpoint entries)."""

import glob
import json
import os
import sys
import threading

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn
from paddle_tpu.amp import GradScaler
from paddle_tpu.checkpoint import (AsyncCheckpointer, CheckpointManager,
                                   NonFiniteError, StepGuard)
from paddle_tpu.io.dataset import TensorDataset
from paddle_tpu.observability import (REGISTRY, CompileMonitor,
                                      FlightRecorder, JsonlSink,
                                      MemorySink, MetricsRegistry,
                                      TelemetrySession,
                                      peak_flops_per_chip,
                                      write_prometheus)


@pytest.fixture(autouse=True, scope="module")
def _no_persistent_compile_cache():
    """Same opt-out as test_fault_tolerance.py: this jax/XLA:CPU build
    mis-executes DONATED programs deserialized from the persistent
    compilation cache (Model's jitted step donates), and cached
    executables would also make the compile-counter assertions depend
    on warm-cache state."""
    import jax

    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    jax.clear_caches()
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


@pytest.fixture(autouse=True)
def _default_registry_isolation():
    """The process-wide REGISTRY must come out of every test the way it
    went in: disabled and sink-free (instrument definitions may
    accumulate — they are keyed and idempotent)."""
    yield
    REGISTRY.disable()
    for s in REGISTRY.sinks:
        REGISTRY.remove_sink(s)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_counter_get_or_create_and_inc(self):
        reg = MetricsRegistry(enabled=True)
        c = reg.counter("a.total")
        assert reg.counter("a.total") is c
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5

    def test_kind_clash_raises(self):
        reg = MetricsRegistry(enabled=True)
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_gauge(self):
        reg = MetricsRegistry(enabled=True)
        g = reg.gauge("depth")
        assert g.value is None
        g.set(7)
        assert g.value == 7

    def test_histogram_stats(self):
        reg = MetricsRegistry(enabled=True)
        h = reg.histogram("lat", unit="s")
        for v in [1.0, 2.0, 3.0, 4.0]:
            h.record(v)
        snap = h.snapshot()
        assert snap["count"] == 4 and snap["sum"] == 10.0
        assert snap["min"] == 1.0 and snap["max"] == 4.0
        assert snap["mean"] == 2.5
        assert 1.0 <= snap["p50"] <= 4.0
        assert h.percentile(100) == 4.0

    def test_histogram_reservoir_bounded(self):
        reg = MetricsRegistry(enabled=True)
        h = reg.histogram("big", reservoir=16)
        for i in range(10_000):
            h.record(float(i))
        assert h.count == 10_000
        assert h.reservoir_len() <= 16          # memory stays bounded
        assert h.snapshot()["min"] == 0.0       # exact extremes kept
        assert h.snapshot()["max"] == 9999.0

    def test_counter_thread_safety(self):
        reg = MetricsRegistry(enabled=True)
        c = reg.counter("conc")
        n_threads, per_thread = 8, 5000

        def work():
            for _ in range(per_thread):
                c.inc()

        ts = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == n_threads * per_thread    # no lost increments

    def test_histogram_thread_safety(self):
        reg = MetricsRegistry(enabled=True)
        h = reg.histogram("conc_h", reservoir=32)
        n_threads, per_thread = 8, 2000

        def work(k):
            for i in range(per_thread):
                h.record(float(k * per_thread + i))

        ts = [threading.Thread(target=work, args=(k,))
              for k in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert h.count == n_threads * per_thread
        assert h.reservoir_len() <= 32

    def test_event_fanout_and_sink_management(self):
        reg = MetricsRegistry(enabled=True)
        a, b = MemorySink(), MemorySink()
        reg.add_sink(a)
        reg.add_sink(b)
        reg.event("step", step=1)
        reg.remove_sink(b)
        reg.event("step", step=2)
        assert [r["step"] for r in a.records] == [1, 2]
        assert [r["step"] for r in b.records] == [1]
        assert all("ts" in r for r in a.records)

    def test_prometheus_text(self, tmp_path):
        reg = MetricsRegistry(enabled=True)
        reg.counter("train.steps_total").inc(5)
        reg.gauge("io.queue_depth").set(2)
        reg.histogram("step_secs").record(0.25)
        text = reg.prometheus_text()
        assert "# TYPE paddle_tpu_train_steps_total counter" in text
        assert "paddle_tpu_train_steps_total 5" in text
        assert "paddle_tpu_io_queue_depth 2" in text
        assert 'paddle_tpu_step_secs{quantile="0.5"} 0.25' in text
        assert "paddle_tpu_step_secs_count 1" in text
        path = write_prometheus(reg, str(tmp_path / "deep" / "m.prom"))
        assert open(path).read() == text

    def test_jsonl_sink(self, tmp_path):
        reg = MetricsRegistry(enabled=True)
        sink = JsonlSink(str(tmp_path / "nested" / "m.jsonl"))
        reg.add_sink(sink)
        reg.event("step", step=1, loss=np.float32(0.5))  # numpy coerced
        sink.close()
        recs = [json.loads(ln) for ln in open(sink.path)]
        assert recs[0]["step"] == 1 and recs[0]["loss"] == 0.5


class TestDisabledOverhead:
    def test_disabled_records_nothing(self):
        reg = MetricsRegistry(enabled=False)
        sink = MemorySink()
        reg.add_sink(sink)
        c, g, h = reg.counter("c"), reg.gauge("g"), reg.histogram("h")
        c.inc()
        g.set(1.0)
        h.record(1.0)
        reg.event("step", step=1)
        assert c.value == 0 and g.value is None and h.count == 0
        assert sink.records == []

    def test_disabled_step_path_allocates_nothing(self):
        """The acceptance bar: disabled mode adds no per-step work —
        in particular no net allocations on the hot path."""
        reg = MetricsRegistry(enabled=False)
        c = reg.counter("c")
        h = reg.histogram("h")
        for _ in range(32):                     # warm caches
            c.inc()
            h.record(1.0)
            reg.event("step", step=1)
        import gc
        gc.collect()
        before = sys.getallocatedblocks()
        for _ in range(2000):
            c.inc()
            h.record(1.0)
            reg.event("step", step=1)
        delta = sys.getallocatedblocks() - before
        assert delta <= 8, f"disabled telemetry leaked {delta} blocks"

    def test_model_has_no_telemetry_handle_by_default(self):
        m = pt.Model(nn.Linear(4, 2))
        assert m._telemetry is None


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
class TestFlightRecorder:
    def test_ring_bounded_and_ordered(self):
        fr = FlightRecorder(capacity=8)
        for i in range(20):
            fr.record("step", step=i)
        assert len(fr) == 8
        assert [r["step"] for r in fr.last()] == list(range(12, 20))
        assert [r["step"] for r in fr.last(2)] == [18, 19]

    def test_dump_format_and_parent_dirs(self, tmp_path):
        reg = MetricsRegistry(enabled=True)
        reg.counter("train.steps_total").inc(3)
        fr = FlightRecorder(capacity=4, registry=reg)
        for i in range(6):
            fr.record("step", step=i, loss=0.1 * i)
        path = str(tmp_path / "a" / "b" / "dump.json")
        assert fr.dump("NonFiniteError: test", path=path) == path
        blob = json.load(open(path))
        assert blob["version"] == 1
        assert blob["reason"].startswith("NonFiniteError")
        assert blob["n_records"] == 4
        assert blob["records"][-1]["step"] == 5
        assert blob["metrics"]["train.steps_total"]["value"] == 3

    def test_dump_dedup(self, tmp_path):
        fr = FlightRecorder(capacity=4, directory=str(tmp_path))
        fr.record("step", step=1)
        key = id(object())
        assert fr.dump("first", dedup_key=key) is not None
        assert fr.dump("again", dedup_key=key) is None
        assert len(fr.dumps) == 1

    def test_dump_without_directory_is_noop(self):
        fr = FlightRecorder(capacity=4)
        fr.record("x")
        assert fr.dump("nowhere") is None

    def test_excepthook_chain_restores(self):
        fr = FlightRecorder(capacity=4)
        prev = sys.excepthook
        fr.install_excepthook()
        assert sys.excepthook is not prev
        fr.install_excepthook()                 # idempotent
        fr.uninstall_excepthook()
        assert sys.excepthook is prev


# ---------------------------------------------------------------------------
# compile monitor
# ---------------------------------------------------------------------------
class TestCompileMonitor:
    def test_counts_forced_recompile(self):
        import jax
        import jax.numpy as jnp

        reg = MetricsRegistry(enabled=True)
        sink = MemorySink()
        reg.add_sink(sink)
        mon = CompileMonitor(reg)
        mon.install()
        try:
            @jax.jit
            def f(x):
                return x * 3.0 + 1.0

            # build inputs OUTSIDE the label: jnp.ones is itself a
            # jitted computation and would be attributed to "f"
            x4 = jax.device_put(np.ones((4,), np.float32))
            x5 = jax.device_put(np.ones((5,), np.float32))
            with mon.label("f"):
                f(x4).block_until_ready()
            n1 = mon.per_label["f"]["compiles"]
            assert n1 >= 1
            with mon.label("f"):
                f(x4).block_until_ready()       # cached: no compile
            assert mon.per_label["f"]["compiles"] == n1
            with mon.label("f"):
                # new shape forces retrace + recompile
                f(x5).block_until_ready()
            n2 = mon.per_label["f"]["compiles"]
            assert n2 > n1
            assert mon.recompiles("f") == n2 - 1
            assert mon.compile_secs > 0
            assert mon.summary()["n_compiles"] >= 2
        finally:
            mon.uninstall()
        # registry got the same story
        assert reg.counter("jax.compile_total").value >= 2
        phases = {r["phase"] for r in sink.by_kind("compile")}
        assert {"trace", "lower", "compile"} <= phases
        assert any(r["fn"] == "f" for r in sink.by_kind("compile"))

    def test_uninstall_stops_counting(self):
        import jax
        import jax.numpy as jnp

        mon = CompileMonitor()
        mon.install()
        mon.uninstall()
        n0 = mon.n_compiles

        @jax.jit
        def g(x):
            return x - 2.0

        g(jnp.ones((3,))).block_until_ready()
        assert mon.n_compiles == n0


# ---------------------------------------------------------------------------
# step guard telemetry
# ---------------------------------------------------------------------------
class TestStepGuardMetrics:
    def test_skip_and_backoff_counted(self):
        reg = MetricsRegistry(enabled=True)
        sink = MemorySink()
        reg.add_sink(sink)
        scaler = GradScaler(init_loss_scaling=1024.0)
        guard = StepGuard(max_consecutive=10, scaler=scaler, metrics=reg)

        guard.record(True, step=5, loss=float("nan"))
        guard.record(True, step=6, loss=float("inf"))
        guard.record(False, step=7, loss=0.5)

        assert reg.counter("train.skipped_steps_total").value == 2
        assert reg.counter("train.scale_backoff_total").value == 2
        assert guard.total_backoffs == 2
        assert scaler.get_loss_scaling() == 256.0   # 1024 * 0.5 * 0.5
        skips = sink.by_kind("step_skip")
        assert [r["step"] for r in skips] == [5, 6]
        assert skips[-1]["consecutive"] == 2
        backoffs = sink.by_kind("scale_backoff")
        assert backoffs[0]["scale_before"] == 1024.0
        assert backoffs[0]["scale"] == 512.0
        assert reg.gauge("train.consecutive_skips").value == 2

    def test_terminal_raise_still_counts(self):
        reg = MetricsRegistry(enabled=True)
        guard = StepGuard(max_consecutive=2, metrics=reg)
        guard.record(True)
        with pytest.raises(NonFiniteError):
            guard.record(True)
        assert reg.counter("train.skipped_steps_total").value == 2

    def test_metrics_off_is_noop(self):
        guard = StepGuard(max_consecutive=10)
        guard.record(True)
        assert guard.total_skipped == 1         # accounting unaffected


# ---------------------------------------------------------------------------
# checkpoint telemetry
# ---------------------------------------------------------------------------
class TestCheckpointTelemetry:
    def _state(self):
        return {"w": pt.Tensor(np.arange(8.0, dtype=np.float32))}

    def test_manager_save_emits_latency(self, tmp_path):
        sink = MemorySink()
        REGISTRY.add_sink(sink)
        REGISTRY.enable()
        try:
            mgr = CheckpointManager(str(tmp_path), keep_last=2)
            mgr.save(self._state(), 7)
        finally:
            REGISTRY.disable()
            REGISTRY.remove_sink(sink)
        recs = sink.by_kind("checkpoint")
        assert len(recs) == 1
        r = recs[0]
        assert r["phase"] == "save" and r["step"] == 7
        assert r["save_secs"] >= 0 and r["verify_secs"] >= 0
        assert r["bytes"] > 0
        assert REGISTRY.histogram("checkpoint.save_secs").count >= 1

    def test_async_checkpointer_queue_metrics(self, tmp_path):
        sink = MemorySink()
        REGISTRY.add_sink(sink)
        REGISTRY.enable()
        try:
            ck = AsyncCheckpointer(CheckpointManager(str(tmp_path)))
            ck.save(self._state(), 1)
            assert ck.wait(30.0)
            ck.close()
        finally:
            REGISTRY.disable()
            REGISTRY.remove_sink(sink)
        assert REGISTRY.counter("checkpoint.async_saves_total").value >= 1
        assert REGISTRY.gauge("checkpoint.queue_depth").value == 0
        assert REGISTRY.histogram("checkpoint.snapshot_secs").count >= 1
        assert sink.by_kind("checkpoint")       # writer-thread event


# ---------------------------------------------------------------------------
# Model.fit(observe=True) — the acceptance path
# ---------------------------------------------------------------------------
def _make_model(max_skips=50):
    net = nn.Sequential(nn.Flatten(), nn.Linear(16, 8), nn.ReLU(),
                        nn.Linear(8, 4))
    m = pt.Model(net)
    m.prepare(
        optimizer=pt.optimizer.Adam(1e-2, parameters=net.parameters()),
        loss=nn.CrossEntropyLoss(),
        max_consecutive_skips=max_skips)
    return m


def _dataset(n=64, nan_from=None):
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, 16)).astype(np.float32)
    if nan_from is not None:
        X[nan_from:] = np.nan
    Y = rng.integers(0, 4, size=(n,)).astype(np.int64)
    return TensorDataset([X, Y])


class TestFitTelemetry:
    def test_observe_true_produces_jsonl_stream(self, tmp_path):
        pt.seed(0)
        m = _make_model()
        tele_dir = str(tmp_path / "tele")
        m.fit(_dataset(), batch_size=16, epochs=2, verbose=0,
              shuffle=False, save_dir=str(tmp_path / "ckpt"),
              observe=True, observe_dir=tele_dir)

        recs = [json.loads(ln)
                for ln in open(os.path.join(tele_dir, "metrics.jsonl"))]
        kinds = {r["kind"] for r in recs}
        assert {"session", "step", "compile", "checkpoint"} <= kinds

        steps = [r for r in recs if r["kind"] == "step"]
        assert len(steps) == 8                  # 2 epochs x 4 batches
        assert [r["step"] for r in steps] == list(range(1, 9))
        for r in steps:
            assert np.isfinite(r["loss"])
            assert r["tokens_per_s"] > 0
            assert r["step_secs"] > 0
            assert "mfu" in r and r["skipped"] is False

        compiles = [r for r in recs if r["kind"] == "compile"]
        assert any(r["fn"] == "jit_train_step" for r in compiles)
        assert any(r["phase"] == "compile" for r in compiles)

        ckpts = [r for r in recs if r["kind"] == "checkpoint"]
        assert len(ckpts) == 2                  # one per epoch
        assert all(r["total_secs"] > 0 for r in ckpts)

        # prometheus dump written on close; session left no global state
        assert os.path.exists(os.path.join(tele_dir, "metrics.prom"))
        assert not REGISTRY.enabled
        assert m._telemetry is None

    def test_observe_path_shorthand(self, tmp_path):
        pt.seed(0)
        m = _make_model()
        tele_dir = str(tmp_path / "shorthand")
        m.fit(_dataset(32), batch_size=16, epochs=1, verbose=0,
              observe=tele_dir)
        assert os.path.exists(os.path.join(tele_dir, "metrics.jsonl"))

    def test_flight_dump_on_injected_nonfinite(self, tmp_path):
        """Acceptance: an injected non-finite loss produces a flight-
        recorder dump whose last record matches the failing step."""
        pt.seed(0)
        m = _make_model(max_skips=2)
        tele_dir = str(tmp_path / "tele")
        with pytest.raises(NonFiniteError):
            # first batch clean, every later batch poisoned with NaN
            m.fit(_dataset(64, nan_from=16), batch_size=16, epochs=1,
                  verbose=0, shuffle=False, observe=True,
                  observe_dir=tele_dir)

        dumps = glob.glob(os.path.join(tele_dir, "flightrec-*.json"))
        assert len(dumps) == 1
        blob = json.load(open(dumps[0]))
        assert "NonFiniteError" in blob["reason"]
        records = blob["records"]
        # last record is the failing step's skip event: the guard
        # emitted it immediately before raising
        last = records[-1]
        assert last["kind"] == "step_skip"
        assert last["consecutive"] == 2
        assert not np.isfinite(last["loss"])
        # the clean step 1 and the first skip are in the ring too
        assert any(r["kind"] == "step" and r["step"] == 1
                   for r in records)
        assert blob["metrics"]["train.skipped_steps_total"]["value"] == 2
        # session tore down despite the raise
        assert not REGISTRY.enabled
        assert m._telemetry is None

    def test_observe_off_does_no_telemetry(self, tmp_path):
        pt.seed(0)
        m = _make_model()
        m.fit(_dataset(32), batch_size=16, epochs=1, verbose=0)
        assert m._telemetry is None
        assert not os.path.exists("telemetry")


class TestHw:
    def test_peak_flops_table(self):
        class Dev:
            device_kind = "TPU v4"
            platform = "tpu"
        assert peak_flops_per_chip(Dev()) == 275e12
        Dev.device_kind = "TPU v5 lite"      # what a v5e chip reports
        assert peak_flops_per_chip(Dev()) == 197e12
        Dev.device_kind = "cpu"
        Dev.platform = "cpu"
        with pytest.raises(KeyError, match="device_kind 'cpu'"):
            peak_flops_per_chip(Dev())


class TestTelemetrySessionLifecycle:
    def test_nested_sessions_restore_enabled_state(self, tmp_path):
        with TelemetrySession(str(tmp_path / "outer"),
                              crash_hooks=False):
            assert REGISTRY.enabled
            with TelemetrySession(str(tmp_path / "inner"),
                                  crash_hooks=False):
                assert REGISTRY.enabled
            assert REGISTRY.enabled             # outer still live
        assert not REGISTRY.enabled

    def test_close_idempotent(self, tmp_path):
        s = TelemetrySession(str(tmp_path), crash_hooks=False)
        s.close()
        s.close()
        assert not REGISTRY.enabled
