"""Fault-tolerant training (ISSUE 2): atomic/async checkpointing with a
verified ``latest`` pointer, ``Model.fit`` auto-resume (bit-exact vs. an
uninterrupted run), SIGTERM drain, and non-finite step-guards.

Crash simulation uses the injection seams in tests/faults.py — a save
killed at a configurable byte offset, or a failed atomic rename — and
asserts the recovery invariant: ``latest`` NEVER resolves to a corrupt
checkpoint."""

import os
import threading

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn
from paddle_tpu.checkpoint import (AsyncCheckpointer, CheckpointManager,
                                   NonFiniteError, latest_checkpoint)
from paddle_tpu.framework import io as fio
from paddle_tpu.framework.io import CheckpointCorruptError
from paddle_tpu.io.dataset import TensorDataset

from faults import (SimulatedCrash, corrupt_file, crash_mid_write,
                    fail_replace, truncate_file)


@pytest.fixture(autouse=True, scope="module")
def _no_persistent_compile_cache():
    """This jax/XLA:CPU build (0.4.37) mis-executes DONATED programs
    DESERIALIZED from the persistent compilation cache: a train step
    loaded from the disk cache can write outside its aliased buffers
    (nondeterministically corrupted params, occasional SIGSEGV), while
    the identical program freshly compiled is bit-exact.  Reproduced
    with a 3-line jit outside this repo; conftest enables the cache with
    min_compile_time=0.0, so every tiny step program here would hit the
    broken path on warm reruns.  The bit-exact resume assertions below
    need trustworthy numerics, so this module opts out of the cache
    (models here are tiny; compile cost is negligible)."""
    import jax

    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    jax.clear_caches()        # drop executables already deserialized
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def _state(step):
    return {"w": pt.Tensor(np.arange(8.0, dtype=np.float32) * step),
            "meta": {"step": step}}


# ---------------------------------------------------------------------------
# atomic framework.io
# ---------------------------------------------------------------------------
class TestAtomicIO:
    def test_round_trip(self, tmp_path):
        p = str(tmp_path / "s.pdckpt")
        fio.save(_state(3), p)
        out = fio.load(p)
        np.testing.assert_array_equal(np.asarray(out["w"]._value),
                                      np.arange(8.0) * 3)
        assert out["meta"]["step"] == 3
        assert fio.verify(p)

    def test_crash_mid_write_preserves_previous(self, tmp_path,
                                                monkeypatch):
        p = str(tmp_path / "s.pdckpt")
        fio.save(_state(1), p)
        with crash_mid_write(monkeypatch, at_bytes=32) as stats:
            with pytest.raises(SimulatedCrash):
                fio.save(_state(2), p)
        assert stats["crashed"] == 1
        # the interrupted save never touched the published file
        out = fio.load(p)
        assert out["meta"]["step"] == 1
        assert fio.verify(p)

    def test_failed_replace_preserves_previous(self, tmp_path,
                                               monkeypatch):
        p = str(tmp_path / "s.pdckpt")
        fio.save(_state(1), p)
        with fail_replace(monkeypatch):
            with pytest.raises(SimulatedCrash):
                fio.save(_state(2), p)
        assert fio.load(p)["meta"]["step"] == 1

    def test_truncated_zip_raises_corrupt_error(self, tmp_path):
        p = str(tmp_path / "s.pdckpt")
        fio.save(_state(1), p)
        truncate_file(p, os.path.getsize(p) // 2)
        with pytest.raises(CheckpointCorruptError):
            fio.load(p)
        with pytest.raises(CheckpointCorruptError):
            fio.verify(p)

    def test_bitrot_raises_corrupt_error(self, tmp_path):
        p = str(tmp_path / "s.pdckpt")
        fio.save(_state(1), p)
        corrupt_file(p, offset=os.path.getsize(p) // 2)
        with pytest.raises(CheckpointCorruptError):
            fio.load(p)

    def test_not_a_zip_raises_corrupt_error(self, tmp_path):
        p = str(tmp_path / "s.pdckpt")
        with open(p, "wb") as f:
            f.write(b"definitely not a checkpoint")
        with pytest.raises(CheckpointCorruptError):
            fio.load(p)

    def test_missing_file_still_filenotfound(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            fio.load(str(tmp_path / "nope.pdckpt"))


# ---------------------------------------------------------------------------
# CheckpointManager: rotation + verified latest pointer
# ---------------------------------------------------------------------------
class TestCheckpointManager:
    def test_rotation_keeps_last_n(self, tmp_path):
        m = CheckpointManager(str(tmp_path), keep_last=2)
        for s in range(1, 6):
            m.save(_state(s), s)
        assert m.all_steps() == [4, 5]
        assert latest_checkpoint(str(tmp_path)).endswith(
            "ckpt-00000005.pdckpt")

    def test_restore_latest(self, tmp_path):
        m = CheckpointManager(str(tmp_path), keep_last=3)
        assert m.restore() is None
        m.save(_state(1), 1)
        m.save(_state(2), 2)
        assert m.restore()["meta"]["step"] == 2

    def test_crash_mid_save_latest_stays_good(self, tmp_path,
                                              monkeypatch):
        m = CheckpointManager(str(tmp_path), keep_last=3)
        m.save(_state(1), 1)
        with crash_mid_write(monkeypatch, at_bytes=16):
            with pytest.raises(SimulatedCrash):
                m.save(_state(2), 2)
        # invariant: latest resolves to the previous GOOD checkpoint
        assert latest_checkpoint(str(tmp_path)).endswith(
            "ckpt-00000001.pdckpt")
        assert m.restore()["meta"]["step"] == 1
        # and a later save recovers cleanly (straggler swept)
        m.save(_state(3), 3)
        assert m.restore()["meta"]["step"] == 3
        assert not [n for n in os.listdir(str(tmp_path))
                    if n.startswith(".tmp-")]

    def test_sweep_spares_another_writers_temp_file(self, tmp_path):
        """``Model.save`` publishes ``epoch_N.*`` in the same directory
        from another thread: the manager's sweep must not unlink that
        writer's in-flight temp file (its ``os.replace`` then failed —
        the async_resume flake), only its own stragglers."""
        m = CheckpointManager(str(tmp_path), keep_last=3)
        theirs = tmp_path / ".tmp-abc123-epoch_3.pdopt"
        ours = tmp_path / ".tmp-def456-ckpt-00000007.pdckpt"
        for f in (theirs, ours):
            f.write_bytes(b"partial")
        m.save(_state(1), 1)
        assert theirs.exists() and not ours.exists()

    def test_crash_before_rename_latest_stays_good(self, tmp_path,
                                                   monkeypatch):
        m = CheckpointManager(str(tmp_path), keep_last=3)
        m.save(_state(1), 1)
        with fail_replace(monkeypatch):
            with pytest.raises(SimulatedCrash):
                m.save(_state(2), 2)
        assert m.restore()["meta"]["step"] == 1

    def test_latest_falls_back_when_pointee_corrupted(self, tmp_path):
        m = CheckpointManager(str(tmp_path), keep_last=3)
        m.save(_state(1), 1)
        p2 = m.save(_state(2), 2)
        corrupt_file(p2, offset=os.path.getsize(p2) // 2)
        assert latest_checkpoint(str(tmp_path)).endswith(
            "ckpt-00000001.pdckpt")

    def test_latest_falls_back_when_pointer_missing(self, tmp_path):
        m = CheckpointManager(str(tmp_path), keep_last=3)
        m.save(_state(1), 1)
        os.unlink(str(tmp_path / "latest"))
        assert latest_checkpoint(str(tmp_path)).endswith(
            "ckpt-00000001.pdckpt")

    def test_empty_dir_has_no_latest(self, tmp_path):
        assert latest_checkpoint(str(tmp_path)) is None


# ---------------------------------------------------------------------------
# AsyncCheckpointer
# ---------------------------------------------------------------------------
class TestAsyncCheckpointer:
    def test_writes_in_background(self, tmp_path):
        with AsyncCheckpointer(CheckpointManager(str(tmp_path),
                                                 keep_last=2)) as ac:
            for s in (1, 2, 3):
                ac.save(_state(s), s)
            assert ac.wait(timeout=30)
        assert ac.last_saved_step == 3
        assert CheckpointManager(str(tmp_path)).restore()["meta"][
            "step"] == 3

    def test_snapshot_isolated_from_caller_mutation(self, tmp_path):
        ac = AsyncCheckpointer(CheckpointManager(str(tmp_path)))
        arr = np.arange(4.0, dtype=np.float32)
        state = {"w": pt.Tensor(arr.copy())}
        ac.save(state, 1)
        # mutate AFTER save returns — the checkpoint must hold the
        # snapshot taken at call time (donated-buffer model)
        state["w"]._value = state["w"]._value * 0 - 7.0
        ac.wait(timeout=30)
        ac.close()
        out = CheckpointManager(str(tmp_path)).restore()
        np.testing.assert_array_equal(np.asarray(out["w"]._value), arr)

    def test_writer_failure_surfaces_on_caller(self, tmp_path,
                                               monkeypatch):
        ac = AsyncCheckpointer(CheckpointManager(str(tmp_path)))
        with crash_mid_write(monkeypatch, at_bytes=8):
            ac.save(_state(1), 1)
            ac._idle.wait(30)
            with pytest.raises(SimulatedCrash):
                ac.wait(timeout=30)
        ac.close()

    def test_close_idempotent(self, tmp_path):
        ac = AsyncCheckpointer(CheckpointManager(str(tmp_path)))
        ac.save(_state(1), 1)
        ac.close()
        ac.close()
        with pytest.raises(RuntimeError):
            ac.save(_state(2), 2)


# ---------------------------------------------------------------------------
# Model.fit resume / SIGTERM / scaler persistence
# ---------------------------------------------------------------------------
def _make_model(max_skips=50, scaler=None):
    net = nn.Sequential(nn.Flatten(), nn.Linear(16, 8), nn.ReLU(),
                        nn.Linear(8, 4))
    m = pt.Model(net)
    m.prepare(
        optimizer=pt.optimizer.Adam(1e-2, parameters=net.parameters()),
        loss=nn.CrossEntropyLoss(), amp_configs=scaler,
        max_consecutive_skips=max_skips)
    return m


def _dataset(n=64):
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, 16)).astype(np.float32)
    Y = rng.integers(0, 4, size=(n,)).astype(np.int64)
    return TensorDataset([X, Y])


def _net_state(m):
    return {k: v.numpy().copy() for k, v in m.network.state_dict().items()}


def _opt_slots(m):
    per = m._optimizer.unflatten_state(m._opt_state)
    return {f"{p}/{s}": np.asarray(v).copy()
            for p, slots in per.items() for s, v in slots.items()}


def _run_scenario(name, tmp_path):
    """Run an end-to-end scenario from ft_scenarios.py in a FRESH
    subprocess.  The bit-exact resume comparisons need cold-compiled
    numerics: inside the long warm-cache pytest process this jax build's
    donated-program/persistent-cache bug (see module fixture) flips them
    nondeterministically, while a fresh process is reliably exact."""
    import subprocess
    import sys

    script = os.path.join(os.path.dirname(__file__), "ft_scenarios.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, script, name, str(tmp_path)],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 0 and f"OK {name}" in proc.stdout, (
        f"scenario {name} failed\n--- stdout ---\n{proc.stdout}\n"
        f"--- stderr ---\n{proc.stderr[-4000:]}")


class TestFitResume:
    def test_epoch_boundary_resume_bit_exact(self, tmp_path):
        _run_scenario("epoch_boundary", tmp_path)

    def test_sigterm_drain_and_midepoch_resume_bit_exact(self, tmp_path):
        _run_scenario("sigterm_midepoch", tmp_path)

    def test_crash_mid_checkpoint_resume_uses_previous(self, tmp_path):
        _run_scenario("crash_mid_checkpoint", tmp_path)

    def test_async_save_resume(self, tmp_path):
        _run_scenario("async_resume", tmp_path)

    def test_resume_restores_loss_scale(self, tmp_path):
        _run_scenario("loss_scale_resume", tmp_path)

    def test_resume_auto_on_fresh_dir_trains_from_scratch(self,
                                                          tmp_path):
        pt.seed(3)
        m = _make_model()
        m.fit(_dataset(), batch_size=16, epochs=1, verbose=0,
              save_dir=str(tmp_path / "fresh"), resume="auto")
        assert m._step_count == 4


class TestModelSaveLoadScaler:
    def test_scaler_state_persisted(self, tmp_path):
        pt.seed(2)
        scaler = pt.amp.GradScaler(init_loss_scaling=2.0 ** 15)
        m = _make_model(scaler=scaler)
        m.fit(_dataset(32), batch_size=16, epochs=1, verbose=0)
        scaler._scale = 64.0
        scaler._good_steps = 17
        path = str(tmp_path / "ck")
        m.save(path)

        m2 = _make_model(scaler=pt.amp.GradScaler())
        assert m2._scaler.get_loss_scaling() == 2.0 ** 15
        m2.load(path)
        assert m2._scaler.get_loss_scaling() == 64.0
        assert m2._scaler._good_steps == 17
        # optimizer moments reach the jit path, not just the eager dict
        assert m2._opt_state is not None
        assert m2._step_count == m._step_count


# ---------------------------------------------------------------------------
# anomaly step-guards
# ---------------------------------------------------------------------------
class TestStepGuard:
    def _batches(self):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(16, 16)).astype(np.float32)
        Y = np.zeros((16,), np.int64)
        Xbad = X.copy()
        Xbad[0, 0] = np.nan
        return X, Xbad, Y

    def test_nonfinite_step_skipped_exactly(self):
        X, Xbad, Y = self._batches()
        pt.seed(0)
        m = _make_model()
        m.train_batch([X], [Y])                 # establish fused state
        sd0, opt0 = _net_state(m), _opt_slots(m)
        step0 = m._step_count

        losses, _ = m.train_batch([Xbad], [Y])  # poisoned batch
        assert not np.isfinite(losses[0])
        sd1, opt1 = _net_state(m), _opt_slots(m)
        for k in sd0:
            np.testing.assert_array_equal(sd0[k], sd1[k], err_msg=k)
        for k in opt0:
            np.testing.assert_array_equal(opt0[k], opt1[k], err_msg=k)
        assert m._step_count == step0           # skipped, not counted
        assert m._step_guard.consecutive == 1

        m.train_batch([X], [Y])                 # training proceeds
        assert m._step_count == step0 + 1
        assert m._step_guard.consecutive == 0

    def test_skip_on_first_step_keeps_fresh_state(self):
        _, Xbad, Y = self._batches()
        pt.seed(0)
        m = _make_model()
        sd0 = _net_state(m)
        m.train_batch([Xbad], [Y])
        sd1 = _net_state(m)
        for k in sd0:
            np.testing.assert_array_equal(sd0[k], sd1[k], err_msg=k)
        assert m._step_count == 0
        for k, v in _opt_slots(m).items():
            if k.endswith("/moment1") or k.endswith("/moment2"):
                assert not np.any(v), k

    def test_loss_scale_backs_off_on_skip(self):
        X, Xbad, Y = self._batches()
        pt.seed(0)
        m = _make_model(scaler=pt.amp.GradScaler(init_loss_scaling=1024.0))
        m.train_batch([X], [Y])
        assert m._scaler.get_loss_scaling() == 1024.0
        m.train_batch([Xbad], [Y])
        assert m._scaler.get_loss_scaling() == 512.0
        m.train_batch([Xbad], [Y])
        assert m._scaler.get_loss_scaling() == 256.0

    def test_consecutive_skips_raise_descriptive_error(self):
        _, Xbad, Y = self._batches()
        pt.seed(0)
        m = _make_model(max_skips=3)
        with pytest.raises(NonFiniteError, match="3 consecutive"):
            for _ in range(10):
                m.train_batch([Xbad], [Y])
        assert m._step_guard.total_skipped == 3

    def test_eager_path_skips_nonfinite(self):
        X, Xbad, Y = self._batches()
        pt.seed(0)
        net = nn.Sequential(nn.Flatten(), nn.Linear(16, 4))
        m = pt.Model(net)
        m.prepare(optimizer=pt.optimizer.SGD(0.1,
                                             parameters=net.parameters()),
                  loss=nn.CrossEntropyLoss(), jit=False)
        m.train_batch([X], [Y])
        sd0 = _net_state(m)
        m.train_batch([Xbad], [Y])
        sd1 = _net_state(m)
        for k in sd0:
            np.testing.assert_array_equal(sd0[k], sd1[k], err_msg=k)
        assert m._step_guard.consecutive == 1

    def test_guard_can_be_disabled(self):
        _, Xbad, Y = self._batches()
        pt.seed(0)
        net = nn.Sequential(nn.Flatten(), nn.Linear(16, 4))
        m = pt.Model(net)
        m.prepare(optimizer=pt.optimizer.SGD(0.1,
                                             parameters=net.parameters()),
                  loss=nn.CrossEntropyLoss(), skip_nonfinite=False)
        step0 = m._step_count
        m.train_batch([Xbad], [Y])
        assert m._step_count == step0 + 1       # legacy behavior


# ---------------------------------------------------------------------------
# DataLoader prefetcher robustness
# ---------------------------------------------------------------------------
class TestPrefetcherRobustness:
    def test_transient_stage_failure_retried(self):
        from paddle_tpu.io.dataloader import _DevicePrefetcher

        attempts = {}

        class Flaky(_DevicePrefetcher):
            BACKOFF_BASE = 0.001

            def _stage(self, item):
                key = float(np.asarray(item).sum())
                attempts[key] = attempts.get(key, 0) + 1
                if attempts[key] < 3:           # fail twice per item
                    raise RuntimeError("transient device hiccup")
                return super()._stage(item)

        pf = Flaky(lambda: iter([np.ones(2, np.float32),
                                 np.zeros(2, np.float32)]), size=2)
        out = list(pf)
        assert len(out) == 2
        np.testing.assert_array_equal(np.asarray(out[0]), np.ones(2))
        assert attempts == {2.0: 3, 0.0: 3}

    def test_persistent_stage_failure_propagates_once(self):
        from paddle_tpu.io.dataloader import _DevicePrefetcher

        class Broken(_DevicePrefetcher):
            BACKOFF_BASE = 0.001

            def _stage(self, item):
                raise RuntimeError("device is gone")

        pf = Broken(lambda: iter([np.ones(2, np.float32)]), size=2)
        with pytest.raises(RuntimeError, match="device is gone"):
            next(pf)
        # exactly once: the iterator is dead, not stuck re-raising
        with pytest.raises(StopIteration):
            next(pf)

    def test_producer_exception_surfaces_exactly_once(self):
        from paddle_tpu.io.dataloader import _DevicePrefetcher

        def produce():
            yield np.ones(2, np.float32)
            raise ValueError("worker exploded")

        pf = _DevicePrefetcher(produce, size=2)
        got = next(pf)
        assert np.asarray(got).shape == (2,)
        with pytest.raises(ValueError, match="worker exploded"):
            next(pf)
        with pytest.raises(StopIteration):
            next(pf)

    def test_close_idempotent_and_join_safe(self):
        from paddle_tpu.io.dataloader import _DevicePrefetcher

        def produce():
            for i in range(100):
                yield np.full(4, float(i), np.float32)

        pf = _DevicePrefetcher(produce, size=2)
        next(pf)
        pf.close()
        pf.close()                              # second close: no-op
        assert not pf._thread.is_alive()
        with pytest.raises(StopIteration):
            next(pf)
        # close from a different thread is also safe
        pf2 = _DevicePrefetcher(produce, size=2)
        t = threading.Thread(target=pf2.close)
        t.start()
        t.join(10)
        pf2.close()

    def test_dataset_exception_through_dataloader(self):
        from paddle_tpu.io import DataLoader

        class Bad(TensorDataset):
            def __getitem__(self, i):
                if i >= 8:
                    raise ValueError("bad sample")
                return super().__getitem__(i)

        rng = np.random.default_rng(0)
        ds = Bad([rng.normal(size=(16, 4)).astype(np.float32)])
        loader = DataLoader(ds, batch_size=4, device_prefetch=2)
        it = iter(loader)
        seen, raised = 0, 0
        while True:
            try:
                next(it)
                seen += 1
            except ValueError:
                raised += 1
            except StopIteration:
                break
        assert seen == 2 and raised == 1
