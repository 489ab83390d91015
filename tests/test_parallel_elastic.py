"""Elastic distributed training (ISSUE 17, ``parallel/elastic.py``):
worker-loss detection, mesh reshape with state carryover, straggler/SDC
defense, and the warm-rebuild AOT path.

The load-bearing pins:

* **Kill bit-identity** — a dp4 run killed mid-step reshapes to dp3 and
  its post-reshape loss trajectory is BITWISE equal to an uninterrupted
  run launched at the new topology (carryover path), resp. to a run
  launched at the new topology from the same restored checkpoint
  (restore-and-replay path — cross-topology prefixes are not bit-stable,
  so the reference must share the restore point).
* **Zero-compile resume** — resuming at a previously-seen topology with
  ``aot_dir`` set performs ZERO backend compiles (CompileMonitor).
* **SDC skip, not corrupt** — a gradient exponent bit-flip inside the
  traced step leaves params bitwise-unchanged and counts one guard skip.
"""

import time
import types

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn
from paddle_tpu.observability import CompileMonitor
from paddle_tpu.observability.registry import MetricsRegistry
from paddle_tpu.parallel import (CollectiveTimeoutError, ElasticPolicy,
                                 ElasticTrainer, WorkerLostError)
from paddle_tpu.parallel import elastic
from paddle_tpu.parallel.elastic import DEGRADED, HEALTHY
from paddle_tpu.parallel.topology import HybridTopology, set_topology

import faults


@pytest.fixture(autouse=True, scope="module")
def _no_persistent_compile_cache():
    """Same deflake as test_parallel.py: this jax/XLA:CPU build (0.4.37)
    mis-executes DONATED programs DESERIALIZED from the persistent
    compilation cache, and every test here builds several bit-for-bit
    identical tiny step programs — opt the module out so fresh compiles
    keep the bit-identity pins exact."""
    from conftest import disable_persistent_compile_cache

    restore = disable_persistent_compile_cache()
    yield
    restore()


@pytest.fixture(autouse=True)
def reset_topology():
    yield
    set_topology(HybridTopology())  # back to single-device default


def _make_net():
    pt.seed(11)
    return nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))


def _data_fn(batch=12):
    def fn(step):
        r = np.random.default_rng(1000 + step)
        return (r.standard_normal((batch, 16)).astype("float32"),
                r.integers(0, 4, (batch,)).astype("int64"))
    return fn


def _make_trainer(*, dp=1, sharding=1, batch=12, stage=2, **kw):
    topo = HybridTopology(dp=dp, sharding=sharding)
    set_topology(topo)
    net = _make_net()
    opt = pt.optimizer.Adam(parameters=net.parameters(),
                            learning_rate=1e-2)
    return ElasticTrainer(net, opt, nn.CrossEntropyLoss(),
                          _data_fn(batch), topology=topo,
                          sharding_stage=stage, rng_seed=7, **kw)


# ---------------------------------------------------------------------
# reshape with carryover (the tentpole acceptance pin)
# ---------------------------------------------------------------------
def test_kill_dp_reshape_carryover_bit_identical():
    """dp4 killed at step 3 reshapes to dp3 with ZeRO state gathered
    from the survivors; every post-reshape loss is bitwise equal to an
    uninterrupted dp3 run."""
    ref = _make_trainer(dp=3)
    ref_losses = ref.run(6)

    tr = _make_trainer(dp=4)
    with faults.kill_worker_at_step(tr, 3, lost_index=2, axis="dp") as st:
        losses = tr.run(6)

    assert st["fired"] == 1
    assert tr.reshapes == 1 and tr.workers_lost == 1
    assert dict(tr.topo.degrees)["dp"] == 3
    assert tr.topo.world_size == 3
    assert tr.state == HEALTHY
    assert tr.global_step == 6
    # the pin: post-reshape trajectory ≡ uninterrupted run at the new
    # topology (bitwise — no tolerance)
    assert losses[3:] == ref_losses[3:]
    # the pre-kill prefix ran on dp4, the reference on dp3: the same
    # numbers summed in another order, so equal to the last ulp or two,
    # not bitwise (it differed by 1 ulp under jax 0.9 on this CPU)
    np.testing.assert_allclose(losses[:3], ref_losses[:3], rtol=1e-6)


def test_kill_dp8_divisor_fallback():
    """XLA refuses uneven sharded batch dims, so dp 8→7 with global
    batch 8 must fall through the divisors and land on dp4."""
    tr = _make_trainer(dp=8, batch=8)
    with faults.kill_worker_at_step(tr, 1, lost_index=5, axis="dp"):
        losses = tr.run(3)
    assert dict(tr.topo.degrees)["dp"] == 4
    assert tr.reshapes == 1
    assert all(np.isfinite(losses))


def test_unreconstructible_without_checkpoint_raises():
    """Losing a sharding-axis worker with dp=1 loses optimizer shards
    held nowhere else; without a checkpoint that is typed and fatal,
    never silently zero-filled."""
    tr = _make_trainer(sharding=4)
    with faults.kill_worker_at_step(tr, 1, lost_index=1, axis="sharding"):
        with pytest.raises(WorkerLostError,
                           match="not reconstructible"):
            tr.run(3)


# ---------------------------------------------------------------------
# restore + deterministic replay (the non-reconstructible path)
# ---------------------------------------------------------------------
def test_kill_sharding_restores_checkpoint_and_replays(tmp_path):
    """sharding4/dp1 ZeRO shards are NOT reconstructible from survivors:
    the reshape restores the hardened sharded checkpoint (explicit
    ``reshape=True``) and replays the data pipeline deterministically.
    Pin: the continuation is bitwise equal to a reference launched at
    the new topology FROM THE SAME restored checkpoint."""
    ck = str(tmp_path / "ck")
    tr = _make_trainer(sharding=4, checkpoint_dir=ck)
    losses_pre = tr.run(2)
    tr.save_checkpoint()
    with faults.kill_worker_at_step(tr, 4, lost_index=1, axis="sharding"):
        losses_post = tr.run(4)          # steps 2,3 then kill at 4

    assert tr.reshapes == 1
    assert dict(tr.topo.degrees)["sharding"] == 3
    assert tr.steps_replayed == 2        # ckpt@2 → replayed steps 2,3
    assert tr.global_step == 6

    # reference: fresh trainer at the NEW topology, restored from the
    # SAME checkpoint, stepping through the same global steps
    ref = _make_trainer(sharding=3, checkpoint_dir=ck)
    assert ref._restore_checkpoint() == 2
    ref_losses = ref.run(4)              # steps 2,3,4,5
    assert losses_post[2:] == ref_losses[2:]
    assert all(np.isfinite(losses_pre + losses_post))


# ---------------------------------------------------------------------
# transient faults: retry, don't reshape
# ---------------------------------------------------------------------
def test_transient_collective_failures_absorbed_bit_identical():
    """Two injected collective timeouts at one step are absorbed by the
    bounded-backoff retry (the step never committed, so the re-run is
    the SAME step): no reshape, and the whole trajectory is bitwise
    equal to a fault-free run."""
    ref = _make_trainer(dp=2)
    ref_losses = ref.run(4)

    tr = _make_trainer(dp=2,
                       policy=ElasticPolicy(max_retries=2,
                                            backoff_s=0.001))
    with faults.transient_collective_failure(tr, 1, failures=2) as st:
        losses = tr.run(4)
    assert st["raised"] == 2
    assert tr.retries == 2
    assert tr.reshapes == 0 and tr.workers_lost == 0
    assert losses == ref_losses


def test_persistent_collective_failure_escalates_to_reshape():
    """Timeouts past ``max_retries`` are a declared worker loss: the
    attributed device is dropped and training continues on the
    survivors."""
    tr = _make_trainer(dp=4,
                       policy=ElasticPolicy(max_retries=1,
                                            backoff_s=0.001))
    with faults.transient_collective_failure(
            tr, 1, failures=99, lost_index=3, axis="dp"):
        losses = tr.run(3)
    assert tr.reshapes == 1
    assert dict(tr.topo.degrees)["dp"] == 3
    assert all(np.isfinite(losses))


# ---------------------------------------------------------------------
# SDC defense: skip, not corrupt
# ---------------------------------------------------------------------
def test_gradient_bit_flip_skipped_not_committed():
    """A forced all-ones exponent in a gradient element (worst-case
    silent data corruption) must be where-selected away by the in-graph
    guard: params come back BITWISE unchanged, the host guard counts
    exactly one skip, and training continues finite."""
    tr = _make_trainer(dp=2)
    tr.run(1)
    before = tr.engine.host_state()["params"]
    with faults.flip_gradient_bits(tr, 1):
        tr.step()                        # the poisoned step
        after = tr.engine.host_state()["params"]
    assert tr.guard.total_skipped == 1
    assert tr.guard.consecutive == 1
    for n in before:
        np.testing.assert_array_equal(before[n], after[n])
    losses = tr.run(3)                   # poison must not persist
    assert all(np.isfinite(losses))
    assert tr.guard.consecutive == 0


def test_repeated_sdc_aborts_via_guard():
    """``max_consecutive_skips`` poisoned steps in a row must abort
    typed (NonFiniteError) instead of spinning forever."""
    from paddle_tpu.checkpoint.step_guard import NonFiniteError
    tr = _make_trainer(dp=2,
                       policy=ElasticPolicy(max_consecutive_skips=2))
    tr.run(1)
    eng = tr.engine

    def hook(grads, step_no):           # poison EVERY step
        import jax
        import jax.numpy as jnp
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        leaves[0] = jnp.full_like(leaves[0], jnp.inf)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    eng.grad_hook = hook
    eng._step_fn = None
    with pytest.raises(NonFiniteError):
        tr.run(3)
    assert tr.guard.total_skipped == 2


# ---------------------------------------------------------------------
# stragglers and deadlines
# ---------------------------------------------------------------------
def test_straggler_flags_degraded_then_recovers(monkeypatch):
    """The step times are injected: the trainer reads a clock that only
    ``train_batch`` moves, so a loaded host (a slow step among the five
    that fill the window, or after the straggler) decides nothing."""
    tr = _make_trainer(dp=2)
    clock = types.SimpleNamespace(now=0.0)
    monkeypatch.setattr(elastic, "time", types.SimpleNamespace(
        perf_counter=lambda: clock.now, sleep=time.sleep))
    real = tr.engine.train_batch
    took = iter([0.01] * 5 + [0.3, 0.01])

    def timed(inputs, labels=None, rng=None):
        clock.now += next(took)
        return real(inputs, labels, rng=rng)

    monkeypatch.setattr(tr.engine, "train_batch", timed)
    tr.run(5)                            # fill the step-time window
    assert tr.state == HEALTHY
    tr.step()                            # 30x the window's median
    assert tr.state == DEGRADED
    tr.step()                            # next normal step clears it
    assert tr.state == HEALTHY


def test_deadline_strikes_rebuild_same_topology():
    """A worker that keeps blowing the step deadline is treated as lost
    even though steps complete; with no attributable device the mesh is
    rebuilt at the SAME topology (state carried, strike counters
    cleared)."""
    tr = _make_trainer(dp=2)
    tr.run(2)
    before = dict(tr.topo.degrees)
    tr.policy.step_deadline_s = 0.2
    tr.policy.deadline_strikes = 2
    with faults.slow_worker(tr, 0.5, n=2):
        tr.run(2)
    assert tr.reshapes == 1
    assert dict(tr.topo.degrees) == before
    assert tr.topo.world_size == 2
    tr.policy.step_deadline_s = 60.0
    losses = tr.run(1)
    assert np.isfinite(losses[0])
    assert tr.state == HEALTHY


# ---------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------
def test_elastic_metrics_and_events():
    reg = MetricsRegistry(enabled=True)
    records = []

    class _Sink:
        def write(self, rec):
            records.append(rec)

    reg.add_sink(_Sink())
    tr = _make_trainer(dp=4, metrics=reg)
    with faults.kill_worker_at_step(tr, 1, lost_index=0, axis="dp"):
        tr.run(3)
    assert reg.counter("train.elastic.worker_lost_total").value == 1
    assert reg.counter("train.elastic.reshapes_total").value == 1
    assert reg.histogram("train.elastic.recovery_s").count == 1
    assert reg.histogram("train.elastic.step_time_s").count >= 3
    reshape_evts = [r for r in records
                    if r["kind"] == "elastic"
                    and r.get("action") == "reshape"]
    assert len(reshape_evts) == 1
    assert reshape_evts[0]["carryover"] is True
    assert reshape_evts[0]["world_size"] == 3


# ---------------------------------------------------------------------
# warm rebuild: per-topology AOT entries
# ---------------------------------------------------------------------
def test_aot_warm_resume_zero_compiles_bit_identical(tmp_path):
    """Resume at a previously-seen topology+devices must be a pure
    deserialize: ZERO backend compiles, bitwise-identical losses (the
    ``train_elastic_warm`` budget row pins the same number)."""
    aot = str(tmp_path / "aot")
    tr = _make_trainer(dp=2, aot_dir=aot)
    cold = tr.run(2)

    tr2 = _make_trainer(dp=2, aot_dir=aot)
    with CompileMonitor() as mon:
        warm = tr2.run(2)
    assert mon.n_compiles == 0, mon.n_compiles
    assert warm == cold


def test_aot_reshape_extends_store_per_topology(tmp_path):
    """A reshape to a new mesh pays its bounded compile once and
    EXTENDS the store; a later kill landing on the same survivor mesh
    resumes with zero compiles."""
    aot = str(tmp_path / "aot")
    tr = _make_trainer(dp=4, aot_dir=aot)
    with faults.kill_worker_at_step(tr, 1, lost_index=2, axis="dp"):
        tr.run(3)
    assert tr.reshapes == 1

    tr2 = _make_trainer(dp=4, aot_dir=aot)
    with CompileMonitor() as mon:
        tr2.run(1)                       # dp4 entry still present
        with faults.kill_worker_at_step(tr2, 1, lost_index=2, axis="dp"):
            tr2.run(2)                   # dp3@survivors entry present
    assert mon.n_compiles == 0, mon.n_compiles
    assert dict(tr2.topo.degrees)["dp"] == 3


# ---------------------------------------------------------------------
# soak: every fault class in one run
# ---------------------------------------------------------------------
@pytest.mark.slow
def test_elastic_soak_all_fault_classes(tmp_path):
    tr = _make_trainer(dp=4, checkpoint_dir=str(tmp_path / "ck"),
                       aot_dir=str(tmp_path / "aot"),
                       policy=ElasticPolicy(max_retries=2,
                                            backoff_s=0.001,
                                            checkpoint_every=4))
    losses = tr.run(2)
    with faults.transient_collective_failure(tr, 2, failures=2):
        losses += tr.run(2)
    with faults.kill_worker_at_step(tr, 5, lost_index=1, axis="dp"):
        losses += tr.run(2)
    with faults.flip_gradient_bits(tr, 7):
        losses += tr.run(2)
    with faults.slow_worker(tr, 0.3, n=1):
        losses += tr.run(2)
    losses += tr.run(2)
    assert tr.global_step == 12
    assert tr.state == HEALTHY
    assert tr.reshapes == 1 and tr.retries == 2
    assert tr.guard.total_skipped == 1
    assert dict(tr.topo.degrees)["dp"] == 3
    assert all(np.isfinite(losses))
