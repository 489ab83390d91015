"""ElasticTrainer — worker-loss detection, mesh reshape with state
carryover, and straggler/SDC defense for :class:`DistributedEngine`
(ISSUE 17).

The training-side twin of ``serving.supervisor.SupervisedEngine``: the
reference's fleet elastic machinery (python/paddle/distributed/fleet
elastic scale-down + resharded resume) reduced to the parts a
single-controller SPMD runtime actually needs:

1. **Failure detection** — every step runs under a watchdog.  Typed
   transient faults (:class:`CollectiveTimeoutError`) are retried with
   bounded exponential backoff; retries exhausted, or a typed
   :class:`WorkerLostError`, declare the worker lost.  A step that
   COMPLETES but blows the step deadline ``deadline_strikes`` times in
   a row is treated the same way (a wedging worker is a failing
   worker).  The deadline check is post-hoc — a truly hung collective
   needs an out-of-process watchdog; in-process we can only observe
   elapsed time between dispatches.

2. **Elastic reshape with state carryover** — on worker loss the mesh
   is rebuilt over the survivors at the nearest valid topology: the
   lost worker's data axis shrinks N→N−1 when the global batch stays
   divisible, else to the largest valid divisor (XLA requires exact
   divisibility for sharded batch dims).  When every lost shard is
   still replicated on some survivor (ZeRO os_g: params/slots carried
   over other axes), state is gathered from the survivors and
   repartitioned onto the new mesh via the ``parallel/sharding.py``
   specs; otherwise the last hardened sharded checkpoint is restored
   (explicit ``reshape=True``) and the data pipeline is replayed
   deterministically from the checkpoint step (per-step
   ``fold_in(run_key, step)`` RNG ≡ PR 2's ``rng_epoch_start``
   discipline).  Either way the post-reshape loss trajectory is
   bit-identical to an uninterrupted run launched at the new topology
   from the same step (pinned in tests/test_parallel_elastic.py).

3. **Straggler + SDC defense** — per-step wall-time tracking over a
   sliding window flags a DEGRADED state (``train.elastic.*`` metrics +
   flight-ring events) when a step exceeds ``straggler_factor`` × the
   window median.  Gradient bit-flips (SDC) are caught in-graph by the
   engine's StepGuard composition (``skip_nonfinite=True``): the
   poisoned update is where-selected away, params/opt-state come back
   bit-identical, and the host-side :class:`StepGuard` counts the skip.

4. **Warm rebuild** — with ``aot_dir`` set, each topology's step
   program is serialized under a per-topology artifact entry
   (``aot/train.py::export_engine_step``): resume at ANY
   previously-seen topology is ZERO backend compiles; a reshape to a
   new topology pays exactly one bounded compile and extends the store
   (``train_elastic_warm`` COMPILE_BUDGET.md row pins both).
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass
from statistics import median
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from ..checkpoint.step_guard import StepGuard
from .checkpoint import load_state_dict, save_state_dict
from .engine import DistributedEngine
from .topology import (AXIS_ORDER, DP_AXIS, SHARDING_AXIS, HybridTopology,
                       get_topology, set_topology)

__all__ = ["ElasticTrainer", "ElasticPolicy", "WorkerLostError",
           "CollectiveTimeoutError", "HEALTHY", "DEGRADED", "RESHAPING"]

HEALTHY = "HEALTHY"
DEGRADED = "DEGRADED"
RESHAPING = "RESHAPING"

_META_FILE = "elastic_meta.pdckpt"


class CollectiveTimeoutError(RuntimeError):
    """A collective timed out — transient until proven persistent: the
    step did NOT commit, so the trainer retries it with backoff."""

    def __init__(self, msg: str = "collective timeout",
                 lost_index: Optional[int] = None, axis: str = DP_AXIS):
        super().__init__(msg)
        self.lost_index = lost_index
        self.axis = axis


class WorkerLostError(RuntimeError):
    """A worker is gone for good.  ``lost_index`` is the flat index of
    the lost device in the current mesh (None when the failing worker
    could not be attributed — the mesh is rebuilt at the SAME topology);
    ``axis`` names the mesh axis the loss is attributed to."""

    def __init__(self, msg: str = "worker lost",
                 lost_index: Optional[int] = None, axis: str = DP_AXIS):
        super().__init__(msg)
        self.lost_index = lost_index
        self.axis = axis


@dataclass
class ElasticPolicy:
    """Knobs for detection, retry, reshape, and defense (documented in
    docs/fault_tolerance.md)."""

    step_deadline_s: float = 60.0     # post-hoc per-step wall budget
    deadline_strikes: int = 2         # consecutive blown deadlines → loss
    max_retries: int = 2              # transient-collective retries/step
    backoff_s: float = 0.05           # first retry sleep
    backoff_factor: float = 2.0       # exponential backoff multiplier
    straggler_window: int = 16        # step-time sliding window
    straggler_factor: float = 3.0     # × window median → DEGRADED
    max_consecutive_skips: int = 3    # StepGuard abort threshold
    checkpoint_every: int = 0         # 0 = only explicit save_checkpoint()
    min_world_size: int = 1           # refuse to shrink below this


class ElasticTrainer:
    """Supervise a :class:`DistributedEngine` through worker loss.

    ``data_fn(step) -> (inputs, labels)`` must be deterministic in
    ``step`` — it is both the training data source and the replay
    mechanism after a checkpoint restore.  RNG is derived per step as
    ``fold_in(key(rng_seed), step)`` so a resumed or reshaped run draws
    the exact keys of an uninterrupted one."""

    def __init__(self, network, optimizer, loss_fn,
                 data_fn: Callable[[int], Any], *,
                 topology: Optional[HybridTopology] = None,
                 sharding_stage: int = 0,
                 policy: Optional[ElasticPolicy] = None,
                 checkpoint_dir: Optional[str] = None,
                 aot_dir: Optional[str] = None,
                 rng_seed: int = 0,
                 recompute: bool = False,
                 amp_dtype: Optional[str] = None,
                 skip_nonfinite: bool = True,
                 metrics=None):
        if metrics is None:
            from ..observability import REGISTRY
            metrics = REGISTRY
        self.metrics = metrics
        self.policy = policy or ElasticPolicy()
        self.data_fn = data_fn
        self.checkpoint_dir = checkpoint_dir
        self.aot_dir = aot_dir
        self.rng_seed = int(rng_seed)
        self._base_key = jax.random.key(self.rng_seed)
        self.topo = topology or get_topology()
        self._engine_kwargs = dict(
            sharding_stage=sharding_stage, recompute=recompute,
            amp_dtype=amp_dtype, skip_nonfinite=skip_nonfinite)
        self._network = network
        self._optimizer = optimizer
        self._loss_fn = loss_fn
        self.engine = DistributedEngine(
            network, optimizer, loss_fn, topology=self.topo,
            **self._engine_kwargs)
        self.guard = StepGuard(
            max_consecutive=self.policy.max_consecutive_skips,
            metrics=metrics)
        self.state = HEALTHY
        self.reshapes = 0
        self.retries = 0
        self.workers_lost = 0
        self.steps_replayed = 0
        self.last_recovery_s = 0.0
        self._step_times: deque = deque(
            maxlen=self.policy.straggler_window)
        self._deadline_strikes = 0
        self._global_batch: Optional[int] = None
        self._last_ckpt_step: Optional[int] = None

    # ------------------------------------------------------------------
    # bookkeeping
    # ------------------------------------------------------------------
    @property
    def global_step(self) -> int:
        return self.engine._step_count

    def _rng_for(self, step: int):
        return jax.random.fold_in(self._base_key, step)

    def _event(self, action: str, **kw) -> None:
        m = self.metrics
        if m is not None and m.enabled:
            m.counter(f"train.elastic.{action}_total").inc()
            m.event("elastic", action=action, step=self.global_step, **kw)

    def _set_state(self, state: str) -> None:
        if state != self.state:
            self.state = state
            m = self.metrics
            if m is not None and m.enabled:
                m.gauge("train.elastic.degraded").set(
                    1.0 if state == DEGRADED else 0.0)
                m.event("elastic", action="state", state=state,
                        step=self.global_step)

    # ------------------------------------------------------------------
    # warm rebuild (per-topology AOT entries)
    # ------------------------------------------------------------------
    def _install_step_fn(self, inputs, labels) -> None:
        """Point ``engine._step_fn`` at this topology's program: the AOT
        entry when one exists (zero compiles), else a fresh compile that
        is immediately exported so the NEXT resume at this topology is
        warm."""
        if self.engine._step_fn is not None:
            return
        if self.engine._state is None:
            self.engine.shard_state()
        if self.aot_dir is None:
            self.engine.build_train_step()
            return
        from ..aot.artifact import AotError
        from ..aot.train import export_engine_step, load_engine_step
        try:
            self.engine._step_fn = load_engine_step(
                self.engine, self.aot_dir, registry=self.metrics)
            self._event("aot_warm_load",
                        topology=dict(self.topo.degrees))
            return
        except AotError as e:
            self._event("aot_fallback", reason=type(e).__name__)
        _, compiled = export_engine_step(
            self.engine, inputs, labels, self.aot_dir,
            registry=self.metrics)
        # export_engine_step left engine._step_fn as the fresh jit; the
        # already-compiled executable is strictly better (no retrace)
        self.engine._step_fn = compiled

    # ------------------------------------------------------------------
    # checkpointing (hardened sharded checkpoint + meta sidecar)
    # ------------------------------------------------------------------
    def _ckpt_state_dict(self) -> Dict[str, Any]:
        # Tensor-wrapped leaves: parameter names contain dots, so the
        # loader's in-place fill must go through Tensor._value (the
        # dotted-path write-back would mis-split the keys)
        from ..core.tensor import Tensor
        params, buffers, opt_state = self.engine._state
        sd: Dict[str, Any] = {
            "params": {n: Tensor(v) for n, v in params.items()},
            "buffers": {n: Tensor(v) for n, v in buffers.items()},
        }
        if opt_state is not None:
            sd["opt"] = {p: {s: Tensor(v) for s, v in slots.items()}
                         for p, slots in opt_state.items()}
        return sd

    def save_checkpoint(self) -> None:
        if self.checkpoint_dir is None:
            raise ValueError("ElasticTrainer(checkpoint_dir=...) unset")
        if self.engine._state is None:
            self.engine.shard_state()
        import os

        from ..framework import io as fio
        save_state_dict(self._ckpt_state_dict(), self.checkpoint_dir,
                        topology=self.topo)
        fio.save({"step": self.engine._step_count,
                  "rng_seed": self.rng_seed,
                  "optimizer": self._optimizer.state_dict(),
                  "guard": self.guard.state_dict()},
                 os.path.join(self.checkpoint_dir, _META_FILE))
        self._last_ckpt_step = self.engine._step_count
        self._event("checkpoint", step=self.engine._step_count)

    def _restore_checkpoint(self) -> int:
        """Load the hardened sharded checkpoint into the CURRENT engine
        (explicit reshape — the saved topology may differ) and return
        the restored step."""
        import os

        from ..framework import io as fio
        meta = fio.load(os.path.join(self.checkpoint_dir, _META_FILE))
        if self.engine._state is None:
            # stage placeholder state at the new topology so the loader
            # has correctly-sharded destination arrays to fill.  The
            # Layer's tensors may be DELETED (the previous engine's
            # donated step consumed them) — only their avals survive, so
            # rebuild zero arrays of the right shape/dtype first.
            import jax.numpy as jnp
            net = self.engine.network
            leaves = list(net.named_parameters()) + [
                (n, b) for n, b in net.named_buffers() if b is not None]
            for _, t in leaves:
                v = t._value
                if isinstance(v, jax.Array) and v.is_deleted():
                    t._value = jnp.zeros(v.shape, v.dtype)
            self.engine.shard_state()
        sd = self._ckpt_state_dict()
        load_state_dict(sd, self.checkpoint_dir, reshape=True)
        params, buffers, opt_state = self.engine._state
        new_params = {n: sd["params"][n]._value for n in params}
        new_buffers = {n: sd["buffers"][n]._value for n in buffers}
        new_opt = None
        if opt_state is not None:
            new_opt = {p: {s: sd["opt"][p][s]._value for s in slots}
                       for p, slots in opt_state.items()}
        self.engine._state = (new_params, new_buffers, new_opt)
        for n, p in self.engine.network.named_parameters():
            if n in new_params:
                p._value = new_params[n]
        self.engine._step_count = int(meta["step"])
        self._optimizer.set_state_dict(meta["optimizer"])
        self.guard.load_state_dict(meta.get("guard", {}))
        return int(meta["step"])

    # ------------------------------------------------------------------
    # reshape policy
    # ------------------------------------------------------------------
    def _valid_degree(self, axis: str, survivors: int) -> int:
        """Largest new degree for ``axis``: ≤ current−1, divides the
        global batch (with the other data axis), and the full mesh fits
        on the survivors.  Falls back through divisors — XLA refuses
        uneven sharded batch dims, so dp 8→7 with batch 8 lands on 4."""
        cur = self.topo.axis_size(axis)
        is_data = axis in (DP_AXIS, SHARDING_AXIS)
        other = int(np.prod([self.topo.axis_size(a)
                             for a in (DP_AXIS, SHARDING_AXIS)
                             if a != axis]))
        fixed = int(np.prod([self.topo.axis_size(a) for a in AXIS_ORDER
                             if a not in (DP_AXIS, SHARDING_AXIS)
                             and a != axis]))
        batch = self._global_batch
        for cand in range(cur - 1, 0, -1):
            if fixed * other * cand > survivors:
                continue
            if fixed * other * cand < self.policy.min_world_size:
                break
            # only data axes shard the batch dim — shrinking pp/mp/sep
            # leaves the per-device batch untouched
            data_deg = cand * other if is_data else other
            if batch is not None and batch % data_deg != 0:
                continue
            return cand
        raise WorkerLostError(
            f"no valid topology below {axis}={cur} for batch "
            f"{batch} on {survivors} survivors "
            f"(min_world_size={self.policy.min_world_size})")

    def _reconstructible(self, lost_axis: str) -> bool:
        """Is every shard the lost worker held still present on some
        survivor?  True when each spec either never shards over
        ``lost_axis`` or is replicated across another axis of size > 1
        (ZeRO os_g: os/grad shards ride the sharding axis, replicated
        over dp)."""
        eng = self.engine
        if not eng.param_specs:
            eng._derive_specs()
        all_specs: List = list(eng.param_specs.values())
        for slots in eng.opt_specs.values():
            all_specs.extend(slots.values())
        repl_product = int(np.prod(
            [self.topo.axis_size(a) for a in AXIS_ORDER if a != lost_axis]))
        for spec in all_specs:
            axes = set()
            for entry in spec:
                if entry is None:
                    continue
                axes.update(entry if isinstance(entry, tuple) else (entry,))
            if lost_axis not in axes:
                continue
            if repl_product <= 1:
                return False
        return True

    def _reshape(self, err) -> None:
        """Tear down the mesh, rebuild over the survivors, and carry or
        restore the training state.  On return the engine is ready to
        (re)execute the step that failed."""
        t0 = time.perf_counter()
        self._set_state(RESHAPING)
        self.workers_lost += 1
        before_step = self.engine._step_count
        lost_index = getattr(err, "lost_index", None)
        axis = getattr(err, "axis", DP_AXIS)
        devices = list(self.topo.mesh.devices.flat)
        if lost_index is not None:
            survivors = [d for i, d in enumerate(devices)
                         if i != int(lost_index)]
            degrees = dict(self.topo.degrees)
            degrees[axis] = self._valid_degree(axis, len(survivors))
        else:
            # unattributed persistent failure: rebuild at the SAME
            # topology (the resume-at-same-topology warm path)
            survivors = devices
            degrees = dict(self.topo.degrees)
        carry = self._reconstructible(axis) if lost_index is not None \
            else True
        host_state = self.engine.host_state() if carry else None
        new_topo = HybridTopology(devices=survivors, **degrees)
        set_topology(new_topo)
        self.topo = new_topo
        self.engine = DistributedEngine(
            self._network, self._optimizer, self._loss_fn,
            topology=new_topo, **self._engine_kwargs)
        replayed = 0
        if carry:
            self.engine.load_host_state(host_state)
        else:
            if self.checkpoint_dir is None:
                raise WorkerLostError(
                    "lost state is not reconstructible from survivors "
                    "and no checkpoint_dir is configured") from err
            restored = self._restore_checkpoint()
            # deterministic replay: same batches (data_fn is a pure
            # function of step) + same fold_in keys ⇒ the replayed
            # trajectory is the uninterrupted one
            self._install_step_fn(*self.data_fn(restored))
            while self.engine._step_count < before_step:
                s = self.engine._step_count
                inputs, labels = self.data_fn(s)
                self.engine.train_batch(inputs, labels,
                                        rng=self._rng_for(s))
                replayed += 1
        self._install_step_fn(*self.data_fn(self.engine._step_count))
        self.steps_replayed += replayed
        self.reshapes += 1
        self.last_recovery_s = time.perf_counter() - t0
        m = self.metrics
        if m is not None and m.enabled:
            m.counter("train.elastic.worker_lost_total").inc()
            m.counter("train.elastic.reshapes_total").inc()
            m.histogram("train.elastic.recovery_s", unit="s").record(
                self.last_recovery_s)
            m.event("elastic", action="reshape",
                    step=self.engine._step_count,
                    carryover=carry, replayed=replayed,
                    degrees={k: v for k, v in degrees.items() if v > 1},
                    world_size=new_topo.world_size,
                    recovery_s=round(self.last_recovery_s, 4),
                    cause=f"{type(err).__name__}: {err}")
        from ..observability.tracing import TRACER
        if TRACER.enabled:
            tr = TRACER.train_trace()
            t1 = tr.now()
            # a reshape can predate the lazily-created trace: clamp
            # into the trace window, keep the true duration in secs=
            tr.add("reshape", max(t1 - self.last_recovery_s, 0.0), t1,
                   carryover=bool(carry), replayed=int(replayed),
                   secs=round(self.last_recovery_s, 6),
                   world_size=int(new_topo.world_size),
                   cause=type(err).__name__)
        self._step_times.clear()
        self._deadline_strikes = 0
        self._set_state(HEALTHY)

    # ------------------------------------------------------------------
    # straggler tracking
    # ------------------------------------------------------------------
    def _observe_step_time(self, dt: float) -> bool:
        """Record one step's wall time; returns True when the step blew
        the deadline (a strike)."""
        m = self.metrics
        if m is not None and m.enabled:
            m.histogram("train.elastic.step_time_s", unit="s").record(dt)
        window = list(self._step_times)
        self._step_times.append(dt)
        if (len(window) >= max(4, self.policy.straggler_window // 4)
                and dt > self.policy.straggler_factor * median(window)):
            self._set_state(DEGRADED)
            self._event("straggler", step_time_s=round(dt, 4),
                        window_median_s=round(median(window), 4))
        elif self.state == DEGRADED:
            self._set_state(HEALTHY)
        if dt > self.policy.step_deadline_s:
            self._deadline_strikes += 1
            self._event("deadline_exceeded", step_time_s=round(dt, 4),
                        strikes=self._deadline_strikes)
            return True
        self._deadline_strikes = 0
        return False

    # ------------------------------------------------------------------
    # the supervised step
    # ------------------------------------------------------------------
    def step(self) -> float:
        """Run ONE training step at the current global step, surviving
        transient collective faults, worker loss (reshape + carryover /
        restore + replay), stragglers, and SDC.  Returns the loss."""
        inputs, labels = self.data_fn(self.global_step)
        arr0 = np.asarray(inputs[0] if isinstance(inputs, (list, tuple))
                          else inputs)
        self._global_batch = int(arr0.shape[0]) if arr0.ndim else None
        self._install_step_fn(inputs, labels)
        attempts = 0
        delay = self.policy.backoff_s
        while True:
            t0 = time.perf_counter()
            try:
                loss = self.engine.train_batch(
                    inputs, labels, rng=self._rng_for(self.global_step))
            except CollectiveTimeoutError as e:
                attempts += 1
                self.retries += 1
                self._event("retry", attempt=attempts,
                            cause=f"{type(e).__name__}: {e}")
                if attempts > self.policy.max_retries:
                    self._reshape(WorkerLostError(
                        f"collective failure persisted through "
                        f"{attempts} attempts: {e}",
                        lost_index=e.lost_index, axis=e.axis))
                    attempts = 0
                    delay = self.policy.backoff_s
                    continue
                time.sleep(delay)
                delay *= self.policy.backoff_factor
                continue
            except WorkerLostError as e:
                self._reshape(e)
                attempts = 0
                delay = self.policy.backoff_s
                continue
            blown = self._observe_step_time(time.perf_counter() - t0)
            if blown and self._deadline_strikes >= \
                    self.policy.deadline_strikes:
                # the step COMMITTED (state advanced) — reshape before
                # the next one rather than re-running this one
                self._reshape(WorkerLostError(
                    f"step deadline ({self.policy.step_deadline_s}s) "
                    f"blown {self._deadline_strikes}x consecutively"))
            break
        self.guard.record(self.engine.last_skipped,
                          step=self.global_step, loss=loss)
        if self.engine.last_skipped:
            self._event("sdc_skip", loss=loss)
        if (self.policy.checkpoint_every
                and self.checkpoint_dir is not None
                and self.global_step % self.policy.checkpoint_every == 0):
            self.save_checkpoint()
        return loss

    def run(self, num_steps: int) -> List[float]:
        """``num_steps`` supervised steps; returns their losses (replay
        after a checkpoint restore happens inside :meth:`step` and is
        not double-counted)."""
        return [self.step() for _ in range(num_steps)]
