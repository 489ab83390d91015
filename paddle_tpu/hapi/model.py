"""High-level trainer (reference: python/paddle/hapi/model.py —
``Model`` :1082, ``fit`` :1808, ``DynamicGraphAdapter.train_batch`` :847).

Two adapters, mirroring the reference's dygraph/static split but TPU-style:

* ``EagerAdapter`` — op-by-op with tape autograd (``loss.backward()``),
  useful for debugging;
* ``JitAdapter`` (default) — one donated, jit-compiled XLA program per train
  step covering forward+backward+optimizer (the static-graph executor
  equivalent, with zero Python-per-op overhead).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.rng import next_rng_key
from ..core.tensor import Tensor
from ..metric import Metric
from ..nn.layer.layers import (Layer, functional_call_with_buffers,
                               state_arrays)
from .callbacks import CallbackList, ProgBarLogger

__all__ = ["Model"]


def _to_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _np(batch):
    out = []
    for b in _to_list(batch):
        if isinstance(b, Tensor):
            out.append(b._value)
        else:
            out.append(jnp.asarray(np.asarray(b)))
    return out


class Model:
    def __init__(self, network: Layer, inputs=None, labels=None):
        self.network = network
        self._optimizer = None
        self._loss = None
        self._metrics: List[Metric] = []
        self._use_jit = True
        self._jit_step = None
        self._jit_eval = None
        self._opt_state = None
        self._step_count = 0
        self._scaler = None
        self._step_guard = None
        self._skip_nonfinite = True
        self._aot_dir = None
        self._aot_error = None
        self._preempted = False
        # telemetry (observability/): None unless fit(observe=True) is
        # live — the disabled step path pays exactly one `is None` check
        self._telemetry = None
        self._last_step_skipped = False

    # ------------------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None, jit: bool = True,
                skip_nonfinite: bool = True,
                max_consecutive_skips: int = 50,
                aot_dir: Optional[str] = None):
        """``skip_nonfinite`` arms the in-graph anomaly guard (see
        checkpoint/step_guard.py): a step whose loss or grads contain
        NaN/Inf leaves params/moments untouched, backs off the dynamic
        loss scale (when amp is configured), and after
        ``max_consecutive_skips`` back-to-back skips raises
        NonFiniteError.  ``amp_configs`` may be a GradScaler, or a dict
        of GradScaler kwargs (optionally under a ``"scaler"`` key).

        ``aot_dir`` warm-starts the jitted train step from a compile
        artifact written by ``paddle_tpu.aot.export_train_step`` (a
        rotation ROOT — generations + ``latest`` pointer — resolves
        through the pointer):
        matching calls run the DESERIALIZED executable (no trace/lower/
        backend-compile at first step); version skew, corruption, a
        donation-unsafe artifact, or a signature the artifacts don't
        cover falls back to a fresh ``jax.jit`` with an ``aot``
        telemetry event (reason kept on ``self._aot_error``)."""
        from ..checkpoint.step_guard import StepGuard

        self._optimizer = optimizer
        self._loss = loss
        self._metrics = _to_list(metrics)
        self._use_jit = jit
        self._scaler = self._make_scaler(amp_configs)
        self._skip_nonfinite = skip_nonfinite
        self._step_guard = StepGuard(max_consecutive_skips,
                                     scaler=self._scaler)
        self._aot_dir = aot_dir
        self._aot_error = None
        self._jit_step = None      # guard/scaler config changes the program
        return self

    @staticmethod
    def _make_scaler(amp_configs):
        from ..amp.grad_scaler import GradScaler

        if amp_configs is None:
            return None
        if isinstance(amp_configs, GradScaler):
            return amp_configs
        if isinstance(amp_configs, dict):
            if isinstance(amp_configs.get("scaler"), GradScaler):
                return amp_configs["scaler"]
            import inspect as _inspect
            keys = set(_inspect.signature(GradScaler).parameters)
            kwargs = {k: v for k, v in amp_configs.items() if k in keys}
            if kwargs:
                return GradScaler(**kwargs)
        return None

    # ------------------------------------------------------------------
    # jitted step machinery
    # ------------------------------------------------------------------
    def _build_jit_step(self, donate: bool = True):
        net = self.network
        opt = self._optimizer
        loss_layer = self._loss
        guard = self._skip_nonfinite

        trainable_names = {n for n, p in net.named_parameters() if p.trainable}

        def step(params, buffers, opt_state, step_no, lr, rng, loss_scale,
                 inputs, labels):
            def loss_fn(train_params):
                arrays = {**buffers, **params, **train_params}
                net.train()
                outs, new_buffers = functional_call_with_buffers(
                    net, arrays, *inputs, rng=rng)
                outs_l = outs if isinstance(outs, (list, tuple)) else [outs]
                if loss_layer is not None:
                    loss = loss_layer(*outs_l, *labels)
                else:
                    loss = outs_l[0]
                lv = loss._value if isinstance(loss, Tensor) else loss
                outs_v = [o._value if isinstance(o, Tensor) else o
                          for o in outs_l]
                # dynamic loss scaling: differentiate scale*loss, unscale
                # grads below.  scale == 1.0 (amp off) seeds the backward
                # pass with exactly 1.0, so numerics are bit-identical to
                # an unscaled step.
                return lv * loss_scale, (lv, outs_v, new_buffers)

            train_params = {n: v for n, v in params.items()
                            if n in trainable_names}
            (_, (loss_v, outs_v, new_buffers)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(train_params)
            inv_scale = 1.0 / loss_scale
            grads = {n: g * inv_scale for n, g in grads.items()}
            # fused multi-tensor update (optimizer/fused.py): one bucketed
            # kernel instead of a per-param loop; opt_state comes back in
            # fused (flat) form and is threaded through unchanged
            new_train, new_opt_state = opt.apply_gradients_fused(
                train_params, grads, opt_state, lr, step_no)
            kept_buffers = {n: new_buffers.get(n, v)
                            for n, v in buffers.items()}
            if guard:
                # anomaly step-guard (checkpoint/step_guard.py): a scalar
                # where-select keeps the program branch-free and donation-
                # safe — on a non-finite step every param/moment/buffer
                # comes back bit-identical to its input
                from ..checkpoint.step_guard import (guard_select,
                                                     nonfinite_guard)
                from ..optimizer.fused import flatten_state, is_fused_state
                ok = nonfinite_guard(loss_v, grads)
                old_state = opt_state
                if (jax.tree_util.tree_structure(new_opt_state)
                        != jax.tree_util.tree_structure(opt_state)):
                    # first fused step: input state is per-name, output is
                    # flat — express "unchanged" in the output's layout
                    old_state = (flatten_state(opt._fused_active_plan,
                                               opt_state)
                                 if is_fused_state(new_opt_state) else None)
                new_train = guard_select(ok, new_train, train_params)
                if old_state is not None:
                    new_opt_state = guard_select(ok, new_opt_state,
                                                 old_state)
                kept_buffers = guard_select(ok, kept_buffers, buffers)
                notfinite = ~ok
            else:
                notfinite = jnp.zeros((), bool)
            new_params = dict(params)
            new_params.update(new_train)
            return (new_params, kept_buffers, new_opt_state, loss_v,
                    outs_v, notfinite)

        return jax.jit(step, donate_argnums=(0, 1, 2) if donate else ())

    def _make_jit_step(self):
        """AOT warm start when prepare(aot_dir=) was given: deserialize
        the exported train-step executables (aot/train.py) and dispatch
        per call signature; ANY artifact problem falls back to a fresh
        jit with the reason recorded + a telemetry event."""
        if self._aot_dir is not None:
            from ..aot.artifact import AotError
            from ..aot.train import load_train_step
            try:
                return load_train_step(self, self._aot_dir)
            except AotError as e:
                self._aot_error = str(e)
                from ..observability import REGISTRY
                if REGISTRY.enabled:
                    REGISTRY.counter("aot.fallback_total").inc()
                    REGISTRY.event("aot", action="fallback",
                                   dir=self._aot_dir,
                                   reason=str(e)[:300])
        return self._build_jit_step()

    def _split_state(self):
        params = {n: p._value for n, p in self.network.named_parameters()}
        buffers = {n: b._value for n, b in self.network.named_buffers()
                   if b is not None}
        return params, buffers

    def _write_state(self, params, buffers):
        for n, p in self.network.named_parameters():
            p._value = params[n]
        for n, b in self.network.named_buffers():
            if b is not None and n in buffers:
                b._value = buffers[n]

    # ------------------------------------------------------------------
    def train_batch(self, inputs, labels=None, update: bool = True):
        inputs = _np(inputs)
        labels = _np(labels)
        if not self._use_jit:
            return self._train_batch_eager(inputs, labels)
        if self._jit_step is None:
            self._jit_step = self._make_jit_step()
        params, buffers = self._split_state()
        if self._opt_state is None:
            trainable = {n: params[n]
                         for n, p in self.network.named_parameters()
                         if p.trainable}
            self._opt_state = self._optimizer.init_state(trainable)
        lr = self._optimizer.get_lr()
        rng = next_rng_key()
        scale = (self._scaler.get_loss_scaling()
                 if self._scaler is not None and self._scaler.is_enable()
                 else 1.0)
        if self._telemetry is not None:
            # attribute any (re)compile of the step program to its label
            with self._telemetry.compile_monitor.label("jit_train_step"):
                params, buffers, loss_v, outs_v, notfin = \
                    self._invoke_jit_step(params, buffers, lr, rng, scale,
                                          inputs, labels)
        else:
            params, buffers, loss_v, outs_v, notfin = \
                self._invoke_jit_step(params, buffers, lr, rng, scale,
                                      inputs, labels)
        self._write_state(params, buffers)
        loss = float(np.asarray(loss_v))
        skipped = self._skip_nonfinite and bool(np.asarray(notfin))
        if skipped:
            # update applied nothing (where-select kept old state); the
            # guard backs off the loss scale and errors out after too
            # many consecutive skips
            self._record_step_outcome(True, loss)
        else:
            self._record_step_outcome(False, loss)
            self._step_count += 1
        self._optimizer._scheduler_step()
        metrics = self._update_metrics(outs_v, labels)
        return [loss], metrics

    def _invoke_jit_step(self, params, buffers, lr, rng, scale, inputs,
                         labels):
        import warnings
        with warnings.catch_warnings():
            # step 1 donates per-name opt state but returns FUSED (flat)
            # state — those buffers legitimately can't be reused once;
            # every later step aliases them in place
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            params, buffers, self._opt_state, loss_v, outs_v, notfin = \
                self._jit_step(params, buffers, self._opt_state,
                               self._step_count + 1, lr, rng, scale,
                               inputs, labels)
        return params, buffers, loss_v, outs_v, notfin

    def _record_step_outcome(self, skipped: bool, loss: float) -> None:
        self._last_step_skipped = skipped
        if self._step_guard is not None:
            self._step_guard.record(skipped, step=self._step_count + 1,
                                    loss=loss)

    def _train_batch_eager(self, inputs, labels):
        self.network.train()
        t_in = [Tensor(v) for v in inputs]
        t_lab = [Tensor(v) for v in labels]
        outs = self.network(*t_in)
        outs_l = _to_list(outs)
        loss = self._loss(*outs_l, *t_lab) if self._loss else outs_l[0]
        loss.backward()
        loss_f = float(loss.numpy())
        skipped = False
        if self._skip_nonfinite:
            skipped = not np.isfinite(loss_f) or any(
                not bool(np.all(np.isfinite(np.asarray(p.grad._value))))
                for p in (self._optimizer._parameters or [])
                if p.grad is not None)
        if skipped:
            self._record_step_outcome(True, loss_f)
        else:
            self._optimizer.step()
            self._record_step_outcome(False, loss_f)
        self._optimizer.clear_grad()
        self._optimizer._scheduler_step()
        metrics = self._update_metrics([o._value for o in outs_l],
                                       [t._value for t in t_lab])
        return [loss_f], metrics

    def _update_metrics(self, outs_v, labels_v):
        res = []
        for m in self._metrics:
            inter = m.compute(np.asarray(outs_v[0]),
                              *[np.asarray(l) for l in labels_v])
            res.append(m.update(np.asarray(inter)))
        return res

    def eval_batch(self, inputs, labels=None):
        inputs = _np(inputs)
        labels = _np(labels)
        self.network.eval()
        if self._jit_eval is None:
            net = self.network
            loss_layer = self._loss

            def eval_step(params, buffers, inputs, labels):
                arrays = {**buffers, **params}
                net.eval()
                outs, _ = functional_call_with_buffers(net, arrays, *inputs)
                outs_l = _to_list(outs)
                outs_v = [o._value if isinstance(o, Tensor) else o
                          for o in outs_l]
                if loss_layer is not None and labels:
                    loss = loss_layer(*outs_l, *[Tensor(l) for l in labels])
                    return outs_v, loss._value
                return outs_v, jnp.zeros(())

            self._jit_eval = jax.jit(eval_step)
        params, buffers = self._split_state()
        outs_v, loss_v = self._jit_eval(params, buffers, inputs, labels)
        metrics = self._update_metrics(outs_v, labels)
        return [float(np.asarray(loss_v))], metrics

    def predict_batch(self, inputs):
        inputs = _np(inputs)
        self.network.eval()
        outs = self.network(*[Tensor(v) for v in inputs])
        return [o.numpy() for o in _to_list(outs)]

    # ------------------------------------------------------------------
    def fit(self, train_data=None, eval_data=None, batch_size: int = 1,
            epochs: int = 1, eval_freq: int = 1, log_freq: int = 10,
            save_dir: Optional[str] = None, save_freq: int = 1,
            verbose: int = 2, drop_last: bool = False, shuffle: bool = True,
            num_workers: int = 0, callbacks=None, accumulate_grad_batches=1,
            num_iters: Optional[int] = None, device_prefetch: int = 0,
            resume=None, keep_last: int = 5, async_save: bool = False,
            observe=False, observe_dir: Optional[str] = None,
            flight_capacity: int = 256):
        """``save_dir`` additionally maintains rotating fault-tolerant
        checkpoints (checkpoint/CheckpointManager: atomic files, verified
        ``latest`` pointer, ``keep_last`` retention; ``async_save``
        overlaps the disk write with training).  ``resume="auto"``
        restarts from the latest verified checkpoint in ``save_dir``
        (no-op when none exists); ``resume=<path-or-dir>`` restarts from
        an explicit checkpoint.  Restores params, optimizer slots, loss
        scale, step counters, and the sampler/RNG position, continuing
        bit-exact with the uninterrupted run.  While checkpointing is
        active a SIGTERM (preemption notice) flushes a final checkpoint
        at the next batch boundary and raises TrainingPreempted.

        ``observe=True`` lights up the runtime telemetry subsystem
        (observability/): a JSONL metrics stream with per-step loss /
        tokens-per-second / MFU, StepGuard skip and loss-scale-backoff
        events, checkpoint save/verify latency, prefetch queue depth,
        and jax compile/recompile counts — plus a crash flight recorder
        that dumps the last ``flight_capacity`` events to disk when the
        run dies (NonFiniteError, TrainingPreempted/SIGTERM, or any
        other escaping exception).  Files land in ``observe_dir``
        (default: ``<save_dir>/telemetry`` when ``save_dir`` is set,
        else ``./telemetry``); ``observe`` may also BE the directory
        path.  All recording is host-side; with ``observe`` left False
        the step path does no telemetry work."""
        from ..io import DataLoader
        from ..io.dataset import Dataset

        if isinstance(train_data, Dataset):
            train_loader = DataLoader(train_data, batch_size=batch_size,
                                      shuffle=shuffle, drop_last=drop_last,
                                      num_workers=num_workers,
                                      device_prefetch=device_prefetch)
        else:
            train_loader = train_data
        if isinstance(eval_data, Dataset):
            eval_loader = DataLoader(eval_data, batch_size=batch_size)
        else:
            eval_loader = eval_data

        ckpt = None
        if save_dir is not None:
            from ..checkpoint import AsyncCheckpointer, CheckpointManager
            manager = CheckpointManager(save_dir, keep_last=keep_last)
            ckpt = AsyncCheckpointer(manager) if async_save else manager

        session = None
        if observe:
            session = self._start_telemetry(observe, observe_dir,
                                            save_dir, flight_capacity)

        start_epoch, skip_steps, resume_rng = self._apply_resume(
            resume, save_dir)

        cbks = CallbackList(_to_list(callbacks) or [ProgBarLogger(log_freq,
                                                                  verbose)])
        cbks.set_model(self)
        try:
            steps = len(train_loader)
        except TypeError:
            steps = None
        cbks.set_params({"epochs": epochs, "steps": steps,
                         "verbose": verbose,
                         "metrics": ["loss"] + self._metric_names()})

        sig_state = self._install_sigterm(
            enabled=ckpt is not None or session is not None)
        cbks.on_train_begin()
        it = 0
        logs = {}
        try:
            for epoch in range(start_epoch, epochs):
                from ..core.rng import get_rng_state, set_rng_state
                rng_epoch_start = np.array(get_rng_state())
                cbks.on_epoch_begin(epoch)
                for m in self._metrics:
                    m.reset()
                for step, batch in enumerate(train_loader):
                    if skip_steps:
                        # mid-epoch resume: replay the epoch's sampler
                        # order and fast-forward past already-trained
                        # batches; the checkpointed RNG state then takes
                        # over so later draws match the original run
                        skip_steps -= 1
                        if skip_steps == 0 and resume_rng is not None:
                            set_rng_state(resume_rng)
                            resume_rng = None
                        continue
                    inputs, labels = self._unpack(batch)
                    cbks.on_train_batch_begin(step)
                    if session is not None:
                        t_step = time.perf_counter()
                    losses, metrics = self.train_batch(inputs, labels)
                    if session is not None:
                        self._emit_step_telemetry(
                            session, losses[0],
                            time.perf_counter() - t_step, inputs)
                    logs = self._make_logs(losses, metrics)
                    cbks.on_train_batch_end(step, logs)
                    it += 1
                    if self._preempted:
                        if ckpt is not None:
                            self._flush_preempt_checkpoint(
                                ckpt, epoch, step + 1, rng_epoch_start)
                        elif session is not None:
                            # no checkpointing configured: the SIGTERM
                            # contract is still "leave a black box" —
                            # raising here reaches the dump below
                            from ..checkpoint import TrainingPreempted
                            raise TrainingPreempted(
                                "SIGTERM received: no checkpoint "
                                "directory configured; telemetry flight "
                                "record dumped, training state NOT "
                                "saved.")
                    if num_iters is not None and it >= num_iters:
                        break
                cbks.on_epoch_end(epoch, logs)
                if eval_loader is not None and (epoch + 1) % eval_freq == 0:
                    self.evaluate(eval_loader, callbacks=callbacks,
                                  verbose=verbose)
                if save_dir is not None and (epoch + 1) % save_freq == 0:
                    self.save(f"{save_dir}/epoch_{epoch}")
                    if ckpt is not None:
                        ckpt.save(self._checkpoint_payload(
                            epoch + 1, 0, rng_epoch_start),
                            self._step_count)
                if num_iters is not None and it >= num_iters:
                    break
            cbks.on_train_end(logs)
        except BaseException as e:
            # crash flight recorder: NonFiniteError (step-guard abort),
            # TrainingPreempted (the SIGTERM path), or anything else
            # escaping the loop flushes the last N telemetry records.
            # dedup_key keeps the session excepthook from re-dumping the
            # same exception if it also goes unhandled.
            if session is not None:
                session.dump_flight(f"{type(e).__name__}: {e}",
                                    dedup_key=id(e))
            raise
        finally:
            self._restore_sigterm(sig_state)
            if ckpt is not None and hasattr(ckpt, "close"):
                ckpt.close()
            if session is not None:
                self._telemetry = None
                session.close()
        return self

    # -- telemetry machinery (observability/) --------------------------
    def _start_telemetry(self, observe, observe_dir, save_dir,
                         flight_capacity):
        """Open a TelemetrySession and wire it into the per-step path:
        the compiled-step label for compile attribution, and the
        StepGuard so skip/backoff events reach the registry."""
        import os
        from ..observability import TelemetrySession

        directory = (observe_dir
                     or (observe if isinstance(observe, str) else None)
                     or (os.path.join(save_dir, "telemetry")
                         if save_dir is not None else "telemetry"))
        session = TelemetrySession(directory,
                                   flight_capacity=flight_capacity)
        self._telemetry = session
        if self._step_guard is not None:
            self._step_guard.metrics = session.registry
        # cache what MFU needs so the per-step path does no discovery
        self._tele_n_params = sum(
            int(p.size) for p in self.network.parameters())
        # off-TPU there is no peak to divide by: the step record keeps
        # its mfu key with the value null
        import jax
        from ..core.device import on_tpu
        from ..observability import peak_flops_per_chip
        self._tele_peak_flops = peak_flops_per_chip(
            jax.local_devices()[0]) if on_tpu() else None
        return session

    @staticmethod
    def _batch_items(inputs):
        """(examples, items) for rate metrics: ``items`` counts tokens
        (leading two dims) for 2-D+ integer inputs — the LM case —
        else examples.  Shape/dtype are metadata reads; nothing here
        syncs the device."""
        if not inputs:
            return 0, 0
        x = inputs[0]
        v = getattr(x, "_value", x)
        shape = getattr(v, "shape", None)
        if not shape:
            return 1, 1
        examples = int(shape[0])
        dt = getattr(v, "dtype", None)
        try:
            is_int = dt is not None and np.issubdtype(dt, np.integer)
        except TypeError:
            is_int = False
        if is_int and len(shape) >= 2:
            return examples, examples * int(shape[1])
        return examples, examples

    def _emit_step_telemetry(self, session, loss, step_secs, inputs):
        """One host-side record per trained batch: loss, rates, MFU,
        guard state.  Runs AFTER train_batch's device sync (loss is
        already a float), so it adds no extra device round-trip."""
        reg = session.registry
        examples, items = self._batch_items(inputs)
        tokens_per_s = items / step_secs if step_secs > 0 else 0.0
        mfu = round(tokens_per_s * 6.0 * self._tele_n_params
                    / self._tele_peak_flops, 8) \
            if self._tele_peak_flops else None
        guard = self._step_guard
        reg.counter("train.steps_total").inc()
        reg.histogram("train.step_secs", unit="s").record(step_secs)
        reg.gauge("train.loss").set(loss)
        reg.gauge("train.tokens_per_s").set(round(tokens_per_s, 3))
        if self._scaler is not None and self._scaler.is_enable():
            reg.gauge("train.loss_scale").set(
                self._scaler.get_loss_scaling())
        reg.event(
            "step", step=self._step_count, loss=loss,
            step_secs=round(step_secs, 6),
            examples_per_s=round(examples / step_secs, 3)
            if step_secs > 0 else 0.0,
            tokens_per_s=round(tokens_per_s, 3),
            mfu=mfu,
            skipped=self._last_step_skipped,
            consecutive_skips=(guard.consecutive if guard else 0),
            skipped_total=(guard.total_skipped if guard else 0))
        from ..observability.tracing import TRACER
        if TRACER.enabled:
            # training twin of the serve-path request trace: one span
            # per trained batch on the process-wide training timeline
            tr = TRACER.train_trace()
            t1 = tr.now()
            # the first step can predate the lazily-created trace
            # (compile time): clamp into the trace window, keep the
            # true duration in secs=
            tr.add("train_step", max(t1 - step_secs, 0.0), t1,
                   step=self._step_count, loss=float(loss),
                   secs=round(step_secs, 6),
                   skipped=bool(self._last_step_skipped))

    # -- fault tolerance machinery (checkpoint/) -----------------------
    def _checkpoint_payload(self, epoch: int, step_in_epoch: int,
                            rng_epoch_start) -> Dict[str, Any]:
        """Everything fit(resume=...) needs to continue bit-exact: model
        arrays, per-name optimizer slots (fused flat buckets are
        unflattened for portability), loss-scaler state, step counters,
        and the RNG position (current + at epoch start, so a mid-epoch
        resume can replay the epoch's shuffle then fast-forward)."""
        from ..core.rng import get_rng_state

        opt_sd = {}
        if self._optimizer is not None:
            opt_sd = self._optimizer.state_dict()
            if self._opt_state is not None:
                per_name = self._optimizer.unflatten_state(self._opt_state)
                for pname, slots in per_name.items():
                    for sname, v in slots.items():
                        opt_sd[f"{pname}/{sname}"] = Tensor(v)
        return {
            "model": self.network.state_dict(),
            "optimizer": opt_sd,
            "scaler": (self._scaler.state_dict()
                       if self._scaler is not None else None),
            "guard": (self._step_guard.state_dict()
                      if self._step_guard is not None else None),
            "meta": {"version": 1, "epoch": int(epoch),
                     "step_in_epoch": int(step_in_epoch),
                     "global_step": int(self._step_count),
                     "rng_state": np.array(get_rng_state()),
                     "rng_epoch_start": np.array(rng_epoch_start)},
        }

    def _restore_checkpoint_payload(self, payload: Dict[str, Any]) -> dict:
        self.network.set_state_dict(payload["model"])
        opt_sd = payload.get("optimizer") or {}
        if self._optimizer is not None and opt_sd:
            self._optimizer.set_state_dict(opt_sd)
            self._opt_state = self._per_name_opt_state(opt_sd)
        if payload.get("scaler") is not None and self._scaler is not None:
            self._scaler.load_state_dict(payload["scaler"])
        if payload.get("guard") is not None and \
                self._step_guard is not None:
            self._step_guard.load_state_dict(payload["guard"])
        meta = payload.get("meta", {})
        self._step_count = int(meta.get("global_step", 0))
        return meta

    @staticmethod
    def _per_name_opt_state(flat_sd: Dict[str, Any]):
        """'pname/sname' flat checkpoint keys → the per-name slot pytree
        the jitted step threads through (re-fused on the next step).
        Leaves are committed to device: the step donates this pytree, and
        donating host-numpy leaves is where corruption hides."""
        per: Dict[str, Dict[str, Any]] = {}
        for key, v in flat_sd.items():
            if key.startswith("@"):
                continue
            pname, _, sname = key.rpartition("/")
            per.setdefault(pname, {})[sname] = jnp.asarray(
                v._value if isinstance(v, Tensor) else v)
        return per or None

    def _apply_resume(self, resume, save_dir):
        """Returns (start_epoch, steps_to_skip, rng_state_after_skip).

        Also restores the RNG: an epoch-boundary resume places the
        generator exactly where the interrupted run left it; a mid-epoch
        resume first rewinds it to the interrupted EPOCH's start so the
        sampler replays the same shuffle, and the checkpointed mid-epoch
        state is re-applied once the trained batches have been skipped."""
        if resume is None:
            return 0, 0, None
        import os
        from ..checkpoint import latest_checkpoint
        from ..core.rng import set_rng_state
        from ..framework.io import load as _load

        if resume == "auto":
            path = (latest_checkpoint(save_dir)
                    if save_dir is not None else None)
            if path is None:
                return 0, 0, None       # fresh run
        elif isinstance(resume, str) and os.path.isdir(resume):
            path = latest_checkpoint(resume)
            if path is None:
                raise FileNotFoundError(
                    f"resume: no usable checkpoint found in {resume}")
        else:
            path = resume
        meta = self._restore_checkpoint_payload(_load(path))
        skip = int(meta.get("step_in_epoch", 0))
        rng_now = meta.get("rng_state")
        if skip > 0 and meta.get("rng_epoch_start") is not None:
            set_rng_state(meta["rng_epoch_start"])
            return int(meta.get("epoch", 0)), skip, rng_now
        if rng_now is not None:
            set_rng_state(rng_now)
        return int(meta.get("epoch", 0)), skip, None

    def _install_sigterm(self, enabled: bool):
        """Preemption notice → flush a final checkpoint at the next batch
        boundary.  Only installable on the main thread; elsewhere (or
        when checkpointing is off) this is a no-op."""
        self._preempted = False
        if not enabled:
            return None
        import signal

        def _on_sigterm(signum, frame):
            self._preempted = True

        try:
            prev = signal.signal(signal.SIGTERM, _on_sigterm)
        except ValueError:          # not the main thread
            return None
        return (signal, prev)

    def _restore_sigterm(self, sig_state) -> None:
        if sig_state is not None:
            signal, prev = sig_state
            signal.signal(signal.SIGTERM, prev)

    def _flush_preempt_checkpoint(self, ckpt, epoch, next_step,
                                  rng_epoch_start) -> None:
        from ..checkpoint import TrainingPreempted
        ckpt.save(self._checkpoint_payload(epoch, next_step,
                                           rng_epoch_start),
                  self._step_count)
        if hasattr(ckpt, "wait"):
            ckpt.wait()             # the drain must hit disk before exit
        raise TrainingPreempted(
            f"SIGTERM received: checkpoint flushed at epoch {epoch}, "
            f"step {next_step} (global step {self._step_count}); "
            "resume with fit(resume='auto').")

    def evaluate(self, eval_data, batch_size: int = 1, log_freq: int = 10,
                 verbose: int = 2, num_workers: int = 0, callbacks=None,
                 num_iters=None):
        from ..io import DataLoader
        from ..io.dataset import Dataset

        loader = DataLoader(eval_data, batch_size=batch_size) if isinstance(
            eval_data, Dataset) else eval_data
        for m in self._metrics:
            m.reset()
        losses_all = []
        for step, batch in enumerate(loader):
            inputs, labels = self._unpack(batch)
            losses, _ = self.eval_batch(inputs, labels)
            losses_all.append(losses[0])
            if num_iters is not None and step + 1 >= num_iters:
                break
        logs = {"loss": float(np.mean(losses_all)) if losses_all else 0.0}
        for m in self._metrics:
            names = m.name() if isinstance(m.name(), list) else [m.name()]
            vals = m.accumulate()
            vals = vals if isinstance(vals, list) else [vals]
            logs.update(dict(zip(names, vals)))
        return logs

    def predict(self, test_data, batch_size: int = 1, num_workers: int = 0,
                stack_outputs: bool = False, callbacks=None, verbose: int = 1):
        from ..io import DataLoader
        from ..io.dataset import Dataset

        loader = DataLoader(test_data, batch_size=batch_size) if isinstance(
            test_data, Dataset) else test_data
        outputs = []
        for batch in loader:
            inputs, _ = self._unpack(batch, has_labels=False)
            outputs.append(self.predict_batch(inputs))
        if stack_outputs:
            n_out = len(outputs[0])
            return [np.concatenate([o[i] for o in outputs])
                    for i in range(n_out)]
        return outputs

    # ------------------------------------------------------------------
    def _unpack(self, batch, has_labels=True):
        if isinstance(batch, (list, tuple)):
            if has_labels and len(batch) >= 2:
                return _to_list(batch[0]), _to_list(batch[1])
            return _to_list(batch[0]) if len(batch) == 1 else list(batch), []
        return [batch], []

    def _metric_names(self):
        names = []
        for m in self._metrics:
            n = m.name()
            names.extend(n if isinstance(n, list) else [n])
        return names

    def _make_logs(self, losses, metrics):
        logs = {"loss": losses[0]}
        for m, r in zip(self._metrics, metrics):
            names = m.name() if isinstance(m.name(), list) else [m.name()]
            vals = r if isinstance(r, list) else [r]
            logs.update({n: float(np.asarray(v))
                         for n, v in zip(names, vals)})
        return logs

    # ------------------------------------------------------------------
    def save(self, path: str, training: bool = True) -> None:
        from ..framework.io import save as _save
        _save(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            opt_sd = self._optimizer.state_dict()
            if self._opt_state is not None:
                per_name = self._optimizer.unflatten_state(self._opt_state)
                for pname, slots in per_name.items():
                    for sname, v in slots.items():
                        opt_sd[f"{pname}/{sname}"] = Tensor(v)
            opt_sd["@global_step"] = self._step_count
            if self._scaler is not None:
                # resumed runs keep the dynamic loss scale instead of
                # resetting to the 2**15 default
                opt_sd["@scaler"] = self._scaler.state_dict()
            _save(opt_sd, path + ".pdopt")

    def load(self, path: str, skip_mismatch: bool = False, reset_optimizer=False):
        from ..framework.io import load as _load
        state = _load(path + ".pdparams")
        self.network.set_state_dict(state)
        import os
        if not reset_optimizer and self._optimizer is not None and \
                os.path.exists(path + ".pdopt"):
            opt_sd = _load(path + ".pdopt")
            scaler_sd = opt_sd.pop("@scaler", None)
            if scaler_sd is not None and self._scaler is not None:
                self._scaler.load_state_dict(scaler_sd)
            self._step_count = int(opt_sd.pop("@global_step",
                                              self._step_count))
            self._optimizer.set_state_dict(opt_sd)
            # the jitted step threads its own opt-state pytree; rebuild
            # it from the restored slots so resume keeps the moments
            self._opt_state = self._per_name_opt_state(opt_sd)
        return self

    def parameters(self, *args, **kwargs):
        return self.network.parameters(*args, **kwargs)

    def summary(self, input_size=None, dtype=None):
        n_params = sum(p.size for p in self.network.parameters())
        trainable = sum(p.size for p in self.network.parameters()
                        if p.trainable)
        lines = [repr(self.network),
                 f"Total params: {n_params:,}",
                 f"Trainable params: {trainable:,}"]
        text = "\n".join(lines)
        print(text)
        return {"total_params": n_params, "trainable_params": trainable}
