"""Traffic kind ``train_stream``: packed documents through the program's
``DataLoader`` into the compiled train step, as fast as it goes.

Set-up builds ONE object — the compiled step with its state — drives it
from the seed through its first steps (the same call and feed as the
window's, rows that all differ), keeps what the comparison needs of
them, and hands that same object to the window."""

from __future__ import annotations

import gc
import math
import time
from typing import Dict, List

import numpy as np

from ..lib import compare, model
from ..lib.traffic import PackedDocuments


class Run:
    def __init__(self, ctx, hooks=None):
        self.ctx = ctx
        self.hooks = hooks
        self.train = ctx.config["assumed"]["train"]
        self.prog = model.program_module(ctx.config)
        self.first: Dict = {}
        self.keep_batches = True     # until the followed steps are done

    # -- set-up --------------------------------------------------------
    def set_up(self) -> None:
        import jax
        from paddle_tpu import parallel as dist
        from paddle_tpu.io import DataLoader
        ctx, tr, prog = self.ctx, self.train, self.prog
        cfg = prog.program_config(ctx.config)
        topo = dist.init_topology(devices=list(ctx.devices))
        step_fn, init_fn = prog.build_train_step(cfg, topo, tr)
        self.batch, self.seq = ctx.traffic["batch"], ctx.traffic["seq_len"]
        data = PackedDocuments(ctx.traffic, ctx.seed,
                               ctx.config["vocab_size"])
        self.feed = iter(DataLoader(
            data, batch_size=self.batch, shuffle=False, drop_last=True,
            num_workers=0, device_prefetch=2))
        ctx.phases.mark("build")

        # the state: the program's own layout, holding the reference's
        # draw of the weights and zero moments
        state = init_fn(0)
        mine = prog.make_params(ctx.config, ctx.seed)
        state["params"] = jax.tree.map(
            lambda new, old: jax.device_put(new, old.sharding),
            mine, state["params"])
        del mine
        jax.block_until_ready(state)
        ctx.phases.mark("state")

        ids, labels = self._next()
        lowered = step_fn.lower(state, ids, labels)
        ctx.phases.mark("trace_and_lower")
        self.step = lowered.compile()
        ctx.phases.mark("compile_or_load")
        if self.hooks is not None:
            # tests only: the timed path broken on purpose
            self.step = self.hooks.compiled(self.step)

        # the first steps, through the window's own call and feed
        follow = int(ctx.traffic["follow_steps"])
        losses, batches = [], []
        for k in range(int(ctx.traffic["warmup_steps"])):
            if k:
                ids, labels = self._next()
            if k < follow:
                batches.append(self.true_batch)
            state, loss = self.step(state, ids, labels)
            if k < follow:
                losses.append(float(loss))
            if k == 0:
                gn = prog.first_grad_norms(state, tr)
                gn = jax.tree.map(np.asarray, gn)
            if k == follow - 1:
                dn = prog.param_change_norms(
                    state, prog.make_params(ctx.config, ctx.seed))
                dn = jax.tree.map(np.asarray, dn)
        jax.block_until_ready(state)
        self.keep_batches = False
        self.state = state
        self.first = {"losses": losses, "batches": batches,
                      "grad_norm": compare.flatten_leaves(gn),
                      "delta_norm": compare.flatten_leaves(dn)}

    def _next(self):
        import jax
        with jax.profiler.TraceAnnotation("bench:batch_fetch"):
            ids, labels = next(self.feed)
        ids, labels = ids._value, labels._value
        if self.keep_batches:
            self.true_batch = (np.asarray(ids), np.asarray(labels))
        if self.hooks is not None:
            # tests only: the reference follows the true feed, the
            # program gets what the planted fault makes of it
            return self.hooks.batch(ids, labels)
        return ids, labels

    # -- the window ----------------------------------------------------
    def window(self) -> Dict:
        import jax
        ctx = self.ctx
        state, step = self.state, self.step
        self.state = None
        pending: List = []
        losses: List[float] = []
        steps = traced_steps = 0
        cut_steps, cut_t = None, None
        ctx.window_opens()
        t_open = time.perf_counter()
        while True:
            elapsed = time.perf_counter() - t_open
            if elapsed >= ctx.seconds:
                break
            if ctx.trace_due(elapsed):
                jax.block_until_ready(state)
                cut_steps, cut_t = steps, time.perf_counter() - t_open
                ctx.start_trace()
            ids, labels = self._next()
            with jax.profiler.TraceAnnotation("bench:train_step"):
                state, loss = step(state, ids, labels)
            steps += 1
            traced_steps += ctx.tracing
            pending.append(loss)
            if len(pending) > 2:
                # at most two steps in flight: the host runs ahead of
                # the device, never away from it
                with jax.profiler.TraceAnnotation("bench:wait_loss"):
                    losses.append(float(pending.pop(0)))
        jax.block_until_ready(state)
        window_s = time.perf_counter() - t_open
        ctx.stop_trace()
        losses += [float(x) for x in pending]
        self.state = state
        tokens = steps * self.batch * self.seq
        if cut_steps is None:
            cut_steps, cut_t = steps, window_s
        host_tok_s = cut_steps * self.batch * self.seq / cut_t
        # the step's required work, under the program module's own
        # names: x the steps before the trace, x the traced steps
        work: Dict = {"window_s": cut_t}
        for k, v in self.prog.train_step_work(
                ctx.config, self.batch, self.seq).items():
            work[f"window_{k}"] = v * cut_steps
            work[f"traced_{k}"] = v * traced_steps
        readings = {
            "counters": {"steps": cut_steps, "window_ms": cut_t * 1e3},
            "spans": {}, "work": work}
        return {"attempted": steps,
                "failed": sum(not math.isfinite(x) for x in losses),
                "window_s": window_s,
                "end_to_end": {"train_tok_s": {
                    "value": tokens / window_s, "unit": "tokens/s"}},
                "readings": readings,
                "notes": {"steps": steps, "last_loss": losses[-1],
                          "tok_s_before_trace": host_tok_s}}

    def release(self) -> None:
        self.state = self.step = self.feed = None
        gc.collect()

    # -- correct -------------------------------------------------------
    def verify(self, outcome: Dict) -> List[Dict]:
        ref = model.reference_module(self.ctx.config)
        tr = self.train
        want = ref.train_steps(
            self.ctx.config, self.ctx.seed, self.first["batches"],
            dtype=self.ctx.config["torch_dtype"], lr=tr["learning_rate"],
            betas=tuple(tr["adam_betas"]), eps=tr["adam_eps"])
        return compare.train_checks(self.first, want,
                                    self.ctx.workload["limits"])
