"""What the two serving kinds share: the engine that the configuration's
program module builds, the scripted warm-up, the single-threaded window
that submits what is due and pumps ``ServingFrontend.step``, and the
comparison of served tokens with the reference.

One thread drives everything: ``submit`` and ``step`` take the same
lock in the program, so a second thread could only wait for it; one
loop is the same offered load with less noise."""

from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from . import compare, model
from .traffic import request_plan, seeded_rng


class Req:
    """One request as the client sees it."""
    __slots__ = ("due", "prompt", "max_new", "handle", "token_t",
                 "plan_index")

    def __init__(self, due, prompt, max_new, plan_index):
        self.due, self.prompt, self.max_new = due, prompt, max_new
        self.plan_index = plan_index
        self.handle = None
        self.token_t: List[float] = []


class ServeRun:
    #: set by the kind: does a request wait for its due time (open
    #: loop), or is a backlog kept topped up?
    open_loop = False

    def __init__(self, ctx, hooks=None):
        self.ctx, self.hooks = ctx, hooks
        self.eng_kw = dict(ctx.config["assumed"]["engine"])
        self.prog = model.program_module(ctx.config)

    # -- set-up --------------------------------------------------------
    def set_up(self) -> None:
        import jax
        from paddle_tpu.observability.tracing import TRACER
        from paddle_tpu.serving import ServingFrontend
        ctx, prog = self.ctx, self.prog
        vocab = int(ctx.config["vocab_size"])
        params = prog.make_params(ctx.config, ctx.seed)
        jax.block_until_ready(params)
        ctx.phases.mark("state")
        kw = self.eng_kw
        self.eng = prog.build_engine(prog.program_config(ctx.config),
                                     params, kw)
        del params
        if self.hooks is not None:
            self.hooks.wrap_engine(self.eng)
        self.fe = ServingFrontend(self.eng)
        if ctx.trace:
            TRACER.reset()
            TRACER.enable()
        self.plan = request_plan(ctx.traffic, ctx.seed, ctx.seconds, vocab)
        ctx.phases.mark("build")
        # warm-up: a fixed script that runs every program the mix can
        # reach — each prefill bucket and the decode step — and nothing
        # else: one prompt as long as all buckets together, one short
        rng = seeded_rng(ctx.seed, 5)
        lens = [sum(kw["prefill_buckets"]), min(kw["prefill_buckets"]) // 2]
        hs = [self.fe.submit(rng.integers(0, vocab, n, dtype=np.int32),
                             int(ctx.traffic["warmup_new_tokens"]))
              for n in lens]
        self.fe.run_until_drained(timeout_s=1100)
        if not all(h.state.name == "FINISHED" for h in hs):
            raise RuntimeError(f"warm-up did not finish: {hs}")
        self.counters_at_open = self._counters()

    def _counters(self) -> Dict[str, int]:
        """The program's counts as they stand: every whole number of
        ``engine.stats`` and ``engine.scheduler_stats()`` under its own
        name (a metric file names the ones it reads; a counter the
        program adds later is there without an edit here), beside the
        three the benchmark has always read."""
        e = self.eng
        out = {k: v for src in (e.stats, e.scheduler_stats())
               for k, v in src.items()
               if isinstance(v, int) and not isinstance(v, bool)}
        out.update(decode_steps=e.decode_steps,
                   decode_slot_steps=e.decode_slot_steps,
                   prefill_tokens=e.stats["prefill_tokens_computed"])
        return out

    # -- the window ----------------------------------------------------
    def _submit(self, p: Dict, index: int, due: float) -> Req:
        r = Req(due, p["prompt"], p["max_new"], index)

        def on_token(handle, tok, r=r):
            r.token_t.append(time.monotonic())

        r.handle = self.fe.submit(p["prompt"], p["max_new"],
                                  on_token=on_token)
        return r

    def window(self) -> Dict:
        import jax
        ctx, fe, eng, plan = self.ctx, self.fe, self.eng, self.plan
        arrivals = ctx.traffic["arrivals"]
        min_waiting = int(arrivals.get("min_waiting", 0))
        reqs: List[Req] = []
        lateness: List[float] = []
        nxt = 0
        cut_t = None
        traced: Dict[str, float] = {}
        backlog_mid = None
        ctx.window_opens()
        t_open = time.monotonic()
        while True:
            now = time.monotonic()
            elapsed = now - t_open
            if elapsed >= ctx.seconds:
                break
            if ctx.trace_due(elapsed):
                cut_t = elapsed
                cut_counters = self._counters()
                ctx.start_trace()
            if backlog_mid is None and elapsed >= ctx.seconds / 2:
                backlog_mid = eng.queue_depth
            with jax.profiler.TraceAnnotation("bench:submit"):
                if self.open_loop:
                    while nxt < len(plan) and plan[nxt]["at"] <= elapsed:
                        due = t_open + plan[nxt]["at"]
                        lateness.append(now - due)
                        reqs.append(self._submit(plan[nxt], nxt, due))
                        nxt += 1
                else:
                    while eng.queue_depth < min_waiting \
                            and nxt < len(plan):
                        reqs.append(self._submit(plan[nxt], nxt, now))
                        nxt += 1
            if fe.live_requests == 0:
                # idle: sleep to the next arrival (open loop only; a
                # backlog that ran dry would be a plan too short)
                if nxt >= len(plan):
                    break
                wait = t_open + plan[nxt]["at"] - time.monotonic()
                with jax.profiler.TraceAnnotation("bench:idle_wait"):
                    time.sleep(max(0.0, min(wait, 0.002)))
                continue
            steps0 = eng.decode_steps
            with jax.profiler.TraceAnnotation("bench:engine_step"):
                fe.step()
            if ctx.tracing and eng.decode_steps > steps0:
                ctxs = [int(eng.lengths[s]) for s in range(eng.B)
                        if eng.slots[s] is not None]
                for k, v in self.prog.decode_step_work(
                        ctx.config, ctxs).items():
                    traced[k] = traced.get(k, 0) + v
        t_close = time.monotonic()
        window_s = t_close - t_open
        ctx.stop_trace()
        counters = self._counters()
        backlog_end = eng.queue_depth
        # the window is closed: stop serving, give the pages back
        fe.close(cancel_pending=True)
        leak = eng.kv_leak_report()
        self.leaked = int(leak.get("leaked", 0))
        self.reqs = reqs

        gaps, tokens = [], 0
        for r in reqs:
            ts = [t for t in r.token_t if t <= t_close]
            tokens += len(ts)
            gaps += [b - a for a, b in zip(ts, ts[1:])]
        finished = [r for r in reqs
                    if r.handle.state.name == "FINISHED"
                    and r.handle.finish_t is not None]
        bad = [r for r in reqs if r.handle.state.name in
               ("REJECTED", "TIMED_OUT")]
        end_to_end = {"serve_tok_s": {"value": tokens / window_s,
                                      "unit": "tokens/s"}}
        if gaps:
            end_to_end["itl_p95_ms"] = {
                "value": 1e3 * float(np.percentile(gaps, 95)),
                "unit": "ms"}
        if cut_t is None:
            cut_t, cut_counters = window_s, counters
        readings = self._readings(reqs, t_open, cut_t, cut_counters,
                                  traced) if ctx.trace else {}
        return {"attempted": len(reqs), "failed": len(bad),
                "window_s": window_s, "end_to_end": end_to_end,
                "readings": readings,
                "notes": {"finished": len(finished), "tokens": tokens,
                          "gaps": len(gaps),
                          "backlog_mid": backlog_mid,
                          "backlog_end": backlog_end,
                          "lateness_max_ms": 1e3 * max(lateness, default=0),
                          "decode_steps": counters["decode_steps"]
                          - self.counters_at_open["decode_steps"],
                          "kv": leak}}

    def _readings(self, reqs, t_open, cut_t, cut_counters, traced) -> Dict:
        """Spans, counts and required work of the window BEFORE the
        profiler started (the host clock is clean there), as plain data:
        the tracer is reset when the run is released.

        ``counters``: every count of ``_counters`` as the window's
        difference.  ``iterations``: the engine timeline's span trees
        that lie in that part of the window, one list a scheduler
        iteration, each span ``{"name", "t0", "t1", "attrs"}`` on the
        monotonic clock; ``spans``: the same spans' durations by name,
        and ``ttft`` from the requests' own traces.  ``work``: what the
        program module's ``request_work`` returns, summed over the
        requests as far as each got, as ``window_<name>``; its
        ``decode_step_work`` summed over the traced steps as
        ``traced_<name>``; ``window_s``."""
        from paddle_tpu.observability.tracing import TRACER
        t_cut = t_open + cut_t
        cfg = self.ctx.config
        c0 = self.counters_at_open
        ttft = []
        work: Dict[str, float] = {"window_s": cut_t}
        for r in reqs:
            n = sum(t <= t_cut for t in r.token_t)
            if n:
                for k, v in self.prog.request_work(
                        cfg, len(r.prompt), n).items():
                    work[f"window_{k}"] = work.get(f"window_{k}", 0) + v
            tr = r.handle.trace
            first = tr.meta.get("ttft_s") if tr is not None else None
            if first is not None and tr.mono_t0 + first <= t_cut:
                ttft.append(tr.mono_t0 + first - r.due)
        for k, v in traced.items():
            work[f"traced_{k}"] = v
        iterations, spans = [], {"ttft": ttft}
        tl = TRACER.timeline()
        for it in tl.iterations() if tl is not None else []:
            root = it.spans[0]
            if root.t0 < t_open or root.t1 > t_cut:
                continue
            iterations.append([{"name": s.name, "t0": s.t0, "t1": s.t1,
                                "attrs": s.attrs or {}} for s in it.spans])
            for s in it.spans:
                spans.setdefault(s.name, []).append(s.t1 - s.t0)
        return {"counters": {k: cut_counters[k] - c0.get(k, 0)
                             for k in cut_counters},
                "spans": spans, "iterations": iterations, "work": work}

    def release(self) -> None:
        from paddle_tpu.observability.tracing import TRACER
        if self.ctx.trace:
            TRACER.disable()
            TRACER.reset()
        self.eng = self.fe = None
        gc.collect()

    # -- correct -------------------------------------------------------
    def sample(self) -> List[Req]:
        """Finished requests to compare: the longest, and others drawn
        from the seed."""
        done = [r for r in self.reqs
                if r.handle.state.name == "FINISHED"]
        if not done:
            return []
        n = int(self.ctx.traffic["check_requests"])
        done.sort(key=lambda r: r.plan_index)
        longest = max(done, key=lambda r: len(r.prompt) + r.max_new)
        rest = [r for r in done if r is not longest]
        pick = seeded_rng(self.ctx.seed, 4).permutation(
            len(rest))[:n - 1]
        return [longest] + [rest[i] for i in pick]

    def verify(self, outcome: Dict) -> List[Dict]:
        ctx = self.ctx
        ref = model.reference_module(ctx.config)
        chosen = self.sample()
        limits = ctx.workload["limits"]
        if not chosen:
            gaps = {"widest_gap": None, "mean_gap": None, "tokens": 0}
        else:
            seqs = [np.concatenate([r.prompt, np.asarray(
                r.handle.tokens(), np.int32)]) for r in chosen]
            tm = ctx.traffic
            pad = -(-(tm["prompt_tokens"]["max"]
                      + tm["output_tokens"]["max"]) // 128) * 128
            gaps = ref.served_gaps(
                ctx.config, ctx.seed, seqs, [len(r.prompt) for r in chosen],
                pad, ctx.config["torch_dtype"],
                control=getattr(self.hooks, "control", ""))
        self.gaps = gaps
        return compare.serve_checks(gaps, self.leaked, limits)
