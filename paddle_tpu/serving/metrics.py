"""Serve-path telemetry: the metric facade the streaming front-end and
the load generator record through.

Everything goes to the PR 5 :class:`~paddle_tpu.observability.
MetricsRegistry` (the process-wide ``REGISTRY`` by default), so the
serve metrics ride the existing sinks unchanged: the JSONL stream, the
Prometheus dump, and — load-bearing for incident forensics — the
:class:`~paddle_tpu.observability.FlightRecorder` ring, which means a
crash anywhere in the process captures the last N ``serve`` lifecycle
events (submits, rejects, timeouts, cancels, finishes) in its black-box
dump with no extra wiring.

Metric catalogue (all names under ``serve.``; docs/serving.md):

===============================  =========  =============================
name                             kind       meaning
===============================  =========  =============================
serve.submitted_total            counter    requests accepted by admission
serve.rejected_total             counter    requests refused at submit
serve.timeouts_total             counter    deadline / max_queue_time kills
serve.cancelled_total            counter    client-initiated cancels
serve.finished_total             counter    requests that ran to completion
serve.tokens_streamed_total      counter    tokens delivered to handles
serve.queue_depth                gauge      engine waiting-queue length
serve.batch_occupancy            gauge      busy decode slots / max_batch
serve.kv_utilization             gauge      1 - free_blocks / num_blocks
serve.kv_free_blocks             gauge      free pool pages right now
serve.ttft_secs                  histogram  submit -> first streamed token
serve.tpot_secs                  histogram  inter-token latency (decode)
serve.e2e_secs                   histogram  submit -> finish (FINISHED only)
serve.backpressure_wait_secs     histogram  producer blocked on full stream
===============================  =========  =============================

Speculative-decode rows (``serve.spec.*``, live only when the engine
has a ``spec_config``; counters recorded by ``spec_decode/runner.py``,
gauges refreshed here per scheduler iteration; docs/spec_decode.md):

================================  =========  ============================
serve.spec.steps_total            counter    draft/verify/commit rounds
serve.spec.proposed_total         counter    draft tokens proposed
serve.spec.accepted_total         counter    proposals verify accepted
serve.spec.emitted_total          counter    tokens committed via spec
serve.spec.rollback_pages_total   counter    pages holding rolled-back KV
serve.spec.accepted_per_step      histogram  accepted per slot per round
serve.spec.acceptance_rate        gauge      cumulative accepted/proposed
serve.spec.steps_per_token        gauge      per-slot decode steps/token
                                             (baseline == 1.0; < 1.0 is
                                             the speculation win)
================================  =========  ============================

Resilience rows (``serve.resilience.*``; counters/histograms recorded
by ``inference/serving.py`` preemption hooks and
``serving/resilience.py``'s :class:`SupervisedEngine`; gauges refreshed
here per scheduler iteration; docs/serving.md):

========================================  =========  ==================
serve.resilience.preemptions_total        counter    running requests evicted (KV spilled)
serve.resilience.restores_total           counter    preempted requests resumed
serve.resilience.spilled_bytes            gauge      host-RAM KV spill tier size
serve.resilience.spilled_requests         gauge      requests currently spilled
serve.resilience.preempt_save_secs        histogram  snapshot+spill latency
serve.resilience.preempt_restore_secs     histogram  restore-into-fresh-blocks latency
serve.resilience.spill_evictions_total    counter    snapshots evicted by the bounded tier
serve.resilience.prefix_replays_total     counter    demoted requests replayed from prefix
serve.resilience.transient_retries_total  counter    retried transient step faults
serve.resilience.slow_steps_total         counter    steps past the slow-step budget
serve.resilience.crashes_total            counter    declared engine crashes
serve.resilience.recoveries_total         counter    successful rebuild+replay cycles
serve.resilience.replayed_requests_total  counter    requests replayed across crashes
serve.resilience.recovery_secs            histogram  teardown->replayed latency
serve.resilience.circuit_open_total       counter    recoveries refused (breaker open)
========================================  =========  ==================

Fleet rows (``serve.fleet.*``, live only when the front-end drives an
``EngineRouter``; counters recorded by ``serving/fleet.py``, gauges
refreshed here per scheduler iteration from ``fleet_stats()``;
docs/serving.md).  The per-replica ``serve.*`` state rolls up into the
fleet gauges — one flight-ring dump shows the whole fleet's health at
the crash:

==========================================  =========  ==============
serve.fleet.replicas                        gauge      fleet size (incl. dead)
serve.fleet.healthy / degraded /            gauge      health census by state
  draining / dead
serve.fleet.queue_depth                     gauge      summed replica queues
serve.fleet.batch_occupancy                 gauge      mean over live replicas
serve.fleet.kv_utilization                  gauge      aggregate pool pressure
serve.fleet.placements_total                counter    requests placed
serve.fleet.replacements_total              counter    cross-replica re-placements
serve.fleet.snapshot_migrations_total       counter    re-placements that moved KV bytes
serve.fleet.rebalanced_total                counter    stuck waiters migrated
serve.fleet.replica_deaths_total            counter    replicas declared dead
serve.fleet.drains_total                    counter    graceful drains started
==========================================  =========  ==============

Prefix-cache rows (``serve.prefix.*``, ISSUE 14; counters recorded by
``inference/serving.py`` admission/eviction hooks, gauges refreshed
here per scheduler iteration from ``prefix_stats()``;
docs/serving.md).  Lookups count admission-time cache consultations
(hit or miss); hit_tokens are prompt tokens whose prefill the cache
skipped — the direct prefill-FLOP savings meter:

==========================================  =========  ==============
serve.prefix.lookups_total                  counter    admissions that consulted the cache
serve.prefix.hits_total                     counter    admissions claiming >= 1 cached block
serve.prefix.hit_tokens_total               counter    prompt tokens NOT re-prefilled
serve.prefix.inserts_total                  counter    blocks registered into the radix tree
serve.prefix.evictions_total                counter    resident blocks evicted under pressure
serve.prefix.offloads_total                 counter    evicted blocks parked in host RAM
serve.prefix.restores_total                 counter    offloaded blocks restored by byte scatter
serve.prefix.restore_failures_total         counter    CRC failures at restore (recompute fallback)
serve.prefix.cached_blocks                  gauge      HBM-resident cached blocks
serve.prefix.offloaded_blocks               gauge      host-RAM tier blocks
serve.prefix.offloaded_bytes                gauge      host-RAM tier size
serve.prefix.hit_rate                       gauge      cumulative hits / lookups
serve.fleet.affinity_hits_total             counter    placements won by prefix affinity
serve.fleet.affinity_capped_total           counter    affinity overridden by the anti-herd cap
==========================================  =========  ==============

HTTP wire rows (``serve.http.*``, live only when requests arrive over
the network front door — ``serving/http.py``; docs/serving.md).  The
wire is where real traffic's failures originate, so every failure mode
the server absorbs is a counter:

==========================================  =========  ==============
serve.http.connections_total                counter    accepted HTTP connections
serve.http.active_connections               gauge      connections being served now
serve.http.requests_total                   counter    /v1/generate bodies parsed
serve.http.disconnect_cancels_total         counter    mid-stream client disconnects
                                                       that cancelled the request
serve.http.dedup_hits_total                 counter    retries attached to a live or
                                                       finished stream (no double submit)
serve.http.write_stall_timeouts_total       counter    SSE writes past the per-connection
                                                       deadline (stalled reader isolated)
serve.http.abandoned_total                  counter    graced disconnects never retried
serve.http.shutdown_drain_secs              histogram  SIGTERM -> drained latency
==========================================  =========  ==============

Every recording entry point checks ``registry.enabled`` first, so a
front-end without telemetry pays one branch per call (the PR 5
zero-cost-disabled contract).  All of this is host-side scheduler code,
never traced — the tracelint ratchet pins this package at zero TL001
findings.
"""

from __future__ import annotations

from typing import Optional

from ..observability import REGISTRY, MetricsRegistry

__all__ = ["ServeMetrics"]


class ServeMetrics:
    """Thin, enabled-guarded facade over the metrics registry."""

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self._reg = REGISTRY if registry is None else registry

    @property
    def registry(self) -> MetricsRegistry:
        return self._reg

    @property
    def enabled(self) -> bool:
        return self._reg.enabled

    # -- lifecycle events ----------------------------------------------
    def event(self, action: str, **fields) -> None:
        if self._reg.enabled:
            self._reg.event("serve", action=action, **fields)

    def on_submit(self, req_id: int, prompt_len: int,
                  max_new_tokens: int) -> None:
        if not self._reg.enabled:
            return
        self._reg.counter("serve.submitted_total").inc()
        self._reg.event("serve", action="submit", req_id=req_id,
                        prompt_len=prompt_len,
                        max_new_tokens=max_new_tokens)

    def on_reject(self, reason: str) -> None:
        if not self._reg.enabled:
            return
        self._reg.counter("serve.rejected_total").inc()
        self._reg.event("serve", action="reject", reason=reason[:200])

    def on_timeout(self, req_id: int, phase: str) -> None:
        if not self._reg.enabled:
            return
        self._reg.counter("serve.timeouts_total").inc()
        self._reg.event("serve", action="timeout", req_id=req_id,
                        phase=phase)

    def on_cancel(self, req_id: int) -> None:
        if not self._reg.enabled:
            return
        self._reg.counter("serve.cancelled_total").inc()
        self._reg.event("serve", action="cancel", req_id=req_id)

    def on_finish(self, req_id: int, e2e_s: float, n_tokens: int) -> None:
        if not self._reg.enabled:
            return
        self._reg.counter("serve.finished_total").inc()
        self._reg.histogram("serve.e2e_secs", unit="s").record(e2e_s)
        self._reg.event("serve", action="finish", req_id=req_id,
                        e2e_s=round(e2e_s, 6), n_tokens=n_tokens)

    # -- token stream ---------------------------------------------------
    def on_first_token(self, req_id: int, ttft_s: float) -> None:
        if not self._reg.enabled:
            return
        self._reg.counter("serve.tokens_streamed_total").inc()
        self._reg.histogram("serve.ttft_secs", unit="s").record(ttft_s)
        self._reg.event("serve", action="first_token", req_id=req_id,
                        ttft_s=round(ttft_s, 6))

    def on_tokens(self, n: int, tpot_s: float) -> None:
        """``n`` decode tokens whose mean inter-arrival was ``tpot_s``."""
        if not self._reg.enabled:
            return
        self._reg.counter("serve.tokens_streamed_total").inc(n)
        h = self._reg.histogram("serve.tpot_secs", unit="s")
        for _ in range(n):
            h.record(tpot_s)

    def on_backpressure(self, waited_s: float) -> None:
        if not self._reg.enabled:
            return
        self._reg.histogram("serve.backpressure_wait_secs",
                            unit="s").record(waited_s)

    # -- HTTP wire (serving/http.py) -------------------------------------
    def on_connection(self, active: int, *, opened: bool) -> None:
        """A connection opened or closed; ``active`` is the server's
        live-connection count AFTER the change (the gauge value)."""
        if not self._reg.enabled:
            return
        if opened:
            self._reg.counter("serve.http.connections_total").inc()
        self._reg.gauge("serve.http.active_connections").set(active)

    def on_http_request(self) -> None:
        if self._reg.enabled:
            self._reg.counter("serve.http.requests_total").inc()

    def on_disconnect_cancel(self, req_id, n_streamed: int) -> None:
        if not self._reg.enabled:
            return
        self._reg.counter("serve.http.disconnect_cancels_total").inc()
        self._reg.event("serve", action="http_disconnect_cancel",
                        req_id=req_id, n_streamed=n_streamed)

    def on_dedup_hit(self, request_id: str, live: bool) -> None:
        if not self._reg.enabled:
            return
        self._reg.counter("serve.http.dedup_hits_total").inc()
        self._reg.event("serve", action="http_dedup_hit",
                        request_id=str(request_id)[:100], live=live)

    def on_write_stall(self, req_id, waited_s: float) -> None:
        if not self._reg.enabled:
            return
        self._reg.counter("serve.http.write_stall_timeouts_total").inc()
        self._reg.event("serve", action="http_write_stall",
                        req_id=req_id, waited_s=round(waited_s, 4))

    def on_abandoned(self, request_id: str) -> None:
        if not self._reg.enabled:
            return
        self._reg.counter("serve.http.abandoned_total").inc()
        self._reg.event("serve", action="http_abandoned",
                        request_id=str(request_id)[:100])

    def on_shutdown_drain(self, secs: float, drained: int,
                          cancelled: int) -> None:
        if not self._reg.enabled:
            return
        self._reg.histogram("serve.http.shutdown_drain_secs",
                            unit="s").record(secs)
        self._reg.event("serve", action="http_shutdown_drain",
                        secs=round(secs, 4), drained=drained,
                        cancelled=cancelled)

    # -- gauges ---------------------------------------------------------
    def publish_engine(self, engine) -> None:
        """Refresh the point-in-time gauges from engine state (called
        once per scheduler iteration, not per token)."""
        if not self._reg.enabled:
            return
        self._reg.gauge("serve.queue_depth").set(engine.queue_depth)
        self._reg.gauge("serve.batch_occupancy").set(
            engine.batch_occupancy())
        self._reg.gauge("serve.kv_utilization").set(
            engine.kv_utilization())
        self._reg.gauge("serve.kv_free_blocks").set(
            engine.alloc.free_blocks)
        spec = engine.spec_stats() if hasattr(engine, "spec_stats") \
            else None
        if spec is not None:
            if spec["acceptance_rate"] is not None:
                self._reg.gauge("serve.spec.acceptance_rate").set(
                    spec["acceptance_rate"])
            if spec["engine_steps_per_token"] is not None:
                self._reg.gauge("serve.spec.steps_per_token").set(
                    spec["engine_steps_per_token"])
        res = engine.resilience_stats() \
            if hasattr(engine, "resilience_stats") else None
        if res is not None:
            self._reg.gauge("serve.resilience.spilled_bytes").set(
                res["spilled_bytes"])
            self._reg.gauge("serve.resilience.spilled_requests").set(
                res["spilled_requests"])
        prefix = engine.prefix_stats() \
            if hasattr(engine, "prefix_stats") else None
        if prefix is not None:
            # .get defaults: an all-dead fleet's rollup has no replica
            # rows to sum, and gauges must still publish zeros
            g = self._reg.gauge
            g("serve.prefix.cached_blocks").set(
                prefix.get("cached_blocks", 0))
            g("serve.prefix.offloaded_blocks").set(
                prefix.get("offloaded_blocks", 0))
            g("serve.prefix.offloaded_bytes").set(
                prefix.get("offloaded_bytes", 0))
            if prefix.get("hit_rate") is not None:
                g("serve.prefix.hit_rate").set(prefix["hit_rate"])
        sched = engine.scheduler_stats() \
            if hasattr(engine, "scheduler_stats") else None
        if sched is not None:
            g = self._reg.gauge
            g("serve.sched.admissions").set(sched.get("admissions", 0))
            g("serve.sched.prefill_chunks").set(
                sched.get("prefill_chunks", 0))
            if sched["bucket_fill"] is not None:
                g("serve.sched.bucket_fill").set(sched["bucket_fill"])
            if sched["stalled_share"] is not None:
                g("serve.sched.stalled_share").set(sched["stalled_share"])
            if sched["kv_walk_share"] is not None:
                g("serve.sched.kv_walk_share").set(sched["kv_walk_share"])
            if sched["kv_walk_fill"] is not None:
                g("serve.sched.kv_walk_fill").set(sched["kv_walk_fill"])
        fleet = engine.fleet_stats() \
            if hasattr(engine, "fleet_stats") else None
        if fleet is not None:
            g = self._reg.gauge
            g("serve.fleet.replicas").set(fleet["replicas"])
            for state in ("healthy", "degraded", "draining", "dead"):
                g(f"serve.fleet.{state}").set(fleet[state])
            g("serve.fleet.queue_depth").set(fleet["queue_depth"])
            g("serve.fleet.batch_occupancy").set(
                fleet["batch_occupancy"])
            g("serve.fleet.kv_utilization").set(
                fleet["kv_utilization"])
