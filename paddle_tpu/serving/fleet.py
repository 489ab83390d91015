"""Fleet-level serving (ISSUE 12): a health-checked multi-replica
router over N data-parallel supervised engines.

One :class:`~paddle_tpu.serving.resilience.SupervisedEngine` behind a
front-end is still a single point of failure: an exhausted circuit
breaker aborts every live stream, and one saturated engine sheds load
the fleet could absorb.  :class:`EngineRouter` fronts N replicas behind
ONE admission view and duck-types the engine surface
``ServingFrontend`` drives, so the whole existing front-end / loadgen /
resilience stack works unchanged at fleet scale::

    factory = aot.serve.warm_engine_factory(cfg, params, aot_dir=root,
                                            max_batch=4, num_blocks=256)
    router = EngineRouter([factory] * 4)          # 4 warm replicas
    fe = ServingFrontend(router)                  # unchanged

* **Placement** is prefix-affine, KV-aware least-loaded: a request
  whose prompt shares a cached prefix routes to the replica whose
  radix tree already holds it (deepest match wins, bounded by an
  anti-herd cap — ISSUE 14); otherwise, among replicas whose health
  admits traffic, the one with the least (queue + running) work wins,
  KV-pool utilization breaking ties.  The router-level
  :class:`~paddle_tpu.serving.frontend.AdmissionConfig` rejects only
  when NO healthy replica can admit.
* **Health states** per replica::

      HEALTHY ──crash/transient──► DEGRADED ──clean steps──► HEALTHY
         │                            │
         ├────────── drain() ─────────┤──────► DRAINING ──► DEAD
         │                            │                      ▲
         └── RecoveryExhaustedError ──┴──────────────────────┘

  DEGRADED replicas keep serving but receive new work only when no
  HEALTHY replica can admit.  A replica whose supervisor escalates
  (:class:`RecoveryExhaustedError` — circuit breaker open or a rebuild
  factory failure) is DEAD: every live request on it is **re-placed**
  onto a healthy replica and replayed from its committed token prefix,
  so consumers see one gap-free bit-identical stream (greedy, sampled,
  and mid-speculation — pinned by tests/test_serving_fleet.py).  Only
  when the LAST replica dies does the router raise
  :class:`FleetExhaustedError`, landing in the front-end's existing
  typed abort-all path.
* **Graceful drain** (:meth:`EngineRouter.drain`) for rolling
  restarts: placement stops, live requests are spilled (their
  CRC-checked KV page bytes are replica-agnostic, so the target
  restores them into fresh blocks without recompute) or run out, the
  spilled ones are re-placed, and only then is the replica torn down —
  with its final KV-leak report recorded (must be zero).
* **Rebalancing**: a request waiting (queued or preempted-and-spilled)
  on a replica that cannot admit it migrates to a replica that can —
  cross-replica re-placement of preempted/spilled requests (ROADMAP
  2(b)), snapshot transplanted when present.
* **Zero compiles at fleet scale**: build every replica from the same
  AOT artifact generation via ``aot.serve.warm_engine_factory`` —
  fleet cold-start, crash rebuilds, AND re-placement prefills all run
  deserialized programs (the ``fleet_warm`` COMPILE_BUDGET.md row pins
  this at ZERO backend compiles).
* **Telemetry**: the ``serve.fleet.*`` family rolls per-replica
  ``serve.*`` state into fleet gauges plus re-placement / drain /
  death counters, all riding the flight ring (docs/serving.md).

Drive the router from one thread (or behind ``ServingFrontend``, whose
lock serializes submit/cancel/step) — like the engine it wraps, it is
a scheduler, not a server.
"""

from __future__ import annotations

import collections
import enum
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from ..inference.serving import GenRequest, sched_ratios
from ..observability import REGISTRY
from ..observability.tracing import TRACER
from .frontend import AdmissionConfig
from .resilience import (PortableRequest, RecoveryExhaustedError,
                         ResilienceError, RetryPolicy, SupervisedEngine)

__all__ = ["EngineRouter", "FleetExhaustedError", "ReplicaState"]


class FleetExhaustedError(ResilienceError):
    """Every replica in the fleet is DEAD while live requests remain.
    Escalates to the front-end's typed abort-all path — the fleet
    analogue of a single supervisor's circuit breaker opening."""


class ReplicaState(enum.Enum):
    HEALTHY = "HEALTHY"
    DEGRADED = "DEGRADED"
    DRAINING = "DRAINING"
    DEAD = "DEAD"


@dataclass
class _Replica:
    idx: int
    sup: Optional[SupervisedEngine]
    state: ReplicaState = ReplicaState.HEALTHY
    reason: Optional[str] = None
    clean_steps: int = 0
    last_crashes: int = 0            # sup crash+transient counter snapshot
    final_leak: Optional[Dict[str, int]] = None

    @property
    def live(self) -> bool:
        return self.state is not ReplicaState.DEAD and self.sup is not None


@dataclass
class _Placement:
    """Router bookkeeping for one live request.  ``req`` is the
    router-owned outer ``GenRequest`` — the object the front-end
    streams from; it survives every re-placement.  ``obj`` is the
    current replica's tracked request, ``base`` the length offset
    between the two token lists (``req.out == req.out[:base] +
    obj.out`` at all times)."""

    req: GenRequest
    kwargs: Dict[str, object]
    max_new: int
    priority: int
    blocks: int
    replica: int
    sid: int
    obj: GenRequest
    base: int
    moves: int = 0


class EngineRouter:
    """N data-parallel supervised replicas behind one admission view.

    Args:
      factories: zero-arg engine factories, one per replica (pass the
        same ``warm_engine_factory`` N times for a homogeneous fleet —
        replicas must share pool geometry for snapshot re-placement).
        Each is wrapped in a :class:`SupervisedEngine`, so intra-replica
        faults (transient retries, crash rebuild + replay) never reach
        the router; only an exhausted replica escalates here.
      policy: per-replica :class:`RetryPolicy`.
      admission: router-level :class:`AdmissionConfig`, applied PER
        replica — a submit is rejected only when NO healthy (then
        degraded) replica passes it.
      heal_after_steps: consecutive clean supervised steps before a
        DEGRADED replica is HEALTHY again.
      prefix_affinity: route a request sharing a cached prefix to the
        replica already holding it (ISSUE 14): placement consults each
        candidate's radix-tree summary (``prefix_match_blocks`` over
        the request's chained block digests) and the deepest match
        wins, least-loaded as tiebreak — a cache hit skips the shared
        prefix's prefill entirely, so affinity beats raw load balance
        whenever a prefix is actually cached.
      affinity_load_slack: the anti-herd cap — the affinity replica is
        taken only while its outstanding work (queue + running) exceeds
        the least-loaded candidate's by at most this many requests;
        past the cap the load balancer wins (counter
        ``affinity_capped``), so a popular system prompt can never
        starve the fleet onto one replica.
      registry / clock / sleep: forwarded to each supervisor.
    """

    def __init__(self, factories: Sequence[Callable[[], object]], *,
                 policy: Optional[RetryPolicy] = None,
                 admission: Optional[AdmissionConfig] = None,
                 heal_after_steps: int = 8, prefix_affinity: bool = True,
                 affinity_load_slack: int = 2, registry=None,
                 clock=None, sleep=None):
        if not factories:
            raise ValueError("EngineRouter needs at least one replica "
                             "factory")
        self.policy = policy
        self.admission = admission or AdmissionConfig()
        self.heal_after_steps = int(heal_after_steps)
        self.prefix_affinity = bool(prefix_affinity)
        self.affinity_load_slack = int(affinity_load_slack)
        self._reg = REGISTRY if registry is None else registry
        self._sup_kwargs = {}
        if clock is not None:
            self._sup_kwargs["clock"] = clock
        if sleep is not None:
            self._sup_kwargs["sleep"] = sleep
        self._replicas: List[_Replica] = []
        for f in factories:
            self._add_replica(f)
        # one fleet, one geometry: page math must keep working even
        # with every replica dead (re-placement decides typed-abort vs
        # strand based on it)
        self._block_size = int(self._replicas[0].sup.engine.BS)
        self._next_id = 0
        self._placements: "collections.OrderedDict[int, _Placement]" = \
            collections.OrderedDict()
        self._by_sid: Dict[tuple, int] = {}      # (replica, sid) -> rid
        self._pending_finished: Dict[int, np.ndarray] = {}
        self._final_replica: Dict[int, int] = {}
        self.stats: Dict[str, int] = {
            "placements": 0, "replacements": 0, "rebalanced": 0,
            "snapshot_migrations": 0, "deaths": 0, "drains": 0,
            "synthesized": 0, "affinity_hits": 0, "affinity_capped": 0,
        }

    # ------------------------------------------------------------------
    # replica lifecycle
    # ------------------------------------------------------------------
    def _add_replica(self, factory: Callable[[], object]) -> _Replica:
        idx = len(self._replicas)

        def numbered():
            # every engine this replica ever runs (rebuilds too) says
            # which replica it is on the one engine timeline
            engine = factory()
            engine.replica = idx
            return engine

        sup = SupervisedEngine(numbered, policy=self.policy,
                               registry=self._reg, **self._sup_kwargs)
        rep = _Replica(idx=idx, sup=sup)
        self._replicas.append(rep)
        return rep

    def add_replica(self, factory: Callable[[], object]) -> int:
        """Grow the fleet by one replica (the second half of a rolling
        restart: drain the old, add the new).  Returns its index."""
        rep = self._add_replica(factory)
        self._event("replica_added", replica=rep.idx)
        return rep.idx

    @property
    def replicas(self) -> List[_Replica]:
        return list(self._replicas)

    def replica_state(self, idx: int) -> ReplicaState:
        return self._replicas[idx].state

    def _live(self) -> List[_Replica]:
        return [r for r in self._replicas if r.live]

    def _placeable(self) -> List[_Replica]:
        """Replicas that may receive NEW work, healthiest tier first."""
        healthy = [r for r in self._replicas
                   if r.live and r.state is ReplicaState.HEALTHY]
        degraded = [r for r in self._replicas
                    if r.live and r.state is ReplicaState.DEGRADED]
        return healthy + degraded

    def placeable(self) -> bool:
        """Readiness predicate: can the fleet accept NEW work right now
        — is at least one replica HEALTHY or DEGRADED?  DRAINING and
        DEAD replicas keep existing streams alive but take no new
        placements, so a fleet of only those is not ready.  This is the
        load-balancer answer ``GET /readyz`` (serving/http.py) serves."""
        return bool(self._placeable())

    def health_census(self) -> Dict[str, int]:
        """Structured replica-health counts, one key per
        :class:`ReplicaState` value (``HEALTHY`` / ``DEGRADED`` /
        ``DRAINING`` / ``DEAD``) plus ``total`` — the readiness and
        metrics endpoints read fleet state through this instead of
        poking ``_replicas``."""
        census = {s.value: 0 for s in ReplicaState}
        for r in self._replicas:
            census[r.state.value] += 1
        census["total"] = len(self._replicas)
        return census

    # ------------------------------------------------------------------
    # placement
    # ------------------------------------------------------------------
    def _outstanding_blocks(self, idx: int) -> int:
        return sum(p.blocks for p in self._placements.values()
                   if p.replica == idx)

    def _replica_admits(self, rep: _Replica, need: int) -> bool:
        """The router-level admission view, applied to ONE replica: the
        fleet rejects only when this fails for every placeable
        replica."""
        eng = rep.sup
        if need > eng.alloc.num_blocks:
            return False                       # could never admit here
        adm = self.admission
        if adm.max_queue_len is not None \
                and eng.queue_depth >= adm.max_queue_len:
            return False
        if adm.kv_demand_factor is not None:
            cap = adm.kv_demand_factor * eng.alloc.num_blocks
            if self._outstanding_blocks(rep.idx) + need > cap:
                return False
        return True

    def _load_key(self, rep: _Replica):
        """KV-aware least-loaded order: outstanding work first, pool
        pressure second, index for determinism."""
        eng = rep.sup
        return (eng.queue_depth + eng.active_requests,
                round(eng.kv_utilization(), 6), rep.idx)

    def _prefix_keys(self, prompt: np.ndarray) -> Optional[List[bytes]]:
        """The request's chained block digests (computed ONCE per
        placement; every replica summary is consulted with the same
        list), or None when affinity is off / the prompt spans no full
        block."""
        if not self.prefix_affinity:
            return None
        from .prefix_cache import block_keys
        full = len(prompt) // self._block_size
        lookup = full - 1 if full and len(prompt) % self._block_size == 0 \
            else full
        if lookup <= 0:
            return None
        return block_keys(prompt, lookup, self._block_size)

    def _pick_replica(self, need: int, exclude: Optional[int] = None,
                      prefix_keys: Optional[List[bytes]] = None
                      ) -> Optional[_Replica]:
        """Least-loaded admitting replica, HEALTHY tier strictly before
        DEGRADED — degraded replicas take new work only as overflow.
        With ``prefix_keys``, prefix affinity runs first within the
        tier: the deepest cached-chain match wins (least-loaded
        tiebreak) unless the anti-herd cap says the affinity target is
        already ``affinity_load_slack`` requests busier than the
        least-loaded candidate — then load balance wins."""
        for state in (ReplicaState.HEALTHY, ReplicaState.DEGRADED):
            cands = [r for r in self._replicas
                     if r.live and r.state is state and r.idx != exclude
                     and self._replica_admits(r, need)]
            if not cands:
                continue
            best = min(cands, key=self._load_key)
            why, chosen, depth = "least_loaded", best, 0
            if prefix_keys:
                matched = [(r.sup.prefix_match_blocks(prefix_keys), r)
                           for r in cands]
                aff = [(m, r) for m, r in matched if m > 0]
                if aff:
                    m, target = min(
                        aff, key=lambda t: (-t[0],) + self._load_key(t[1]))
                    t_load = (target.sup.queue_depth
                              + target.sup.active_requests)
                    b_load = best.sup.queue_depth + best.sup.active_requests
                    if target is best or \
                            t_load <= b_load + self.affinity_load_slack:
                        self.stats["affinity_hits"] += 1
                        if self._reg.enabled:
                            self._reg.counter(
                                "serve.fleet.affinity_hits_total").inc()
                        why, chosen, depth = "affinity_hit", target, m
                    else:
                        self.stats["affinity_capped"] += 1
                        if self._reg.enabled:
                            self._reg.counter(
                                "serve.fleet.affinity_capped_total").inc()
                        why, depth = "affinity_capped", m
            if TRACER.enabled:
                # request tracing (ISSUE 20): the placement decision —
                # replica chosen and WHY — as an instant on the ambient
                # trace (active during submit and re-placement)
                tr = TRACER.current()
                if tr is not None:
                    tr.event("placement", replica=chosen.idx, why=why,
                             tier=state.value, matched_blocks=depth)
            return chosen
        return None

    def add_request(self, prompt_ids, max_new_tokens: int,
                    eos_token_id: Optional[int] = None, *,
                    temperature: float = 0.0,
                    top_k: Optional[int] = None,
                    top_p: Optional[float] = None,
                    seed: int = 0, priority: int = 0) -> int:
        """Place one request on the least-loaded admitting replica.
        Raises ``ValueError`` when no healthy replica can admit (the
        front-end turns that into a typed REJECTED handle), or for a
        genuinely malformed request."""
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if len(prompt) < 1:
            raise ValueError("prompt must contain at least one token")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if not self._live():
            raise ValueError("no live replica in the fleet")
        need = self._blocks_needed(len(prompt) + max_new_tokens)
        rep = self._pick_replica(need,
                                 prefix_keys=self._prefix_keys(prompt))
        if rep is None:
            raise ValueError(
                f"no healthy replica can admit: demand {need} blocks "
                f"across {len(self._placeable())} placeable replica(s) "
                f"(fleet admission {self.admission})")
        kwargs = {"eos_token_id": eos_token_id, "temperature": temperature,
                  "top_k": top_k, "top_p": top_p, "seed": seed}
        sid = rep.sup.add_request(
            prompt, max_new_tokens, eos_token_id,
            temperature=temperature, top_k=top_k, top_p=top_p,
            seed=seed, priority=priority)
        obj = rep.sup.tracked_request(sid)
        rid = self._next_id
        self._next_id += 1
        outer = GenRequest(rid, prompt, max_new_tokens, eos_token_id,
                           temperature=temperature, top_k=top_k,
                           top_p=top_p, seed=seed, priority=int(priority))
        self._placements[rid] = _Placement(
            req=outer, kwargs=kwargs, max_new=int(max_new_tokens),
            priority=int(priority), blocks=need, replica=rep.idx,
            sid=sid, obj=obj, base=0)
        self._by_sid[(rep.idx, sid)] = rid
        self.stats["placements"] += 1
        if self._reg.enabled:
            self._reg.counter("serve.fleet.placements_total").inc()
        return rid

    def cancel(self, req_id: int) -> bool:
        if self._pending_finished.pop(req_id, None) is not None:
            return True
        p = self._placements.pop(req_id, None)
        if p is None:
            return False
        del self._by_sid[(p.replica, p.sid)]
        self._final_replica[req_id] = p.replica
        rep = self._replicas[p.replica]
        if rep.live:
            rep.sup.cancel(p.sid)
        return True

    # ------------------------------------------------------------------
    # scheduling
    # ------------------------------------------------------------------
    def step(self) -> Dict[int, np.ndarray]:
        """One fleet iteration: step every live replica (a replica
        whose supervisor escalates dies here, its requests re-placed),
        bridge fresh tokens into the outer request objects, finish
        drains whose replica ran dry, and rebalance one stuck waiter.
        Returns newly finished ``{router_id: full ids}``."""
        out: Dict[int, np.ndarray] = {}
        for rep in list(self._replicas):
            if not rep.live:
                continue
            try:
                fin = rep.sup.step()
            except (KeyboardInterrupt, SystemExit):
                raise
            except RecoveryExhaustedError as e:
                self._on_death(rep, e)
                continue
            self._absorb_replica(rep, fin, out)
            self._update_health(rep)
        for rep in self._replicas:
            if rep.state is ReplicaState.DRAINING and rep.live \
                    and not any(p.replica == rep.idx
                                for p in self._placements.values()):
                self._teardown(rep, "drained")
        self._rebalance_one()
        if self._pending_finished:
            out.update(self._pending_finished)
            self._pending_finished = {}
        if self._placements and not self._live():
            raise FleetExhaustedError(
                "every replica in the fleet is dead; "
                f"{len(self._placements)} live request(s) cannot be "
                "re-placed")
        return out

    def run_to_completion(self) -> Dict[int, np.ndarray]:
        results: Dict[int, np.ndarray] = {}
        while self._placements or self._pending_finished:
            results.update(self.step())
        return results

    def _absorb_replica(self, rep: _Replica, fin: Dict[int, np.ndarray],
                        out: Dict[int, np.ndarray]) -> None:
        """Bridge new tokens into outer requests and translate this
        replica's finished ids to router ids."""
        for p in self._placements.values():
            if p.replica != rep.idx or p.sid in fin:
                continue
            new = p.obj.out[len(p.req.out) - p.base:]
            if new:
                p.req.out.extend(int(x) for x in new)
            if p.obj.eos_pos is not None and p.req.eos_pos is None:
                p.req.eos_pos = p.base + p.obj.eos_pos
        for sid, arr in fin.items():
            rid = self._by_sid.pop((rep.idx, sid), None)
            if rid is None:
                continue                        # cancelled passthrough
            p = self._placements.pop(rid)
            p.req.out = p.req.out[:p.base] + [int(x) for x in p.obj.out]
            if p.obj.eos_pos is not None:
                p.req.eos_pos = p.base + p.obj.eos_pos
            self._final_replica[rid] = rep.idx
            out[rid] = np.concatenate(
                [p.req.prompt, np.asarray(p.req.out, np.int32)])

    def _update_health(self, rep: _Replica) -> None:
        faults = rep.sup.stats["crashes"] + rep.sup.stats["transient_retries"]
        if faults > rep.last_crashes:
            rep.last_crashes = faults
            rep.clean_steps = 0
            if rep.state is ReplicaState.HEALTHY:
                rep.state = ReplicaState.DEGRADED
                self._event("replica_degraded", replica=rep.idx)
        elif rep.state is ReplicaState.DEGRADED:
            rep.clean_steps += 1
            if rep.clean_steps >= self.heal_after_steps:
                rep.state = ReplicaState.HEALTHY
                rep.clean_steps = 0
                self._event("replica_healed", replica=rep.idx)

    # ------------------------------------------------------------------
    # death + re-placement
    # ------------------------------------------------------------------
    def kill_replica(self, idx: int, reason: str = "killed") -> None:
        """Declare a replica dead NOW (the chaos/ops entry point — the
        organic path is its supervisor raising
        :class:`RecoveryExhaustedError` inside :meth:`step`).  Live
        requests re-place onto surviving replicas and replay from their
        committed prefixes."""
        rep = self._replicas[idx]
        if not rep.live:
            raise ValueError(f"replica {idx} is already dead")
        self._on_death(rep, RecoveryExhaustedError(reason))

    def _on_death(self, rep: _Replica, exc: BaseException) -> None:
        rep.state = ReplicaState.DEAD
        rep.reason = f"{type(exc).__name__}: {exc}"
        rep.sup = None                        # drop pools with the wrapper
        self.stats["deaths"] += 1
        if self._reg.enabled:
            self._reg.counter("serve.fleet.replica_deaths_total").inc()
        self._event("replica_dead", replica=rep.idx,
                    error=rep.reason[:300])
        victims = [(rid, p) for rid, p in self._placements.items()
                   if p.replica == rep.idx]
        for rid, p in victims:
            del self._placements[rid]
            self._by_sid.pop((p.replica, p.sid), None)
            req = p.req
            if req.eos_pos is not None or len(req.out) >= p.max_new:
                # died between the final token and its delivery:
                # synthesize the terminal result from the committed
                # prefix, exactly like a supervisor-internal recovery
                if req.eos_pos is not None:
                    req.out = req.out[:req.eos_pos + 1]
                self._pending_finished[rid] = np.concatenate(
                    [req.prompt, np.asarray(req.out, np.int32)])
                self._final_replica[rid] = rep.idx
                self.stats["synthesized"] += 1
                continue
            portable = PortableRequest(
                prompt=req.prompt, out=list(req.out),
                kwargs=dict(p.kwargs), max_new=p.max_new,
                priority=p.priority)
            self._re_place(rid, p, portable)

    def _re_place(self, rid: int, p: _Placement,
                  portable: PortableRequest) -> None:
        """Adopt a portable request on the least-loaded live replica
        and splice the placement so the outer stream continues."""
        # the portable is the source of truth — extraction bridges
        # tokens the router has not absorbed yet
        out = [int(x) for x in portable.out]
        eos = portable.kwargs.get("eos_token_id")
        if eos is not None and eos in out:
            out = out[:out.index(eos) + 1]
            done = True
        else:
            done = len(out) >= portable.max_new
        if done:
            # extracted between the final token and its retire (the
            # engine retires at the START of the next step): nothing
            # left to run — synthesize the terminal result; the outer
            # object is synced so the handle streams the tail first
            p.req.out = out
            self._pending_finished[rid] = np.concatenate(
                [portable.prompt, np.asarray(out, np.int32)])
            self._final_replica[rid] = p.replica
            self.stats["synthesized"] += 1
            return
        need = portable.snapshot.num_blocks \
            if portable.snapshot is not None \
            else self._blocks_needed(
                len(portable.prompt) + portable.max_new)
        # request tracing (ISSUE 20): re-place under the ORIGINAL trace
        # (the router rid IS the frontend rid the tracer indexed), so a
        # mid-stream replica kill keeps one trace_id across the move
        tr = TRACER.lookup(rid=rid) if TRACER.enabled else None
        t_mv = tr.now() if tr is not None else 0.0
        src = p.replica
        with TRACER.activating(tr):
            target = self._pick_replica(
                need, exclude=p.replica,
                prefix_keys=self._prefix_keys(portable.prompt))
            if target is None:
                # admission knobs must not strand an ALREADY-admitted
                # request: fall back to any live replica, least loaded
                cands = [r for r in self._live() if r.idx != p.replica] \
                    or self._live()
                if not cands:
                    # keep the placement so the next step() still sees a
                    # live request on a dead fleet and escalates typed —
                    # the stream must abort, never silently vanish
                    self._placements[rid] = p
                    raise FleetExhaustedError(
                        "every replica in the fleet is dead; request "
                        f"{rid} cannot be re-placed")
                target = min(cands, key=self._load_key)
            sid = target.sup.adopt_request(portable)
        obj = target.sup.tracked_request(sid)
        if tr is not None:
            tr.add("re_place", t_mv, tr.now(), from_replica=src,
                   to_replica=target.idx, committed=len(out),
                   snapshot=portable.snapshot is not None)
            tr.meta["replayed"] = True
        p.replica = target.idx
        p.sid = sid
        p.obj = obj
        p.base = len(p.req.out) - len(obj.out)
        p.moves += 1
        self._placements[rid] = p
        self._by_sid[(target.idx, sid)] = rid
        self.stats["replacements"] += 1
        if portable.snapshot is not None:
            self.stats["snapshot_migrations"] += 1
        if self._reg.enabled:
            self._reg.counter("serve.fleet.replacements_total").inc()
            if portable.snapshot is not None:
                self._reg.counter(
                    "serve.fleet.snapshot_migrations_total").inc()
        self._event("re_place", req_id=rid, replica=target.idx,
                    committed=len(p.req.out),
                    snapshot=portable.snapshot is not None)

    # ------------------------------------------------------------------
    # graceful drain
    # ------------------------------------------------------------------
    def drain(self, idx: int, *, mode: str = "replace") -> None:
        """Gracefully remove a replica (rolling restart): placement
        stops immediately; live requests are spilled and re-placed
        (``mode="replace"`` — KV snapshots transplant, streams resume
        bit-identically on the target) or allowed to run out
        (``mode="run_out"`` — teardown happens in :meth:`step` once the
        replica runs dry).  Teardown records the replica's final
        KV-leak report (must be zero) before dropping it."""
        if mode not in ("replace", "run_out"):
            raise ValueError(f"unknown drain mode {mode!r}")
        rep = self._replicas[idx]
        if not rep.live:
            raise ValueError(f"replica {idx} is already dead")
        others = [r for r in self._live()
                  if r.idx != idx and r.state is not ReplicaState.DRAINING]
        if not others:
            raise ValueError("cannot drain the last live replica — add "
                             "a replacement first (add_replica)")
        rep.state = ReplicaState.DRAINING
        self.stats["drains"] += 1
        if self._reg.enabled:
            self._reg.counter("serve.fleet.drains_total").inc()
        self._event("drain_start", replica=idx, mode=mode)
        if mode == "run_out":
            return
        for rid, p in [(r, q) for r, q in self._placements.items()
                       if q.replica == idx]:
            arr = rep.sup.take_pending_result(p.sid)
            if arr is not None:
                del self._placements[rid]
                self._by_sid.pop((idx, p.sid), None)
                self._pending_finished[rid] = arr
                continue
            portable = rep.sup.extract_request(p.sid)
            if portable is None:
                continue                   # finished this very step
            self._by_sid.pop((idx, p.sid), None)
            del self._placements[rid]
            self._re_place(rid, p, portable)
        self._teardown(rep, "drained")

    def _teardown(self, rep: _Replica, reason: str) -> None:
        rep.final_leak = rep.sup.kv_leak_report()
        rep.state = ReplicaState.DEAD
        rep.reason = reason
        rep.sup = None
        self._event("drain_done", replica=rep.idx,
                    leaked=rep.final_leak["leaked"]
                    + rep.final_leak["unaccounted"])

    # ------------------------------------------------------------------
    # rebalancing: cross-replica re-placement of waiting/spilled work
    # ------------------------------------------------------------------
    def _rebalance_one(self) -> None:
        """Migrate ONE stuck waiter per fleet step: a request queued
        (often preempted-and-spilled) on a replica that cannot seat it
        now moves to a replica with a free slot and pages — bounded
        work per step, monotonic progress, no thrashing."""
        for rep in self._live():
            eng = rep.sup
            if eng.queue_depth == 0:
                continue
            src_slot_free = any(s is None for s in eng.slots)
            for waiting in list(eng.queue):
                rid = self._by_sid.get((rep.idx, waiting.req_id))
                if rid is None:
                    continue
                p = self._placements[rid]
                snap = eng._spill.get(waiting.req_id)
                need = snap.num_blocks if snap is not None else \
                    self._blocks_needed(len(waiting.prompt)
                                        + waiting.max_new_tokens)
                if src_slot_free and eng.alloc.free_blocks >= need:
                    continue               # source can seat it itself
                target = self._target_with_room(need, exclude=rep.idx)
                if target is None:
                    continue
                portable = eng.extract_request(p.sid)
                if portable is None:
                    continue
                del self._placements[rid]
                self._by_sid.pop((rep.idx, p.sid), None)
                self._re_place(rid, p, portable)
                self.stats["rebalanced"] += 1
                if self._reg.enabled:
                    self._reg.counter(
                        "serve.fleet.rebalanced_total").inc()
                return
        return

    def _target_with_room(self, need: int,
                          exclude: int) -> Optional[_Replica]:
        """A replica that could seat the request THIS step: a free
        decode slot and enough free pool pages right now."""
        cands = [r for r in self._placeable()
                 if r.idx != exclude
                 and any(s is None for s in r.sup.slots)
                 and r.sup.alloc.free_blocks >= need]
        if not cands:
            return None
        return min(cands, key=self._load_key)

    # ------------------------------------------------------------------
    # engine-surface duck typing (ServingFrontend / loadgen / bench)
    # ------------------------------------------------------------------
    @property
    def queue(self) -> List[GenRequest]:
        """Outer request objects of every live request (newest last) —
        the front-end's post-submit lookup reads this."""
        return [p.req for p in self._placements.values()]

    @property
    def queue_depth(self) -> int:
        return sum(r.sup.queue_depth for r in self._live())

    @property
    def active_requests(self) -> int:
        return sum(r.sup.active_requests for r in self._live())

    @property
    def live_requests(self) -> int:
        return len(self._placements)

    @property
    def cfg(self):
        live = self._live()
        if not live:
            raise FleetExhaustedError("no live replica in the fleet")
        return live[0].sup.cfg

    class _FleetPool:
        """Aggregate KV-pool view over live replicas (the front-end's
        admission math and gauges read ``num_blocks``/``free_blocks``)."""

        def __init__(self, router: "EngineRouter"):
            self._router = router

        @property
        def num_blocks(self) -> int:
            return sum(r.sup.alloc.num_blocks
                       for r in self._router._live())

        @property
        def free_blocks(self) -> int:
            return sum(r.sup.alloc.free_blocks
                       for r in self._router._live())

    @property
    def alloc(self) -> "_FleetPool":
        return EngineRouter._FleetPool(self)

    def _blocks_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self._block_size)

    def batch_occupancy(self) -> float:
        live = self._live()
        if not live:
            return 0.0
        return sum(r.sup.batch_occupancy() for r in live) / len(live)

    def kv_utilization(self) -> float:
        pool = self.alloc
        n = pool.num_blocks
        return 0.0 if n == 0 else 1.0 - pool.free_blocks / float(n)

    def kv_leak_report(self) -> Dict[str, int]:
        """Component-wise sum over live replicas (drained replicas'
        final reports are checked at teardown and kept in
        ``fleet_stats()['drain_reports']``)."""
        total = {"free_blocks": 0, "index_blocks": 0, "slot_blocks": 0,
                 "leaked": 0, "unaccounted": 0}
        for r in self._live():
            for k, v in r.sup.kv_leak_report().items():
                total[k] += v
        return total

    def resilience_stats(self) -> Dict[str, object]:
        """Summed per-replica resilience counters plus the fleet's own
        re-placement counters (the gauge publisher and bench rows read
        one dict)."""
        keys: Dict[str, object] = {}
        for r in self._live():
            for k, v in r.sup.resilience_stats().items():
                if isinstance(v, (int, float)):
                    keys[k] = keys.get(k, 0) + v
        for k, v in self.stats.items():
            keys[f"fleet_{k}"] = v
        keys.setdefault("spilled_bytes", 0)
        keys.setdefault("spilled_requests", 0)
        return keys

    def prefix_stats(self) -> Dict[str, object]:
        """Fleet-wide prefix-cache rollup: summed per-replica counters
        plus the router's own affinity counters (``hit_rate`` is
        recomputed over the summed lookups, never averaged)."""
        total: Dict[str, object] = {}
        for r in self._live():
            for k, v in r.sup.prefix_stats().items():
                if k == "hit_rate" or isinstance(v, bool):
                    continue
                if isinstance(v, (int, float)):
                    total[k] = total.get(k, 0) + v
        lk = total.get("lookups", 0)
        total["hit_rate"] = (total.get("hits", 0) / lk) if lk else None
        total["affinity_hits"] = self.stats["affinity_hits"]
        total["affinity_capped"] = self.stats["affinity_capped"]
        return total

    def aot_stats(self) -> Dict[str, object]:
        return {f"replica{r.idx}": r.sup.aot_stats()
                for r in self._live()}

    def scheduler_stats(self) -> Dict[str, object]:
        """Summed per-replica admission/prefill counters, the ratios
        recomputed over the sums (the ``serve.sched.*`` gauges)."""
        total: Dict[str, object] = {}
        for r in self._live():
            for k, v in r.sup.scheduler_stats().items():
                if isinstance(v, int):
                    total[k] = total.get(k, 0) + v
        return sched_ratios(total)

    def fleet_stats(self) -> Dict[str, object]:
        """The ``serve.fleet.*`` rollup: health census, aggregate load,
        re-placement / drain / death counters, per-replica breakdown,
        and drained replicas' final leak reports."""
        by_state = self.health_census()
        per_replica = []
        for r in self._replicas:
            row: Dict[str, object] = {"replica": r.idx,
                                      "state": r.state.value}
            if r.live:
                row.update(
                    queue_depth=r.sup.queue_depth,
                    active=r.sup.active_requests,
                    batch_occupancy=round(r.sup.batch_occupancy(), 4),
                    kv_utilization=round(r.sup.kv_utilization(), 4),
                    crashes=r.sup.stats["crashes"],
                    recoveries=r.sup.stats["recoveries"])
            elif r.reason is not None:
                row["reason"] = r.reason
            per_replica.append(row)
        return {
            "replicas": len(self._replicas),
            **{st.value.lower(): by_state[st.value]
               for st in ReplicaState},
            "live_requests": len(self._placements),
            "queue_depth": self.queue_depth,
            "batch_occupancy": round(self.batch_occupancy(), 4),
            "kv_utilization": round(self.kv_utilization(), 4),
            **self.stats,
            "per_replica": per_replica,
            "drain_reports": {r.idx: r.final_leak
                              for r in self._replicas
                              if r.final_leak is not None},
        }

    def replica_of(self, req_id: int) -> Optional[int]:
        """Current (live) or final replica of a request — the loadgen
        per-replica breakdown reads this."""
        p = self._placements.get(req_id)
        if p is not None:
            return p.replica
        return self._final_replica.get(req_id)

    def _event(self, action: str, **fields) -> None:
        if self._reg.enabled:
            self._reg.event("serve", action=f"fleet_{action}", **fields)
