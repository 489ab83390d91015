"""Serving resilience (ISSUE 11): KV spill/restore for priority
preemption, and a supervising engine wrapper that survives step faults.

The serve stack before this module had exactly one failure mode: an
engine exception aborted every live stream (the front-end's typed
abort-all path).  This module adds the two layers between "fine" and
"abort everything":

* **KV snapshot / restore** — the page-level save→verify→publish
  discipline of ``checkpoint/`` applied to the serving KV pool, but
  into a host-RAM spill tier instead of disk.  ``snapshot_slot`` reads
  a running slot's committed KV pages off the device and CRC32-stamps
  them (the ``framework/io.py`` manifest convention); ``restore_into_
  slot`` verifies the checksums and scatters the exact bytes into
  fresh blocks.  Because the engine's decode reads KV only through the
  block table and the sampler is keyed by (seed, absolute position), a
  preempt/restore cycle is **bit-identical** to an unpreempted run —
  pinned by tests/test_serving_resilience.py.

* :class:`SupervisedEngine` — a drop-in engine wrapper (the
  ``ServingFrontend`` drives it unchanged) with three escalation
  levels:

  1. **transient faults** (:class:`TransientStepError`) retry the step
     with bounded exponential backoff;
  2. a **declared crash** (any other ``Exception``, retries exhausted,
     or a run of slow steps past ``RetryPolicy.slow_step_s``) tears
     the engine down, rebuilds it through the caller's factory — AOT-
     warm factories (``aot.serve.warm_engine_factory``) rebuild with
     ZERO backend compiles, ratcheted by the ``serve_recovery_warm``
     budget row — and **replays every live request from its committed
     token prefix**: the replayed request's prompt is
     ``original prompt + tokens already streamed``, so the resumed
     stream continues gap-free and (greedy / seeded-sampled)
     bit-identically, invisible to the consumer;
  3. a **circuit breaker** (``max_restarts`` within
     ``restart_window_s``) raises :class:`RecoveryExhaustedError`,
     which lands in the front-end's existing crash path: flight-ring
     dump + typed abort of every live stream.

  Every recovery dumps the flight-recorder ring (the serve event ring
  is the post-mortem timeline) and records the ``serve.resilience.*``
  metric family.

``BaseException`` faults (``KeyboardInterrupt``, the checkpoint
harness's ``SimulatedCrash``) are never swallowed — supervision is for
engine faults, not for the process being killed.
"""

from __future__ import annotations

import collections
import functools
import time
import zlib
from dataclasses import dataclass
from typing import Callable, Dict, Optional

import numpy as np

from ..observability import REGISTRY
from ..observability.tracing import TRACER

__all__ = [
    "EngineCrashError", "KVSnapshot", "PortableRequest",
    "RecoveryExhaustedError", "ResilienceError", "RetryPolicy",
    "SpillCorruptError", "SpillTier", "SupervisedEngine",
    "TransientStepError", "restore_into_slot", "snapshot_slot",
]


class ResilienceError(RuntimeError):
    """Base for typed resilience failures."""


class SpillCorruptError(ResilienceError):
    """A spilled KV snapshot failed its CRC check at restore time.  The
    snapshot (and, on a bare engine, the request) is dropped — a
    supervising wrapper replays the request from its committed token
    prefix instead, so nothing is lost above the supervisor."""


class TransientStepError(RuntimeError):
    """A step fault the supervisor should RETRY (bounded backoff)
    rather than treat as an engine crash — the fault-injection marker
    for retryable conditions (tests/faults.py raises it)."""


class EngineCrashError(RuntimeError):
    """A declared engine crash: the supervisor tears down, rebuilds,
    and replays.  Any non-transient ``Exception`` escaping
    ``engine.step()`` is treated the same way; this type exists so
    policies (slow-step escalation) and injectors can declare one
    explicitly."""


class RecoveryExhaustedError(ResilienceError):
    """The restart circuit breaker opened: more than
    ``RetryPolicy.max_restarts`` rebuilds inside
    ``restart_window_s``.  Escalates to the front-end's typed
    abort-all path (every live stream gets a terminal state)."""


# ---------------------------------------------------------------------
# KV spill tier: page snapshots with the checkpoint CRC convention
# ---------------------------------------------------------------------
@dataclass
class KVSnapshot:
    """One preempted request's committed serving state, held in host
    RAM: the exact bytes of its committed KV pages plus the decode
    cursor (committed length + pending fed token).  The sampler needs
    no extra state — it is keyed by (seed, absolute position), both of
    which the request/cursor already carry."""

    req_id: int
    length: int                # committed KV positions
    next_token: int            # pending fed token (decode cursor)
    num_blocks: int            # full table width to re-acquire
    k_pages: np.ndarray        # [L, used_pages, BS, Hkv, D]
    v_pages: np.ndarray
    # quantized pools (ISSUE 16): k_pages/v_pages hold the int8 codes
    # and the per-(token, head) fp32 scales ride here — the CRCs chain
    # over codes THEN scales, so bit-rot in either is caught
    k_scale: Optional[np.ndarray] = None   # [L, used_pages, BS, Hkv]
    v_scale: Optional[np.ndarray] = None
    # a model with per-slot recurrent state (hybrid of state-space and
    # attention layers): the slot's rows of the engine's two state
    # arrays, CRC-stamped like the pages — the pages alone could not
    # resume such a sequence
    ssm_state: Optional[np.ndarray] = None   # [L_state, heads, P, N] f32
    conv_state: Optional[np.ndarray] = None  # [L_state, channels, W-1]
    crc_k: int = 0
    crc_v: int = 0
    crc_state: int = 0

    def __post_init__(self):
        if not self.crc_k and not self.crc_v:
            self.crc_k = self._crc(self.k_pages, self.k_scale)
            self.crc_v = self._crc(self.v_pages, self.v_scale)
            if self.ssm_state is not None:
                self.crc_state = self._crc(self.ssm_state,
                                           self.conv_state)

    @staticmethod
    def _crc(pages: np.ndarray, scale: Optional[np.ndarray]) -> int:
        crc = zlib.crc32(pages.tobytes())
        if scale is not None:
            crc = zlib.crc32(scale.tobytes(), crc)
        return crc

    @property
    def state_nbytes(self) -> int:
        """Bytes of per-slot recurrent state that ride with the pages
        (0 for a model that keeps none)."""
        if self.ssm_state is None:
            return 0
        return self.ssm_state.nbytes + self.conv_state.nbytes

    @property
    def nbytes(self) -> int:
        n = self.k_pages.nbytes + self.v_pages.nbytes + self.state_nbytes
        if self.k_scale is not None:
            n += self.k_scale.nbytes + self.v_scale.nbytes
        return n

    def verify(self) -> None:
        """Raise :class:`SpillCorruptError` unless the page bytes still
        match their spill-time checksums (framework/io.py convention:
        every array member carries a CRC32, verified on read)."""
        if self._crc(self.k_pages, self.k_scale) != self.crc_k or \
                self._crc(self.v_pages, self.v_scale) != self.crc_v or (
                    self.ssm_state is not None and self._crc(
                        self.ssm_state, self.conv_state)
                    != self.crc_state):
            raise SpillCorruptError(
                f"spilled KV snapshot for request {self.req_id} failed "
                "its CRC check — host-RAM bit-rot or a write raced the "
                "spill; the request must be replayed from its committed "
                "token prefix")


def snapshot_slot(engine, slot: int) -> KVSnapshot:
    """Read the committed KV pages of a RUNNING slot off the device and
    CRC-stamp them.  Only pages holding committed positions
    (``ceil(length / block_size)``) are copied — pages reserved for the
    not-yet-generated tail carry no state worth saving (any stale bytes
    there are masked by ``lengths`` exactly as on a fresh slot).

    The gather runs HOST-side (one pool transfer + numpy indexing)
    rather than as a traced ``pool[:, idx]``: a device gather is an
    op-by-op backend compile per distinct page count, which would break
    the fleet's zero-compile contract (``fleet_warm`` budget row) the
    first time a drain spilled an unseen length.  Spill/restore are
    rare, host-bound control-plane events; the extra copy is the cheap
    side of that trade."""
    from ..ops.paged_kv import is_quantized_pool
    req = engine.slots[slot]
    length = int(engine.lengths[slot])
    used = -(-length // engine.BS)
    pages = engine.slot_pages[slot]
    idx = np.asarray(pages[:used], np.int64)
    ks = vs = None
    if engine.pool_v is None:
        # a latent pool: one vector a token, no value pages.  The pages
        # come off the device through ONE fixed-width program (the
        # table's width; unused columns read page 0 and are cut here):
        # the pool itself, gigabytes of it, never visits the host
        k = np.asarray(_page_programs()[0](
            engine.pool_k, _table_width(idx, engine.MB, 0)))[:, :used]
        v = k[..., :0]
    elif is_quantized_pool(engine.pool_k):
        k = np.asarray(engine.pool_k.data)[:, idx].copy()
        v = np.asarray(engine.pool_v.data)[:, idx].copy()
        ks = np.asarray(engine.pool_k.scale)[:, idx].copy()
        vs = np.asarray(engine.pool_v.scale)[:, idx].copy()
    else:
        k = np.asarray(engine.pool_k)[:, idx].copy()
        v = np.asarray(engine.pool_v)[:, idx].copy()
    ssm = conv = None
    if getattr(engine, "ssm_state", None) is not None:
        ssm, conv = (np.asarray(a) for a in _state_row_programs()[0](
            engine.ssm_state, engine.conv_state, np.int32(slot)))
    return KVSnapshot(req_id=req.req_id, length=length,
                      next_token=int(engine.tokens[slot]),
                      num_blocks=len(pages), k_pages=k, v_pages=v,
                      k_scale=ks, v_scale=vs, ssm_state=ssm,
                      conv_state=conv)


def _table_width(pages: np.ndarray, width: int, fill: int) -> np.ndarray:
    """``pages`` padded with ``fill`` to a table row's ``width``."""
    out = np.full((width,), fill, np.int32)
    out[:len(pages)] = pages
    return out


@functools.lru_cache(maxsize=1)
def _page_programs():
    """``(read, write)`` of a latent pool ``[L, NB, BS, W]``: the pages
    a table row names, ``[L, MB, BS, W]``, out of the pool / into it
    (an entry past the pool's last page writes nowhere).  One compiled
    program each for an engine, whatever the slot and its length."""
    import jax
    read = jax.jit(lambda pool, idx: pool[:, idx])
    write = jax.jit(
        lambda pool, idx, pages: pool.at[:, idx].set(pages, mode="drop"),
        donate_argnums=(0,))
    return read, write


@functools.lru_cache(maxsize=1)
def _state_row_programs():
    """``(read, write)``: a slot's rows of the two per-slot state arrays
    out of / into them, each ONE compiled program whatever the slot (the
    index is an argument)."""
    import jax
    take = jax.lax.dynamic_index_in_dim
    put = jax.lax.dynamic_update_index_in_dim
    read = jax.jit(lambda s, c, i: (take(s, i, 1, keepdims=False),
                                    take(c, i, 1, keepdims=False)))
    write = jax.jit(lambda s, c, sr, cr, i: (put(s, sr, i, 1),
                                             put(c, cr, i, 1)),
                    donate_argnums=(0, 1))
    return read, write


def restore_into_slot(engine, slot: int, snap: KVSnapshot) -> None:
    """Verify and scatter a snapshot's page bytes into the slot's
    freshly acquired blocks (``engine.slot_pages[slot]``).  The
    device→host→device round trip preserves bytes exactly, so decode
    resumed from the restored pages is bit-identical to one that was
    never preempted.  Host-side scatter for the same zero-compile
    reason as :func:`snapshot_slot`."""
    import jax.numpy as jnp

    from ..ops.paged_kv import QuantizedKVPool, is_quantized_pool
    snap.verify()
    quant = is_quantized_pool(engine.pool_k)
    if (snap.k_scale is not None) != quant:
        raise SpillCorruptError(
            f"KV snapshot for request {snap.req_id} "
            f"{'carries' if snap.k_scale is not None else 'lacks'} "
            "quantization scales but the engine's pool "
            f"{'is' if quant else 'is not'} quantized — the snapshot "
            "cannot scatter; replay from the committed token prefix")
    has_state = getattr(engine, "ssm_state", None) is not None
    if (snap.ssm_state is not None) != has_state:
        raise SpillCorruptError(
            f"KV snapshot for request {snap.req_id} "
            f"{'carries' if snap.ssm_state is not None else 'lacks'} a "
            "recurrent state but the engine's model "
            f"{'keeps' if has_state else 'does not keep'} one — replay "
            "from the committed token prefix")
    if has_state:
        engine.ssm_state, engine.conv_state = _state_row_programs()[1](
            engine.ssm_state, engine.conv_state, snap.ssm_state,
            snap.conv_state, np.int32(slot))
    used = snap.k_pages.shape[1]
    pages = np.asarray(engine.slot_pages[slot][:used], np.int64)
    if engine.pool_v is None:
        # a latent pool (see snapshot_slot): the rows past the
        # snapshot's pages are aimed past the pool and dropped
        if snap.v_pages.shape[-1]:
            raise SpillCorruptError(
                f"KV snapshot for request {snap.req_id} carries value "
                "pages but the engine's cache is one latent pool — "
                "replay from the committed token prefix")
        rows = np.zeros((snap.k_pages.shape[0], engine.MB)
                        + snap.k_pages.shape[2:], snap.k_pages.dtype)
        rows[:, :used] = snap.k_pages
        engine.pool_k = _page_programs()[1](
            engine.pool_k,
            _table_width(pages, engine.MB, engine.pool_k.shape[1]), rows)
        return
    # jnp.array (owned copy), NOT jax.device_put/jnp.asarray: both can
    # zero-copy ALIAS the numpy buffer on CPU, and the decode step
    # DONATES the pools — XLA reusing memory numpy still owns is a
    # use-after-free.  The copy runs through a pool-shaped
    # convert_element_type executable that the engine pre-warms at
    # construction, so restores under traffic stay at zero backend
    # compiles (fleet_warm budget row).
    if quant:
        pk = np.asarray(engine.pool_k.data).copy()
        pv = np.asarray(engine.pool_v.data).copy()
        pks = np.asarray(engine.pool_k.scale).copy()
        pvs = np.asarray(engine.pool_v.scale).copy()
        pk[:, pages] = snap.k_pages
        pv[:, pages] = snap.v_pages
        pks[:, pages] = snap.k_scale
        pvs[:, pages] = snap.v_scale
        engine.pool_k = QuantizedKVPool(jnp.array(pk), jnp.array(pks))
        engine.pool_v = QuantizedKVPool(jnp.array(pv), jnp.array(pvs))
        return
    pk = np.asarray(engine.pool_k).copy()
    pv = np.asarray(engine.pool_v).copy()
    pk[:, pages] = snap.k_pages
    pv[:, pages] = snap.v_pages
    engine.pool_k = jnp.array(pk)
    engine.pool_v = jnp.array(pv)


# ---------------------------------------------------------------------
# bounded host-RAM spill tier (ISSUE 12 satellite)
# ---------------------------------------------------------------------
class SpillTier:
    """Bounded host-RAM store for spilled :class:`KVSnapshot` objects,
    shared by priority preemption and graceful drain.

    Host RAM is a real resource: a saturated fleet preempting
    long-context requests could otherwise grow the spill tier without
    limit until the OS kills the serving process — a worse failure than
    the one preemption avoids.  ``capacity_bytes`` caps the tier;
    inserting past the cap EVICTS snapshots (``policy="evict-oldest"``
    — the snapshot spilled longest ago is the one whose request has
    waited longest and is cheapest to recompute relative to its wait).
    An evicted request is NOT lost: it is demoted to
    **replay-from-prefix** — the engine's admission path detects a
    queued request with committed tokens but no snapshot and recomputes
    its KV from the committed token prefix (bit-identical, just paid in
    prefill FLOPs instead of host bytes).  Every eviction is a typed
    ``spill_evict`` event plus the
    ``serve.resilience.spill_evictions_total`` counter.

    The dict-like surface (``tier[rid]``, ``rid in tier``, ``pop``,
    ``del``) keeps the engine's bookkeeping unchanged; only the
    capacity-checked :meth:`put` differs from a plain dict.
    """

    def __init__(self, capacity_bytes: Optional[int] = None,
                 policy: str = "evict-oldest"):
        if policy != "evict-oldest":
            raise ValueError(f"unknown spill policy {policy!r} "
                             "(have: evict-oldest)")
        if capacity_bytes is not None and capacity_bytes < 0:
            raise ValueError("capacity_bytes must be >= 0 or None")
        self.capacity_bytes = capacity_bytes
        self.policy = policy
        self._snaps: "collections.OrderedDict[int, KVSnapshot]" = \
            collections.OrderedDict()
        self.evictions = 0

    @property
    def nbytes(self) -> int:
        return sum(s.nbytes for s in self._snaps.values())

    def put(self, req_id: int, snap: KVSnapshot) -> list:
        """Insert a snapshot; returns the req_ids EVICTED to make room
        (possibly including ``req_id`` itself when one snapshot alone
        exceeds the cap).  The caller demotes evicted requests to
        replay-from-prefix and records the typed event."""
        self._snaps[req_id] = snap
        evicted = []
        if self.capacity_bytes is not None:
            while self._snaps and self.nbytes > self.capacity_bytes:
                rid, _ = self._snaps.popitem(last=False)
                evicted.append(rid)
                self.evictions += 1
        return evicted

    def get(self, req_id: int, default=None):
        return self._snaps.get(req_id, default)

    def pop(self, req_id: int, *default):
        return self._snaps.pop(req_id, *default)

    def values(self):
        return self._snaps.values()

    def keys(self):
        return self._snaps.keys()

    def __getitem__(self, req_id: int) -> KVSnapshot:
        return self._snaps[req_id]

    def __delitem__(self, req_id: int) -> None:
        del self._snaps[req_id]

    def __contains__(self, req_id: int) -> bool:
        return req_id in self._snaps

    def __len__(self) -> int:
        return len(self._snaps)


# ---------------------------------------------------------------------
# supervised engine: retry / rebuild / replay
# ---------------------------------------------------------------------
@dataclass(frozen=True)
class RetryPolicy:
    """Escalation knobs for :class:`SupervisedEngine`.

    max_retries:
        Transient-fault retries per step before escalating to a
        declared crash.
    backoff_base_s / backoff_factor / backoff_max_s:
        Bounded exponential backoff between transient retries
        (``base * factor**(attempt-1)``, capped).
    slow_step_s:
        A step slower than this counts as a slow step (None disables
        the detector — wall-clock on a shared CI host is noisy).
    slow_steps_to_crash:
        Consecutive slow steps that escalate to a declared crash (a
        hung-but-not-dead engine must not stall streams forever).
    max_restarts / restart_window_s:
        Circuit breaker: more than ``max_restarts`` rebuilds within the
        window raises :class:`RecoveryExhaustedError` instead of
        rebuilding again.
    """

    max_retries: int = 3
    backoff_base_s: float = 0.02
    backoff_factor: float = 2.0
    backoff_max_s: float = 1.0
    slow_step_s: Optional[float] = None
    slow_steps_to_crash: int = 3
    max_restarts: int = 3
    restart_window_s: float = 60.0


@dataclass
class _Tracked:
    """Supervisor bookkeeping for one live request.  ``req`` is the
    OUTER GenRequest — the object the caller (and the front-end's
    stream delivery) holds.  Before any crash the inner engine runs
    that very object; after a rebuild ``inner`` is the replayed
    request inside the fresh engine and newly committed tokens are
    bridged into ``req`` so consumers never notice the splice."""

    req: object
    kwargs: Dict[str, object]
    max_new: int
    priority: int
    inner: object = None
    base: int = 0               # outer tokens committed before replay


@dataclass
class PortableRequest:
    """A live request lifted OUT of one supervised engine so another
    replica can carry it (the fleet router's re-placement currency —
    ``serving/fleet.py``).  ``out`` is the committed token prefix the
    consumer has already (or could have) seen; ``snapshot`` is the
    CRC-checked KV page bytes when the source replica was healthy
    enough to spill them (page bytes are replica-agnostic: any engine
    with the same geometry can scatter them into fresh blocks), or
    None — in which case the target replays from the committed token
    prefix instead (bit-identical either way, the snapshot just saves
    the prefill recompute)."""

    prompt: np.ndarray
    out: list
    kwargs: Dict[str, object]
    max_new: int
    priority: int
    snapshot: Optional[KVSnapshot] = None

    @property
    def done(self) -> bool:
        return len(self.out) >= self.max_new


class _DeadEngine:
    """Sentinel installed when a recovery rebuild itself fails: every
    engine-surface access raises the typed circuit-breaker error
    instead of ``AttributeError`` on ``None``, so callers that keep
    driving the wrapper after the escalation still land in the
    front-end's typed abort-all path."""

    def __init__(self, cause: BaseException):
        object.__setattr__(self, "_cause", cause)

    def __getattr__(self, name):
        cause = object.__getattribute__(self, "_cause")
        err = RecoveryExhaustedError(
            "engine rebuild failed during crash recovery — the "
            f"supervisor has no live engine; rebuild error: "
            f"{type(cause).__name__}: {cause}")
        err.__cause__ = cause
        raise err


class SupervisedEngine:
    """Crash-supervised wrapper around a ``ContinuousBatchingEngine``.

    Args:
      factory: zero-arg callable building a fresh engine.  Use an AOT-
        warm factory (``aot.serve.warm_engine_factory``) so rebuilds
        deserialize every compiled program instead of tracing — the
        ``serve_recovery_warm`` compile-budget row pins recovery at
        ZERO backend compiles.
      policy: :class:`RetryPolicy` escalation knobs.
      registry: metrics registry (defaults to the process registry).
      clock / sleep: injectable time sources (tests drive backoff and
        the circuit-breaker window without real waiting).

    The wrapper duck-types the engine surface the serving front-end
    uses (``add_request`` / ``cancel`` / ``step`` / ``queue`` /
    introspection helpers), so ``ServingFrontend(SupervisedEngine(...))``
    serves streams that survive engine crashes.
    """

    def __init__(self, factory: Callable[[], object], *,
                 policy: Optional[RetryPolicy] = None, registry=None,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep):
        self._factory = factory
        self.policy = policy or RetryPolicy()
        self._reg = REGISTRY if registry is None else registry
        self._clock = clock
        self._sleep = sleep
        self.engine = factory()
        # The supervisor owns the caller-visible id space: a rebuilt
        # engine restarts ITS counter at 0, so reusing inner rids would
        # collide with still-live outer ids after any pre-crash request
        # finished.  Outer ids are monotone and never reused; each
        # inner GenRequest is re-keyed to its outer id on creation, so
        # the inner engine's finished/spill/cancel bookkeeping (all
        # keyed off ``req.req_id``) speaks outer ids too.
        self._next_outer_id = 0
        self._tracked: "collections.OrderedDict[int, _Tracked]" = \
            collections.OrderedDict()
        self._pending_finished: Dict[int, np.ndarray] = {}
        self._restart_times: "collections.deque[float]" = \
            collections.deque()
        self._consecutive_slow = 0
        self.last_error: Optional[BaseException] = None
        self.stats: Dict[str, int] = {
            "transient_retries": 0, "slow_steps": 0, "crashes": 0,
            "recoveries": 0, "replayed_requests": 0, "circuit_opens": 0,
            "rebuild_failures": 0,
        }

    # -- engine surface -------------------------------------------------
    def add_request(self, prompt_ids, max_new_tokens: int,
                    eos_token_id: Optional[int] = None, *,
                    temperature: float = 0.0,
                    top_k: Optional[int] = None,
                    top_p: Optional[float] = None,
                    seed: int = 0, priority: int = 0) -> int:
        inner_rid = self.engine.add_request(
            prompt_ids, max_new_tokens, eos_token_id,
            temperature=temperature, top_k=top_k, top_p=top_p,
            seed=seed, priority=priority)
        req = next(r for r in reversed(self.engine.queue)
                   if r.req_id == inner_rid)
        rid = self._next_outer_id
        self._next_outer_id += 1
        req.req_id = rid        # re-key to the supervisor's id space
        self._tracked[rid] = _Tracked(
            req=req,
            kwargs={"eos_token_id": eos_token_id,
                    "temperature": temperature, "top_k": top_k,
                    "top_p": top_p, "seed": seed},
            max_new=int(max_new_tokens), priority=int(priority),
            inner=req)
        return rid

    def cancel(self, req_id: int) -> bool:
        if self._pending_finished.pop(req_id, None) is not None:
            # terminal result synthesized during a recovery but not yet
            # delivered: cancelling drops the delivery — and must NOT
            # fall through to the engine, whose id space never held
            # this request after the rebuild
            return True
        t = self._tracked.pop(req_id, None)
        if t is None:
            # unknown or already finished.  Never forward an untracked
            # outer id into the engine: after a rebuild the inner
            # counter restarted, so a stale outer id could name (and
            # cancel) an unrelated request
            return False
        self.engine.cancel(req_id)
        return True

    def step(self) -> Dict[int, np.ndarray]:
        """One supervised scheduler iteration: retry transients with
        backoff, recover declared crashes via rebuild + replay, then
        hand back newly finished requests keyed by their ORIGINAL ids."""
        p = self.policy
        attempt = 0
        while True:
            t0 = self._clock()
            try:
                finished = self.engine.step()
            except (KeyboardInterrupt, SystemExit):
                raise
            except RecoveryExhaustedError:
                raise       # breaker already open (dead-engine access)
            except TransientStepError as e:
                attempt += 1
                self.stats["transient_retries"] += 1
                if self._reg.enabled:
                    self._reg.counter(
                        "serve.resilience.transient_retries_total").inc()
                self._event("retry", attempt=attempt,
                            error=f"{type(e).__name__}: {e}"[:200])
                if attempt > p.max_retries:
                    self._recover(e)
                    return self._absorb({})
                self._sleep(min(
                    p.backoff_base_s * p.backoff_factor ** (attempt - 1),
                    p.backoff_max_s))
                continue
            except Exception as e:
                self._recover(e)
                return self._absorb({})
            dt = self._clock() - t0
            if p.slow_step_s is not None and dt > p.slow_step_s:
                self._consecutive_slow += 1
                self.stats["slow_steps"] += 1
                if self._reg.enabled:
                    self._reg.counter(
                        "serve.resilience.slow_steps_total").inc()
                self._event("slow_step", secs=round(dt, 4),
                            consecutive=self._consecutive_slow)
                if self._consecutive_slow >= p.slow_steps_to_crash:
                    n = self._consecutive_slow
                    self._consecutive_slow = 0
                    self._recover(EngineCrashError(
                        f"{n} consecutive steps slower than "
                        f"{p.slow_step_s}s — declaring the engine hung"))
                    return self._absorb({})
            else:
                self._consecutive_slow = 0
            return self._absorb(finished)

    def run_to_completion(self) -> Dict[int, np.ndarray]:
        """Drive supervised steps until every tracked request resolves."""
        results: Dict[int, np.ndarray] = {}
        while self._tracked or self._pending_finished:
            results.update(self.step())
        return results

    # -- introspection (front-end / loadgen / bench surface) ------------
    @property
    def queue(self):
        return self.engine.queue

    @property
    def slots(self):
        return self.engine.slots

    @property
    def alloc(self):
        return self.engine.alloc

    @property
    def cfg(self):
        return self.engine.cfg

    @property
    def queue_depth(self) -> int:
        return self.engine.queue_depth

    @property
    def active_requests(self) -> int:
        return self.engine.active_requests

    @property
    def live_requests(self) -> int:
        return len(self._tracked)

    def _blocks_needed(self, n_tokens: int) -> int:
        return self.engine._blocks_needed(n_tokens)

    def batch_occupancy(self) -> float:
        return self.engine.batch_occupancy()

    def kv_utilization(self) -> float:
        return self.engine.kv_utilization()

    def kv_leak_report(self) -> Dict[str, int]:
        return self.engine.kv_leak_report()

    def spec_stats(self):
        return self.engine.spec_stats()

    def aot_stats(self):
        return self.engine.aot_stats()

    def resilience_stats(self) -> Dict[str, object]:
        """Engine preemption counters merged with the supervisor's
        crash-recovery counters — one dict for bench rows / gauges."""
        s: Dict[str, object] = dict(self.engine.resilience_stats())
        s.update(self.stats)
        s["restarts_in_window"] = len(self._restart_times)
        return s

    def __getattr__(self, name):
        # anything not supervised is plain engine surface
        if name == "engine":     # not set yet: don't recurse
            raise AttributeError(name)
        return getattr(self.engine, name)

    # -- cross-replica re-placement surface (serving/fleet.py) ----------
    def extract_request(self, req_id: int) -> Optional[PortableRequest]:
        """Lift a live request out of this engine for re-placement on
        another replica (the fleet router's drain/rebalance path).

        A RUNNING request is preempted first — its committed KV pages
        spill through the ordinary CRC-checked snapshot path — so the
        returned :class:`PortableRequest` carries the page bytes and
        the target replica can restore them instead of recomputing.
        The request stops existing here (no terminal state is
        delivered); the caller owns its continuation.  Returns None
        for unknown / already-finished ids (a pending synthesized
        result is NOT extractable — collect it from ``step()``)."""
        t = self._tracked.pop(req_id, None)
        if t is None:
            return None
        self._bridge(t)                 # fold any unabsorbed tokens in
        eng = self.engine
        for slot in range(eng.B):
            r = eng.slots[slot]
            if r is not None and r.req_id == req_id:
                eng.preempt(slot)       # snapshot committed KV first
                break
        snap = eng._spill.pop(req_id, None)
        eng.cancel(req_id)              # queued by now; frees nothing
        return PortableRequest(
            prompt=t.req.prompt, out=list(t.req.out),
            kwargs=dict(t.kwargs), max_new=t.max_new,
            priority=t.priority, snapshot=snap)

    def adopt_request(self, portable: PortableRequest) -> int:
        """Admit a request extracted from ANOTHER replica, resuming it
        under a fresh id in this supervisor's id space.

        With a KV snapshot (same pool geometry — all replicas of one
        fleet are built from one factory), the page bytes are seeded
        into this engine's spill tier and admission restores them into
        fresh blocks exactly as if the preemption had happened here: no
        recompute, bit-identical.  Without one, the request is replayed
        from its committed token prefix (the crash-recovery machinery's
        path — also bit-identical)."""
        kw = portable.kwargs
        snap = portable.snapshot
        if snap is not None and self.engine.spill_compatible(snap):
            from ..inference.serving import GenRequest
            rid = self._next_outer_id
            self._next_outer_id += 1
            req = GenRequest(
                rid, portable.prompt, portable.max_new,
                kw["eos_token_id"], temperature=kw["temperature"],
                top_k=kw["top_k"], top_p=kw["top_p"], seed=kw["seed"],
                priority=portable.priority)
            req.out = [int(x) for x in portable.out]
            if kw["eos_token_id"] is not None \
                    and kw["eos_token_id"] in req.out:
                # keep the retire contract for a committed eos the
                # source had not retired yet
                req.eos_pos = req.out.index(kw["eos_token_id"])
            snap.req_id = rid           # re-keyed to this id space
            if TRACER.enabled:
                # adopt under the ambient trace (the fleet's re-place
                # path activates the original request's trace): no
                # add_request runs on this path, so stamp it here
                atr = TRACER.current()
                if atr is not None:
                    req.trace = atr
                    atr.mark("enqueued")
            self.engine.adopt_preempted(req, snap)
            self._tracked[rid] = _Tracked(
                req=req, kwargs=dict(kw), max_new=portable.max_new,
                priority=portable.priority, inner=req)
            return rid
        committed = np.concatenate(
            [portable.prompt, np.asarray(portable.out, np.int32)]) \
            if portable.out else portable.prompt
        return self.add_request(
            committed, portable.max_new - len(portable.out),
            kw["eos_token_id"], temperature=kw["temperature"],
            top_k=kw["top_k"], top_p=kw["top_p"], seed=kw["seed"],
            priority=portable.priority)

    def take_pending_result(self, req_id: int) -> Optional[np.ndarray]:
        """Pop a terminal result synthesized during a recovery but not
        yet delivered through ``step()`` (the drain path collects these
        directly instead of extracting a request that no longer
        exists)."""
        return self._pending_finished.pop(req_id, None)

    def tracked_request(self, req_id: int):
        """The live outer ``GenRequest`` for ``req_id`` (tokens
        accumulate here across this engine's internal crash replays),
        or None once terminal."""
        t = self._tracked.get(req_id)
        return None if t is None else t.req

    # -- internals ------------------------------------------------------
    def _bridge(self, t: _Tracked) -> None:
        """Fold a replayed request's fresh inner tokens into its outer
        object (no-op before any crash, when inner IS the outer)."""
        if t.inner is t.req:
            return
        bridged = len(t.req.out) - t.base
        new = t.inner.out[bridged:]
        if new:
            t.req.out.extend(int(x) for x in new)
        if t.inner.eos_pos is not None and t.req.eos_pos is None:
            t.req.eos_pos = t.base + t.inner.eos_pos

    def _absorb(self, finished: Dict[int, np.ndarray]
                ) -> Dict[int, np.ndarray]:
        """Bridge replayed requests' fresh tokens into the outer
        request objects and translate finished ids back to the
        caller's originals."""
        for t in self._tracked.values():
            self._bridge(t)
        out: Dict[int, np.ndarray] = {}
        for rid, t in list(self._tracked.items()):
            # inner requests are re-keyed to their outer ids at
            # creation, so the engine's finished dict speaks outer ids
            if rid not in finished:
                continue
            arr = finished.pop(rid)
            if t.inner is not t.req:
                # exact final sync (retire may have truncated at eos)
                t.req.out = t.req.out[:t.base] + [int(x)
                                                  for x in t.inner.out]
                arr = np.concatenate(
                    [t.req.prompt, np.asarray(t.req.out, np.int32)])
            out[rid] = arr
            del self._tracked[rid]
        out.update(finished)        # untracked passthrough (defensive)
        if self._pending_finished:
            out.update(self._pending_finished)
            self._pending_finished = {}
        return out

    def _recover(self, exc: BaseException) -> None:
        """Declared crash: circuit-breaker check, flight dump, rebuild
        through the factory, replay every live request from its
        committed token prefix."""
        p = self.policy
        now = self._clock()
        self.last_error = exc
        self.stats["crashes"] += 1
        if self._reg.enabled:
            self._reg.counter("serve.resilience.crashes_total").inc()
        self._event("crash", error=f"{type(exc).__name__}: {exc}"[:300])
        self._dump_flight(
            f"engine recovery: {type(exc).__name__}: {exc}")
        while self._restart_times and \
                now - self._restart_times[0] > p.restart_window_s:
            self._restart_times.popleft()
        if len(self._restart_times) >= p.max_restarts:
            self.stats["circuit_opens"] += 1
            if self._reg.enabled:
                self._reg.counter(
                    "serve.resilience.circuit_open_total").inc()
            self._event("circuit_open",
                        restarts=len(self._restart_times))
            raise RecoveryExhaustedError(
                f"{len(self._restart_times)} engine restarts within "
                f"{p.restart_window_s}s — circuit breaker open; last "
                f"error: {type(exc).__name__}: {exc}") from exc
        self._restart_times.append(now)
        t0 = self._clock()
        # drop the crashed engine's pools before rebuilding; the
        # sentinel (not None) keeps every engine-surface access typed
        # if the rebuild itself fails
        self.engine = _DeadEngine(exc)
        try:
            rebuilt = self._factory()
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as build_err:
            self.engine = _DeadEngine(build_err)
            self.stats["rebuild_failures"] += 1
            self.stats["circuit_opens"] += 1
            if self._reg.enabled:
                self._reg.counter(
                    "serve.resilience.rebuild_failures_total").inc()
                self._reg.counter(
                    "serve.resilience.circuit_open_total").inc()
            self._event("rebuild_failed",
                        error=f"{type(build_err).__name__}: "
                              f"{build_err}"[:300])
            raise RecoveryExhaustedError(
                "engine rebuild failed during crash recovery — "
                "escalating to the typed abort-all path; rebuild "
                f"error: {type(build_err).__name__}: {build_err}"
            ) from build_err
        self.engine = rebuilt
        replayed = 0
        for rid, t in list(self._tracked.items()):
            req = t.req
            if req.eos_pos is not None or len(req.out) >= t.max_new:
                # crashed between producing the final token and the
                # retire: synthesize the terminal result from the
                # committed prefix (eos truncation included)
                if req.eos_pos is not None:
                    req.out = req.out[:req.eos_pos + 1]
                self._pending_finished[rid] = np.concatenate(
                    [req.prompt, np.asarray(req.out, np.int32)])
                del self._tracked[rid]
                continue
            committed = np.concatenate(
                [req.prompt, np.asarray(req.out, np.int32)]) \
                if req.out else req.prompt
            kw = t.kwargs
            # request tracing (ISSUE 20): replay under the ORIGINAL
            # trace — the fresh inner GenRequest adopts it through the
            # ambient channel, so the post-crash spans (queue_wait,
            # replay prefill, decode) stay on one trace_id
            tr = getattr(req, "trace", None) if TRACER.enabled else None
            t_rp = tr.now() if tr is not None else 0.0
            with TRACER.activating(tr):
                inner_rid = self.engine.add_request(
                    committed, t.max_new - len(req.out),
                    kw["eos_token_id"], temperature=kw["temperature"],
                    top_k=kw["top_k"], top_p=kw["top_p"],
                    seed=kw["seed"], priority=t.priority)
            t.inner = next(r for r in reversed(self.engine.queue)
                           if r.req_id == inner_rid)
            t.inner.req_id = rid    # replayed under the same outer id
            t.base = len(req.out)
            if tr is not None:
                tr.add("crash_replay", t_rp, tr.now(),
                       committed=int(len(committed)),
                       error=f"{type(exc).__name__}")
                tr.meta["replayed"] = True
            replayed += 1
        dt = self._clock() - t0
        self.stats["recoveries"] += 1
        self.stats["replayed_requests"] += replayed
        if self._reg.enabled:
            self._reg.counter("serve.resilience.recoveries_total").inc()
            self._reg.counter(
                "serve.resilience.replayed_requests_total").inc(replayed)
            self._reg.histogram("serve.resilience.recovery_secs",
                                unit="s").record(dt)
        self._event("recovered", replayed=replayed, secs=round(dt, 6))

    def _event(self, action: str, **fields) -> None:
        if self._reg.enabled:
            self._reg.event("serve", action=f"resilience_{action}",
                            **fields)

    def _dump_flight(self, reason: str) -> None:
        """Flight-ring post-mortem on every recovery — the serve event
        ring around the crash is the incident timeline."""
        try:
            from ..observability.flight_recorder import FlightRecorder
            for sink in self._reg.sinks:
                if isinstance(sink, FlightRecorder) \
                        and sink.directory is not None:
                    sink.dump(reason)
        except Exception as dump_err:   # the dump must not mask recovery
            self._event("flight_dump_failed",
                        error=str(dump_err)[:200])
