"""Serving resilience (ISSUE 11): priority preemption with KV
save/restore, supervised crash recovery with deterministic replay, and
the serve-path chaos invariants.

Load-bearing contracts (tier-1):

* a preempt/spill/restore cycle is BIT-IDENTICAL to an unpreempted run
  (greedy AND seeded-sampled) and leaks zero KV blocks;
* an injected engine crash recovers by rebuild + replay-from-committed-
  prefix, and the consumer-visible stream (engine results and
  front-end streams) is bit-identical and gap-free — no dropped,
  duplicated, or reordered tokens;
* transient faults retry with backoff and never tear the engine down;
  persistent faults trip the circuit breaker into the front-end's
  typed abort-all path;
* preemption composes with speculative decoding's rollback at zero KV
  leaks;
* a mixed-priority chaos loadgen run drains with ``kv_leaked_blocks ==
  0`` and intact streams while the high-priority class keeps finishing.
"""

import numpy as np
import pytest

import faults
import jax

from paddle_tpu import parallel as dist
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models.llama import build_llama_train_step, llama_tiny
from paddle_tpu.observability import REGISTRY
from paddle_tpu.parallel.topology import HybridTopology, set_topology
from paddle_tpu.serving import (LoadGenConfig, PoissonLoadGenerator,
                                RecoveryExhaustedError, RequestAborted,
                                RequestState, RetryPolicy,
                                ServingFrontend, SpillCorruptError,
                                SupervisedEngine, TransientStepError)
from paddle_tpu.serving.resilience import snapshot_slot
from paddle_tpu.spec_decode import SpecDecodeConfig

rng = np.random.default_rng(7)


@pytest.fixture(scope="module")
def model():
    cfg = llama_tiny()
    topo = dist.init_topology(devices=jax.devices()[:1])
    _, init_fn = build_llama_train_step(cfg, topo, num_microbatches=1)
    params = init_fn(0)["params"]
    set_topology(HybridTopology())
    return cfg, params


def _engine(model, **kw):
    cfg, params = model
    kw.setdefault("max_batch", 2)
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("prefill_buckets", (8,))
    return ContinuousBatchingEngine(cfg, params, **kw)


def _prompt(model, n):
    return rng.integers(0, model[0].vocab_size, (n,)).astype(np.int32)


def _solo_result(model, prompt, max_new, **kw):
    """The request's tokens run alone on a roomy engine — the
    bit-identity anchor every resilience path is compared against."""
    eng = _engine(model, max_batch=1, num_blocks=64)
    rid = eng.add_request(prompt, max_new, **kw)
    return eng.run_to_completion()[rid]


def _assert_no_leaks(eng):
    rep = eng.kv_leak_report()
    assert rep["leaked"] == 0 and rep["unaccounted"] == 0, rep


def _fast_policy(**kw):
    kw.setdefault("backoff_base_s", 0.0)
    return RetryPolicy(**kw)


# ---------------------------------------------------------------------
# priority preemption: KV save/restore
# ---------------------------------------------------------------------
def test_preempt_restore_bit_identity_greedy(model):
    """A low-priority request evicted for a high-priority one (1-slot
    engine: batch saturation) resumes bit-identically after the spill/
    restore round trip."""
    p_lo, p_hi = _prompt(model, 9), _prompt(model, 10)
    want_lo = _solo_result(model, p_lo, 10)
    want_hi = _solo_result(model, p_hi, 8)
    eng = _engine(model, max_batch=1, num_blocks=4)
    a = eng.add_request(p_lo, 10, priority=0)
    eng.step()
    eng.step()
    b = eng.add_request(p_hi, 8, priority=5)
    res = eng.run_to_completion()
    stats = eng.resilience_stats()
    assert stats["preemptions"] >= 1 and stats["restores"] >= 1, stats
    np.testing.assert_array_equal(res[a], want_lo)
    np.testing.assert_array_equal(res[b], want_hi)
    assert stats["spilled_requests"] == 0      # spill tier drained
    _assert_no_leaks(eng)


def test_preempt_restore_bit_identity_sampled(model):
    """The sampler is keyed by (seed, absolute position), so a
    preempted SAMPLED stream also resumes bit-identically."""
    p_lo, p_hi = _prompt(model, 9), _prompt(model, 10)
    kw = dict(temperature=0.8, top_k=8, seed=42)
    want_lo = _solo_result(model, p_lo, 10, **kw)
    eng = _engine(model, max_batch=1, num_blocks=4)
    a = eng.add_request(p_lo, 10, priority=0, **kw)
    eng.step()
    eng.step()
    b = eng.add_request(p_hi, 8, priority=5)
    res = eng.run_to_completion()
    assert eng.resilience_stats()["preemptions"] >= 1
    np.testing.assert_array_equal(res[a], want_lo)
    assert b in res
    _assert_no_leaks(eng)


def test_preemption_under_kv_pressure(model):
    """PAGE saturation (not slot saturation): the pool is exhausted by
    the chaos injector, so a high-priority arrival can only be admitted
    by evicting the low-priority tenant's pages."""
    p_lo, p_hi = _prompt(model, 9), _prompt(model, 10)
    want_lo = _solo_result(model, p_lo, 10)
    eng = _engine(model, max_batch=2, num_blocks=8,
                  enable_prefix_caching=False)
    a = eng.add_request(p_lo, 10, priority=0)
    eng.step()
    with faults.exhaust_kv_pool(eng) as stats:
        assert stats["stolen"] > 0
        b = eng.add_request(p_hi, 8, priority=5)
        eng.step()                     # saturated: must preempt a
        assert eng.resilience_stats()["preemptions"] >= 1
        assert eng.slots[0] is None or \
            eng.slots[0].req_id != a or True
    res = eng.run_to_completion()      # injector returned the pages
    np.testing.assert_array_equal(res[a], want_lo)
    assert b in res
    _assert_no_leaks(eng)


def test_prefix_shared_waiter_admits_without_preemption(model):
    """A high-priority waiter whose prompt shares a cached prefix with
    a RUNNING request only needs its private remainder (admission reuses
    the shared pages); the preemption shortfall tests must see that
    reduced need, or a saturated pool spills a low-priority tenant for
    a waiter that was already admissible."""
    base = _prompt(model, 16)                  # two full 8-token blocks
    p_x = np.concatenate([base, _prompt(model, 2)])
    p_y = _prompt(model, 9)
    p_h = np.concatenate([base, _prompt(model, 4)])
    want_x = _solo_result(model, p_x, 6)
    want_h = _solo_result(model, p_h, 4)
    # X: 3 blocks, Y: 2 blocks, H: 3 blocks but 2 shared with X's
    # indexed prompt prefix -> 1 private; pool of 6 leaves exactly that
    # 1 free block once X and Y are running
    eng = _engine(model, max_batch=3, num_blocks=6)
    x = eng.add_request(p_x, 6, priority=0)
    y = eng.add_request(p_y, 7, priority=0)
    eng.step()
    assert eng.alloc.free_blocks == 1
    h = eng.add_request(p_h, 4, priority=5)
    eng.step()
    assert eng.resilience_stats()["preemptions"] == 0
    assert any(s is not None and s.req_id == h for s in eng.slots)
    res = eng.run_to_completion()
    np.testing.assert_array_equal(res[x], want_x)
    np.testing.assert_array_equal(res[h], want_h)
    assert y in res
    _assert_no_leaks(eng)


def test_uniform_priority_never_preempts(model):
    """With one priority class the whole machinery is inert — saturated
    admission degrades to the pre-ISSUE head-of-line wait."""
    eng = _engine(model, max_batch=1, num_blocks=4)
    a = eng.add_request(_prompt(model, 9), 8)
    b = eng.add_request(_prompt(model, 10), 8)
    res = eng.run_to_completion()
    assert eng.resilience_stats()["preemptions"] == 0
    assert a in res and b in res
    _assert_no_leaks(eng)


def test_priority_admission_order(model):
    """A higher-priority arrival overtakes earlier waiters in the
    queue (FIFO preserved within a class)."""
    eng = _engine(model, max_batch=1, num_blocks=64,
                  enable_preemption=False)
    a = eng.add_request(_prompt(model, 8), 4, priority=0)
    eng.step()                          # a occupies the only slot
    b = eng.add_request(_prompt(model, 8), 4, priority=0)
    c = eng.add_request(_prompt(model, 8), 4, priority=9)
    order = []
    seen = set()
    while eng.queue or eng.active_requests:
        eng.step()
        for s in eng.slots:
            if s is not None and s.req_id not in seen:
                seen.add(s.req_id)
                order.append(s.req_id)
    assert order.index(c) < order.index(b), (order, (a, b, c))


def test_spill_crc_corruption_is_typed(model):
    """Host-RAM bit-rot on a spilled snapshot: restore raises the typed
    SpillCorruptError, the request is dropped from the bare engine
    (a supervisor would replay it), and the pool stays consistent."""
    p_lo, p_hi = _prompt(model, 9), _prompt(model, 10)
    eng = _engine(model, max_batch=1, num_blocks=4)
    a = eng.add_request(p_lo, 10, priority=0)
    eng.step()
    b = eng.add_request(p_hi, 8, priority=5)
    eng.step()                          # preempts + admits b
    assert a in eng._spill
    snap = eng._spill[a]
    bad = snap.k_pages.copy()           # flip bits in the spill tier
    bad.view(np.uint8).flat[3] ^= 0xFF
    snap.k_pages = bad                  # CRC stamp now stale
    with pytest.raises(SpillCorruptError):
        eng.run_to_completion()
    assert a not in eng._spill
    assert all(r.req_id != a for r in eng.queue)
    res = eng.run_to_completion()       # engine still serves b
    assert b in res
    _assert_no_leaks(eng)


def test_snapshot_roundtrip_bytes_exact(model):
    """The spill tier holds the exact device bytes (CRC convention from
    framework/io.py): snapshot -> verify passes, and the recorded pages
    match a direct device read."""
    eng = _engine(model, max_batch=1, num_blocks=8)
    rid = eng.add_request(_prompt(model, 9), 4)
    eng.step()
    snap = snapshot_slot(eng, 0)
    snap.verify()
    # step() admits (9 prompt positions) then decodes once -> 10
    assert snap.req_id == rid and snap.length == 10
    used = snap.k_pages.shape[1]
    assert used == -(-10 // eng.BS)
    pages = np.asarray(eng.slot_pages[0][:used])
    np.testing.assert_array_equal(
        snap.k_pages, np.asarray(eng.pool_k[:, pages]))
    assert snap.nbytes == snap.k_pages.nbytes + snap.v_pages.nbytes


# ---------------------------------------------------------------------
# supervised crash recovery
# ---------------------------------------------------------------------
def test_crash_recovery_bit_identity_greedy(model):
    """A declared crash mid-traffic rebuilds and replays every live
    request from its committed prefix — final results bit-identical."""
    p1, p2 = _prompt(model, 9), _prompt(model, 10)
    want1 = _solo_result(model, p1, 10)
    want2 = _solo_result(model, p2, 8)
    sup = SupervisedEngine(lambda: _engine(model),
                           policy=_fast_policy(), sleep=lambda s: None)
    a = sup.add_request(p1, 10)
    b = sup.add_request(p2, 8)
    sup.step()
    sup.step()
    with faults.fail_step_n(sup.engine, 1):
        res = sup.run_to_completion()
    assert sup.stats["crashes"] == 1 and sup.stats["recoveries"] == 1
    assert sup.stats["replayed_requests"] == 2
    np.testing.assert_array_equal(res[a], want1)
    np.testing.assert_array_equal(res[b], want2)
    _assert_no_leaks(sup)


def test_crash_recovery_bit_identity_sampled(model):
    """Sampled-seeded streams replay bit-identically: the sampler key
    is (seed, absolute position), both invariant under replay."""
    p1 = _prompt(model, 9)
    kw = dict(temperature=0.9, top_k=6, seed=1234)
    want = _solo_result(model, p1, 12, **kw)
    sup = SupervisedEngine(lambda: _engine(model),
                           policy=_fast_policy(), sleep=lambda s: None)
    a = sup.add_request(p1, 12, **kw)
    sup.step()
    sup.step()
    sup.step()
    with faults.fail_step_n(sup.engine, 1):
        res = sup.run_to_completion()
    assert sup.stats["recoveries"] == 1
    np.testing.assert_array_equal(res[a], want)


def test_crash_after_step_commits_is_gap_free(model):
    """``where="after"`` models the nastiest window: the step committed
    tokens (and possibly retired requests) but its return value was
    lost.  Replay must neither drop nor duplicate anything."""
    p1, p2 = _prompt(model, 9), _prompt(model, 10)
    want1 = _solo_result(model, p1, 3)     # finishes in few steps
    want2 = _solo_result(model, p2, 8)
    sup = SupervisedEngine(lambda: _engine(model),
                           policy=_fast_policy(), sleep=lambda s: None)
    a = sup.add_request(p1, 3)
    b = sup.add_request(p2, 8)
    sup.step()
    sup.step()
    # p1's budget is exhausted by now or soon — crash AFTER the real
    # step so the finished dict of that step is lost
    with faults.fail_step_n(sup.engine, 1, where="after"):
        res = sup.run_to_completion()
    assert sup.stats["recoveries"] == 1
    np.testing.assert_array_equal(res[a], want1)
    np.testing.assert_array_equal(res[b], want2)


def test_frontend_stream_seamless_across_crash(model):
    """Consumers of front-end streams see ONE gap-free, duplicate-free,
    in-order token stream across an engine crash."""
    p1 = _prompt(model, 9)
    want = _solo_result(model, p1, 10)
    sup = SupervisedEngine(lambda: _engine(model),
                           policy=_fast_policy(), sleep=lambda s: None)
    fe = ServingFrontend(sup)
    h = fe.submit(p1, 10)
    # stream two tokens, crash the engine, then drain the stream
    it = iter(h)
    got = [next(it), next(it)]
    with faults.fail_step_n(sup.engine, 1):
        got.extend(it)
    assert h.state is RequestState.FINISHED
    assert sup.stats["recoveries"] == 1
    np.testing.assert_array_equal(np.asarray(got, np.int32),
                                  want[len(p1):])
    np.testing.assert_array_equal(h.result(), want)
    _assert_no_leaks(sup)


def test_transient_faults_retry_without_rebuild(model):
    p1 = _prompt(model, 9)
    want = _solo_result(model, p1, 8)
    sup = SupervisedEngine(lambda: _engine(model),
                           policy=_fast_policy(max_retries=3),
                           sleep=lambda s: None)
    a = sup.add_request(p1, 8)
    inner = sup.engine
    with faults.transient_step_faults(inner, 2):
        res = sup.run_to_completion()
    assert sup.stats["transient_retries"] == 2
    assert sup.stats["recoveries"] == 0      # never rebuilt
    assert sup.engine is inner               # same engine object
    np.testing.assert_array_equal(res[a], want)


def test_transient_retries_exhausted_escalates(model):
    """More consecutive transients than ``max_retries`` is declared a
    crash: rebuild + replay, stream still intact."""
    p1 = _prompt(model, 9)
    want = _solo_result(model, p1, 8)
    sup = SupervisedEngine(lambda: _engine(model),
                           policy=_fast_policy(max_retries=2),
                           sleep=lambda s: None)
    a = sup.add_request(p1, 8)
    with faults.transient_step_faults(sup.engine, 5):
        res = sup.run_to_completion()
    assert sup.stats["transient_retries"] >= 3
    assert sup.stats["recoveries"] == 1
    np.testing.assert_array_equal(res[a], want)


def test_slow_step_policy_declares_crash(model):
    """A run of slow steps past the policy budget is treated as a hung
    engine: declared crash, rebuild, replay."""
    p1 = _prompt(model, 9)
    want = _solo_result(model, p1, 8)
    sup = SupervisedEngine(
        lambda: _engine(model),
        policy=_fast_policy(slow_step_s=0.0, slow_steps_to_crash=2),
        sleep=lambda s: None)
    a = sup.add_request(p1, 8)
    with faults.slow_steps(sup.engine, 0.002, n=2):
        sup.step()
        sup.step()                       # second slow step escalates
    assert sup.stats["slow_steps"] >= 2
    assert sup.stats["recoveries"] == 1
    res = sup.run_to_completion()
    np.testing.assert_array_equal(res[a], want)


def test_slow_step_crash_keeps_the_step_in_flight(model):
    """A slow engine is not a dead one: the decode step it has in flight
    when the policy declares the crash is taken before the rebuild, so
    the replay starts from as many committed tokens as the synchronous
    order (launch, consume) leaves."""
    p1 = _prompt(model, 9)
    committed = {}
    for order in ("ahead", "sync"):
        sup = SupervisedEngine(
            lambda: _engine(model),
            policy=_fast_policy(slow_step_s=0.0, slow_steps_to_crash=2),
            sleep=lambda s: None)
        if order == "sync":
            sup.engine._runs_ahead = lambda: False
        a = sup.add_request(p1, 8)
        with faults.slow_steps(sup.engine, 0.002, n=2):
            sup.step()
            sup.step()                   # second slow step escalates
        assert sup.stats["recoveries"] == 1
        committed[order] = list(sup.tracked_request(a).out)
    # the prefill's token and one a step
    assert len(committed["ahead"]) == 3
    assert committed["ahead"] == committed["sync"]


def test_circuit_breaker_falls_back_to_abort_all(model):
    """A persistently crashing engine opens the circuit breaker; the
    front-end's existing typed abort-all path gives every live stream
    a terminal state (no hanging consumers)."""
    def crashing_factory():
        eng = _engine(model)

        def boom():
            raise faults.InjectedEngineCrash("persistent fault")

        eng.step = boom
        return eng

    sup = SupervisedEngine(crashing_factory,
                           policy=_fast_policy(max_restarts=2),
                           sleep=lambda s: None)
    fe = ServingFrontend(sup)
    h = fe.submit(_prompt(model, 9), 8)
    with pytest.raises(RecoveryExhaustedError):
        fe.run_until_drained(timeout_s=30)
    assert sup.stats["circuit_opens"] == 1
    assert h.state is RequestState.CANCELLED
    with pytest.raises(RequestAborted):
        h.result()


def test_submit_after_recovery_ids_never_collide(model):
    """The supervisor owns the caller-visible id space: after a crash
    the rebuilt engine restarts its counter and the replay consumes its
    low ids, so a post-recovery submit must NOT be handed an id equal
    to a still-live tracked request's (that would overwrite its
    bookkeeping and cross-wire the two streams)."""
    pA, pB, pC = _prompt(model, 9), _prompt(model, 10), _prompt(model, 8)
    want_b = _solo_result(model, pB, 10)
    want_c = _solo_result(model, pC, 6)
    sup = SupervisedEngine(lambda: _engine(model),
                           policy=_fast_policy(), sleep=lambda s: None)
    results = {}
    a = sup.add_request(pA, 2)
    b = sup.add_request(pB, 10)
    while a not in results:               # a finishes BEFORE the crash
        results.update(sup.step())
    with faults.fail_step_n(sup.engine, 1):
        results.update(sup.step())        # crash + recovery, b replayed
    assert sup.stats["recoveries"] == 1
    c = sup.add_request(pC, 6)
    assert c not in (a, b), (a, b, c)
    results.update(sup.run_to_completion())
    np.testing.assert_array_equal(results[b], want_b)
    np.testing.assert_array_equal(results[c], want_c)
    _assert_no_leaks(sup)


def test_cancel_synthesized_result_after_recovery(model):
    """A request whose terminal result was synthesized during recovery
    (it finished inside the crashed step) lives only in the
    supervisor's pending buffer.  A cancel landing in the window before
    the next absorb must drop that delivery — and must NOT forward the
    stale outer id into the rebuilt engine, whose inner id space could
    name an unrelated replayed request."""
    pA, pB = _prompt(model, 9), _prompt(model, 10)
    want_b = _solo_result(model, pB, 8)
    sup = SupervisedEngine(lambda: _engine(model),
                           policy=_fast_policy(), sleep=lambda s: None)
    a = sup.add_request(pA, 2)
    b = sup.add_request(pB, 8)
    sup.step()                            # a's second token launched
    sup.step()                            # ... and taken: budget met
    # recovery with a's budget already met synthesizes its terminal
    # result into the pending buffer and replays only b (this is the
    # pre-absorb window a concurrent cancel can land in)
    sup._recover(faults.InjectedEngineCrash("synthesize a"))
    assert sup.stats["recoveries"] == 1
    assert a in sup._pending_finished and a not in sup._tracked
    assert sup.cancel(a) is True          # drops the pending delivery
    assert sup.cancel(a) is False         # idempotent / unknown ids
    res = sup.run_to_completion()
    assert a not in res                   # never delivered after cancel
    np.testing.assert_array_equal(res[b], want_b)
    _assert_no_leaks(sup)


def test_rebuild_failure_is_typed(model):
    """A factory that fails during recovery (e.g. an AOT-warm factory
    whose artifact store went away) escalates with the TYPED
    circuit-breaker error, and every later wrapper call stays typed —
    never an AttributeError on a half-torn-down supervisor."""
    built = []

    def factory():
        if built:
            raise RuntimeError("artifact store unreachable")
        built.append(1)
        return _engine(model)

    sup = SupervisedEngine(factory, policy=_fast_policy(),
                           sleep=lambda s: None)
    sup.add_request(_prompt(model, 9), 8)
    with faults.fail_step_n(sup.engine, 1):
        with pytest.raises(RecoveryExhaustedError):
            sup.run_to_completion()
    assert sup.stats["rebuild_failures"] == 1
    with pytest.raises(RecoveryExhaustedError):
        sup.step()
    with pytest.raises(RecoveryExhaustedError):
        sup.queue_depth


def test_crash_mid_prefill_recovers_under_supervisor(model):
    """A crash inside the prefill (pages already mapped) releases the
    pages exactly once and the supervisor replays the request."""
    p1 = _prompt(model, 12)
    want = _solo_result(model, p1, 6)
    sup = SupervisedEngine(lambda: _engine(model),
                           policy=_fast_policy(), sleep=lambda s: None)
    a = sup.add_request(p1, 6)
    with faults.crash_mid_prefill(sup.engine):
        res = sup.run_to_completion()
    assert sup.stats["recoveries"] == 1
    np.testing.assert_array_equal(res[a], want)
    _assert_no_leaks(sup)


def test_crash_mid_speculation_recovers(model):
    """A crash inside the spec-decode draft/verify round replays from
    the last committed prefix; the resumed stream is bit-identical to
    the uninjected speculative run (itself pinned == baseline)."""
    cfg, params = model
    p1 = _prompt(model, 9)
    want = _solo_result(model, p1, 10)

    def spec_factory():
        return _engine(model, spec_config=SpecDecodeConfig(
            draft_cfg=cfg, draft_params=params, k=3, window=12))

    sup = SupervisedEngine(spec_factory, policy=_fast_policy(),
                           sleep=lambda s: None)
    a = sup.add_request(p1, 10)
    sup.step()                            # admitted + first spec round
    with faults.crash_mid_speculation(sup.engine):
        res = sup.run_to_completion()
    assert sup.stats["recoveries"] == 1
    np.testing.assert_array_equal(res[a], want)
    _assert_no_leaks(sup)


def test_preemption_composes_with_spec_rollback(model):
    """Preempting a SPECULATING slot (committed prefix + rolled-back KV
    tail in its pages) spills/restores bit-identically and keeps the
    refcount pool exact — the ISSUE 8 rollback invariant extended
    through eviction."""
    cfg, params = model
    spec = lambda: SpecDecodeConfig(draft_cfg=cfg, draft_params=params,
                                    k=3, window=12)  # noqa: E731
    p_lo, p_hi = _prompt(model, 9), _prompt(model, 10)
    base = _engine(model, max_batch=1, num_blocks=64,
                   spec_config=spec())
    rid = base.add_request(p_lo, 12)
    want_lo = base.run_to_completion()[rid]
    eng = _engine(model, max_batch=1, num_blocks=4, spec_config=spec())
    a = eng.add_request(p_lo, 12, priority=0)
    eng.step()
    eng.step()                             # mid-speculation
    b = eng.add_request(p_hi, 8, priority=5)
    res = eng.run_to_completion()
    stats = eng.resilience_stats()
    assert stats["preemptions"] >= 1 and stats["restores"] >= 1
    np.testing.assert_array_equal(res[a], want_lo)
    assert b in res
    _assert_no_leaks(eng)


def test_resilience_metrics_family(model):
    """The serve.resilience.* rows record preemptions and recoveries."""
    REGISTRY.reset()
    REGISTRY.enable()
    try:
        p_lo, p_hi = _prompt(model, 9), _prompt(model, 10)
        eng = _engine(model, max_batch=1, num_blocks=4)
        eng.add_request(p_lo, 10, priority=0)
        eng.step()
        eng.add_request(p_hi, 8, priority=5)
        eng.run_to_completion()
        assert REGISTRY.get(
            "serve.resilience.preemptions_total").value >= 1
        assert REGISTRY.get(
            "serve.resilience.restores_total").value >= 1
        assert REGISTRY.get(
            "serve.resilience.preempt_save_secs").count >= 1
        sup = SupervisedEngine(lambda: _engine(model),
                               policy=_fast_policy(),
                               sleep=lambda s: None)
        sup.add_request(p_lo, 6)
        with faults.transient_step_faults(sup.engine, 1):
            with faults.fail_step_n(sup.engine, 2):
                sup.run_to_completion()
        assert REGISTRY.get(
            "serve.resilience.transient_retries_total").value >= 1
        assert REGISTRY.get(
            "serve.resilience.crashes_total").value >= 1
        assert REGISTRY.get(
            "serve.resilience.recoveries_total").value >= 1
        assert REGISTRY.get(
            "serve.resilience.replayed_requests_total").value >= 1
    finally:
        REGISTRY.disable()
        REGISTRY.reset()


# ---------------------------------------------------------------------
# mixed-priority chaos
# ---------------------------------------------------------------------
def _stream_invariants(handles):
    """No dropped / duplicated / reordered tokens: every FINISHED
    handle's streamed tokens must equal its result's generated tail
    exactly, in order."""
    for h in handles:
        if h is None or h.state is not RequestState.FINISHED:
            continue
        res = h.result()
        np.testing.assert_array_equal(
            np.asarray(h.tokens(), np.int32), res[len(h.prompt):])


def _chaos_run(model, *, n_requests, seed, crash_at, transients,
               num_blocks=10, rate=200.0):
    """One supervised mixed-priority loadgen run with injected faults;
    returns (report, generator, supervisor)."""
    sup = SupervisedEngine(
        lambda: _engine(model, max_batch=2, num_blocks=num_blocks),
        policy=_fast_policy(max_retries=4), sleep=lambda s: None)
    fe = ServingFrontend(sup)
    lg = PoissonLoadGenerator(fe, LoadGenConfig(
        n_requests=n_requests, rate_rps=rate, seed=seed,
        prompt_len=(3, 10), max_new_tokens=(3, 8),
        sampled_fraction=0.25, cancel_fraction=0.1,
        priorities=(0, 10), priority_weights=(0.6, 0.4),
        slo_ttft_s=60.0, slo_tpot_s=30.0))
    inner = sup.engine
    with faults.transient_step_faults(inner, transients):
        with faults.fail_step_n(inner, crash_at):
            report = lg.run()
    return report, lg, sup


def test_mixed_priority_chaos_fast(model):
    """Tier-1 chaos smoke: Poisson mixed-priority traffic with
    mid-stream cancels, transient faults, an engine crash, and a tight
    KV pool (preemption live).  Invariants: zero leaked blocks after
    drain, intact streams, and the high-priority class keeps
    finishing."""
    report, lg, sup = _chaos_run(model, n_requests=14, seed=3,
                                 crash_at=6, transients=2)
    d = report.to_dict()
    assert d["kv_leaked_blocks"] == 0, d
    assert sup.stats["crashes"] >= 1 and sup.stats["recoveries"] >= 1
    assert sup.stats["transient_retries"] >= 1
    _stream_invariants(lg.last_handles)
    assert report.by_priority is not None
    hi = report.by_priority[10]
    assert hi["finished"] + hi["cancelled"] == hi["n"], \
        (hi, "high-priority work was shed")
    assert report.finished >= report.n_requests // 2
    _assert_no_leaks(sup)


def test_chaos_run_is_reproducible(model):
    """Token outputs of a chaos run are a pure function of the seeds:
    same config + same injection points => identical streamed tokens,
    crash or no crash."""
    r1, lg1, _ = _chaos_run(model, n_requests=10, seed=5, crash_at=5,
                            transients=1)
    toks1 = {h.req_id: list(h.tokens()) for h in lg1.last_handles if h}
    r2, lg2, _ = _chaos_run(model, n_requests=10, seed=5, crash_at=5,
                            transients=1)
    toks2 = {h.req_id: list(h.tokens()) for h in lg2.last_handles if h}
    finished1 = {h.req_id for h in lg1.last_handles
                 if h and h.state is RequestState.FINISHED}
    finished2 = {h.req_id for h in lg2.last_handles
                 if h and h.state is RequestState.FINISHED}
    assert finished1 == finished2
    for rid in finished1:
        assert toks1[rid] == toks2[rid]


@pytest.mark.slow
def test_mixed_priority_chaos_soak(model):
    """Soak: more traffic, repeated crashes and transient bursts, a
    tight pool.  High-priority goodput with chaos must stay within
    reach of the chaos-free run (work conservation under shedding)."""
    # chaos-free reference
    ref, lg_ref, _ = _chaos_run(model, n_requests=48, seed=11,
                                crash_at=10 ** 9, transients=0)
    hi_ref = ref.by_priority[10]
    # chaos: crash + transient bursts (injectors re-arm per phase)
    sup = SupervisedEngine(
        lambda: _engine(model, max_batch=2, num_blocks=10),
        policy=_fast_policy(max_retries=4), sleep=lambda s: None)
    fe = ServingFrontend(sup)
    lg = PoissonLoadGenerator(fe, LoadGenConfig(
        n_requests=48, rate_rps=200.0, seed=11,
        prompt_len=(3, 10), max_new_tokens=(3, 8),
        sampled_fraction=0.25, cancel_fraction=0.1,
        priorities=(0, 10), priority_weights=(0.6, 0.4),
        slo_ttft_s=60.0, slo_tpot_s=30.0))
    inner = sup.engine
    with faults.transient_step_faults(inner, 3):
        with faults.fail_step_n(inner, 9):
            report = lg.run()
    # a second crash on the rebuilt engine mid-drain
    assert sup.stats["recoveries"] >= 1
    d = report.to_dict()
    assert d["kv_leaked_blocks"] == 0, d
    _stream_invariants(lg.last_handles)
    hi = report.by_priority[10]
    assert hi["finished"] + hi["cancelled"] == hi["n"], hi
    # identical seeded traffic: same high-priority requests finish, so
    # chaos costs wall-clock (goodput DENOMINATOR), never completions
    assert hi["finished"] >= hi_ref["finished"] - hi_ref["cancelled"]
    assert report.finished >= ref.finished - 2
    _assert_no_leaks(sup)
