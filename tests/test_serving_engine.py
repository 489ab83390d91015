"""Continuous-batching engine: iteration-level scheduling over the paged
KV pool (inference/serving.py).

The load-bearing guarantee: a request's output is INDEPENDENT of which
other requests share the batch or when it was admitted — pinned by
comparing a staggered multi-request run against a batch-of-one engine
(identical code path, so equality is exact), plus a logits-tolerance
check against the dense (non-paged) decoder."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import parallel as dist
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models.llama import llama_tiny, build_llama_train_step
from paddle_tpu.parallel.topology import HybridTopology, set_topology

rng = np.random.default_rng(0)


@pytest.fixture(scope="module")
def model():
    cfg = llama_tiny()
    topo = dist.init_topology(devices=jax.devices()[:1])
    _, init_fn = build_llama_train_step(cfg, topo, num_microbatches=1)
    params = init_fn(0)["params"]
    set_topology(HybridTopology())
    return cfg, params


def _solo(cfg, params, prompt, max_new):
    eng = ContinuousBatchingEngine(cfg, params, max_batch=1,
                                   block_size=8, num_blocks=64)
    eng.add_request(prompt, max_new)
    return list(eng.run_to_completion().values())[0]


def test_staggered_batch_matches_solo(model):
    """Three requests with different prompt lengths and budgets, the
    third admitted mid-flight: every result equals its batch-of-one
    run (scheduling must not leak state across slots)."""
    cfg, params = model
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 9, 3)]
    budgets = [6, 4, 8]

    eng = ContinuousBatchingEngine(cfg, params, max_batch=2,
                                   block_size=8, num_blocks=64)
    r0 = eng.add_request(prompts[0], budgets[0])
    r1 = eng.add_request(prompts[1], budgets[1])
    results = {}
    results.update(eng.step())
    results.update(eng.step())
    r2 = eng.add_request(prompts[2], budgets[2])   # joins mid-flight
    results.update(eng.run_to_completion())
    assert set(results) == {r0, r1, r2}
    for rid, prompt, budget in zip((r0, r1, r2), prompts, budgets):
        want = _solo(cfg, params, prompt, budget)
        np.testing.assert_array_equal(results[rid], want)
        assert len(results[rid]) == len(prompt) + budget


def test_engine_logits_match_dense_decoder(model):
    """Paged decode numerics vs the dense decoder on the same prefix."""
    from paddle_tpu.models.generation import build_llama_decoder
    cfg, params = model
    prompt = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)

    eng = ContinuousBatchingEngine(cfg, params, max_batch=1,
                                   block_size=8, num_blocks=64)
    eng.add_request(prompt, 4)
    prefill, step = build_llama_decoder(cfg, len(prompt) + 5,
                                        use_pallas=False)
    cache, logits = jax.jit(prefill)(params, prompt[None, :])
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    pos = len(prompt)
    while any(s is not None for s in eng.slots) or eng.queue:
        eng.step()
        if eng.last_logits is None:
            continue
        cache, dlogits = step(params, cache, tok, pos)
        np.testing.assert_allclose(eng.last_logits[0],
                                   np.asarray(dlogits)[0],
                                   rtol=2e-3, atol=2e-3)
        tok = jnp.argmax(dlogits, -1).astype(jnp.int32)
        pos += 1
        if pos >= len(prompt) + 4:
            break


def test_page_exhaustion_queues_requests(model):
    """With a pool too small for two sequences, the second request waits
    for the first to retire and still completes correctly."""
    cfg, params = model
    p1 = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
    p2 = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
    # 3 blocks of 8 = 24 token slots; each request needs 2 blocks (12
    # tokens) — only one fits at a time
    eng = ContinuousBatchingEngine(cfg, params, max_batch=2,
                                   block_size=8, num_blocks=3)
    a = eng.add_request(p1, 4)
    b = eng.add_request(p2, 4)
    eng.step()
    assert eng.slots[1] is None          # p2 queued on page pressure
    results = eng.run_to_completion()
    np.testing.assert_array_equal(results[a], _solo(cfg, params, p1, 4))
    np.testing.assert_array_equal(results[b], _solo(cfg, params, p2, 4))


def test_moe_engine_runs(model):
    """MoE config serves through the same engine (grouped-GEMM FFN)."""
    cfg = llama_tiny(moe_num_experts=4)
    topo = dist.init_topology(devices=jax.devices()[:1])
    _, init_fn = build_llama_train_step(cfg, topo, num_microbatches=1)
    params = init_fn(0)["params"]
    set_topology(HybridTopology())
    prompt = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    eng = ContinuousBatchingEngine(cfg, params, max_batch=2,
                                   block_size=8, num_blocks=32)
    rid = eng.add_request(prompt, 5)
    out = eng.run_to_completion()[rid]
    assert out.shape == (9,)
    np.testing.assert_array_equal(out[:4], prompt)


def test_oversized_request_rejected(model):
    cfg, params = model
    eng = ContinuousBatchingEngine(cfg, params, max_batch=1,
                                   block_size=8, num_blocks=3)
    with pytest.raises(ValueError, match="pages"):
        eng.add_request(np.zeros(20, np.int32), 12)


def test_one_token_budget_and_prefill_eos(model):
    """max_new_tokens=1 returns exactly one generated token (the prefill
    argmax) without entering the decode batch; a prefill token equal to
    eos retires immediately too."""
    cfg, params = model
    prompt = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
    eng = ContinuousBatchingEngine(cfg, params, max_batch=1,
                                   block_size=8, num_blocks=32)
    rid = eng.add_request(prompt, 1)
    out = eng.run_to_completion()[rid]
    assert out.shape == (6,)
    first = int(out[-1])

    eng2 = ContinuousBatchingEngine(cfg, params, max_batch=1,
                                    block_size=8, num_blocks=32)
    rid2 = eng2.add_request(prompt, 10, eos_token_id=first)
    out2 = eng2.run_to_completion()[rid2]
    np.testing.assert_array_equal(out2, out)   # stopped at the eos


def test_prefix_cache_reuses_and_preserves_output(model):
    """Two requests sharing a 2-block prompt prefix: the second admission
    must reuse the indexed pages (stats) and produce exactly the output
    of a caching-disabled engine."""
    cfg, params = model
    prefix = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    p1 = np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (5,))
                         .astype(np.int32)])
    p2 = np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (3,))
                         .astype(np.int32)])

    eng = ContinuousBatchingEngine(cfg, params, max_batch=1,
                                   block_size=8, num_blocks=64)
    a = eng.add_request(p1, 4)
    res = eng.run_to_completion()
    assert eng.stats["prefix_blocks_registered"] >= 2
    b = eng.add_request(p2, 4)
    res.update(eng.run_to_completion())
    assert eng.stats["prefix_blocks_reused"] >= 2

    for rid, p in ((a, p1), (b, p2)):
        cold = ContinuousBatchingEngine(cfg, params, max_batch=1,
                                        block_size=8, num_blocks=64,
                                        enable_prefix_caching=False)
        cold.add_request(p, 4)
        want = list(cold.run_to_completion().values())[0]
        np.testing.assert_array_equal(res[rid], want)


def test_chunk_fill_logits_match_dense_prefill(model):
    """The paged suffix prefill must reproduce dense-prefill next-token
    logits when the prefix pages hold the same KV."""
    from paddle_tpu.models.generation import build_llama_decoder
    cfg, params = model
    prompt = rng.integers(0, cfg.vocab_size, (20,)).astype(np.int32)
    eng = ContinuousBatchingEngine(cfg, params, max_batch=1,
                                   block_size=8, num_blocks=64)
    eng.add_request(prompt, 2)       # registers blocks 0..1 (16 tokens)
    eng.run_to_completion()
    # same prompt again: suffix fill runs the last 4 tokens only
    eng.add_request(prompt, 2)
    eng.step()
    assert eng.stats["prefix_blocks_reused"] >= 2
    req = next(r for r in eng.slots if r is not None)
    first_cached = req.out[0]
    prefill, _ = build_llama_decoder(cfg, 20, use_pallas=False)
    _, ref_logits = jax.jit(prefill)(params, prompt[None, :])
    assert first_cached == int(np.asarray(jnp.argmax(ref_logits, -1))[0])


def test_prefix_index_evicts_under_pressure(model):
    """A full index must LRU-evict to admit new work rather than wedge."""
    cfg, params = model
    eng = ContinuousBatchingEngine(cfg, params, max_batch=1,
                                   block_size=8, num_blocks=6)
    outs = {}
    for i in range(4):
        p = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
        rid = eng.add_request(p, 3)
        outs.update(eng.run_to_completion())
        assert rid in outs
    assert eng.alloc.free_blocks + len(eng.prefix_index) > 0


def test_sampled_requests_independent_of_batch(model):
    """A sampled request (per-slot PRNG folded by absolute position)
    produces the same tokens whether it runs alone or next to other
    requests — and different seeds diverge."""
    cfg, params = model
    prompt = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)

    def run(batchmates, seed):
        eng = ContinuousBatchingEngine(cfg, params, max_batch=2,
                                       block_size=8, num_blocks=64)
        rid = eng.add_request(prompt, 6, temperature=0.8, top_k=20,
                              seed=seed)
        for bp in batchmates:
            eng.add_request(bp, 4)
        return eng.run_to_completion()[rid]

    solo = run([], seed=7)
    mate = rng.integers(0, cfg.vocab_size, (9,)).astype(np.int32)
    shared = run([mate], seed=7)
    np.testing.assert_array_equal(solo, shared)
    other = run([], seed=8)
    assert not np.array_equal(solo, other)


def test_sampler_topk_filter_actually_filters(model):
    """top_k=2 with near-zero temperature must only ever emit one of the
    two highest-logit tokens (regression: a traced negative sort index
    clamps to 0 under jit and silently disables the filter)."""
    cfg, params = model
    eng = ContinuousBatchingEngine(cfg, params, max_batch=1,
                                   block_size=8, num_blocks=32)
    logits = np.full((cfg.vocab_size,), -10.0, np.float32)
    logits[5], logits[9] = 4.0, 3.9
    from paddle_tpu.inference.serving import GenRequest
    req = GenRequest(0, np.zeros(1, np.int32), 4, temperature=1.0,
                     top_k=2, seed=0)
    picks = {eng._pick_token(req, logits, position=p)
             for p in range(64)}
    assert picks <= {5, 9} and len(picks) == 2, picks


def test_topp_applies_after_topk(model):
    """HF sequential-warper semantics: top-p mass is computed over the
    top-k-FILTERED distribution.  With a dominant argmax, top_k=2 +
    top_p=0.9 must keep ONLY the argmax (over the raw distribution the
    cutoff would fall below both survivors and top-p would no-op)."""
    cfg, params = model
    eng = ContinuousBatchingEngine(cfg, params, max_batch=1,
                                   block_size=8, num_blocks=32)
    logits = np.zeros((cfg.vocab_size,), np.float32)
    # raw cum mass of token 5 is ~0.906 (< 0.95) but its top-2-filtered
    # mass is ~0.982 (>= 0.95): only the sequential-warper semantics
    # reduce the keep-set to {5}
    logits[5], logits[9] = 8.0, 4.0
    from paddle_tpu.inference.serving import GenRequest
    req = GenRequest(0, np.zeros(1, np.int32), 4, temperature=1.0,
                     top_k=2, top_p=0.95, seed=0)
    picks = {eng._pick_token(req, logits, position=p) for p in range(64)}
    assert picks == {5}, picks


def test_dynamic_rope_rejected_in_engine(model):
    cfg, params = model
    from paddle_tpu.models.llama import llama_tiny
    c = llama_tiny(rope_scaling={"rope_type": "dynamic", "factor": 2.0,
                                 "original_max_position_embeddings": 16})
    with pytest.raises(NotImplementedError, match="dynamic"):
        ContinuousBatchingEngine(c, params, max_batch=1)


def test_moe_engine_with_prefix_cache(model):
    """MoE serving + automatic prefix caching compose: the chunk fill
    runs the grouped-GEMM FFN over the suffix and outputs stay exact."""
    cfg = llama_tiny(moe_num_experts=4)
    topo = dist.init_topology(devices=jax.devices()[:1])
    _, init_fn = build_llama_train_step(cfg, topo, num_microbatches=1)
    params = init_fn(0)["params"]
    set_topology(HybridTopology())
    prefix = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    p1 = np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (4,))
                         .astype(np.int32)])
    eng = ContinuousBatchingEngine(cfg, params, max_batch=1,
                                   block_size=8, num_blocks=64)
    a = eng.add_request(p1, 4)
    res = eng.run_to_completion()
    b = eng.add_request(p1, 4)          # full prefix hit
    res.update(eng.run_to_completion())
    assert eng.stats["prefix_blocks_reused"] >= 2
    np.testing.assert_array_equal(res[a], res[b])
    cold = ContinuousBatchingEngine(cfg, params, max_batch=1,
                                    block_size=8, num_blocks=64,
                                    enable_prefix_caching=False)
    cold.add_request(p1, 4)
    want = list(cold.run_to_completion().values())[0]
    np.testing.assert_array_equal(res[b], want)


def _assert_pool_consistent(eng):
    """Full _RefPool invariant: every block is free XOR referenced, and
    each refcount equals (slots holding it) + (1 if prefix-indexed)."""
    held = {}
    for pages in eng.slot_pages:
        for p in pages:
            held[p] = held.get(p, 0) + 1
    for p in eng.prefix_index.values():
        held[p] = held.get(p, 0) + 1
    free = set(eng.alloc._free)
    for p, r in eng.alloc.ref.items():
        assert p not in free, f"block {p} free AND ref={r}"
        assert held.get(p, 0) == r, \
            f"block {p}: ref={r}, holders={held.get(p, 0)}"
    for p in held:
        assert p in eng.alloc.ref, f"block {p} held but unreferenced"
    assert len(free) + len(eng.alloc.ref) == eng.alloc.num_blocks
    rep = eng.kv_leak_report()
    assert rep["leaked"] == 0 and rep["unaccounted"] == 0, rep


def test_cancel_accounting_queued_phase(model):
    """ISSUE 7 regression: a WAITING request holds no page references —
    cancelling it must not touch the pool, and the invariant must hold
    through the subsequent drain."""
    cfg, params = model
    prefix = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    p1 = np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (3,))
                         .astype(np.int32)])
    p2 = np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (5,))
                         .astype(np.int32)])
    eng = ContinuousBatchingEngine(cfg, params, max_batch=1,
                                   block_size=8, num_blocks=8)
    a = eng.add_request(p1, 8)
    b = eng.add_request(p2, 8)           # queued: slot busy after step
    eng.step()
    free_before = eng.alloc.free_blocks
    refs_before = dict(eng.alloc.ref)
    assert eng.cancel(b)                 # waiting-queue phase
    assert eng.alloc.free_blocks == free_before
    assert eng.alloc.ref == refs_before  # untouched: no refs were held
    _assert_pool_consistent(eng)
    out = eng.run_to_completion()
    assert a in out and b not in out
    _assert_pool_consistent(eng)


def test_cancel_accounting_scheduled_phase_prefix_shared(model):
    """ISSUE 7 regression: cancelling a SCHEDULED request that reuses
    prefix-cached blocks must release each of its references exactly
    once — shared pages stay alive for the index (and other hitters),
    private pages return to the free list."""
    cfg, params = model
    prefix = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    p1 = np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (3,))
                         .astype(np.int32)])
    p2 = np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (5,))
                         .astype(np.int32)])
    eng = ContinuousBatchingEngine(cfg, params, max_batch=2,
                                   block_size=8, num_blocks=16)
    a = eng.add_request(p1, 6)
    eng.run_to_completion()              # indexes the 2 prefix blocks
    _assert_pool_consistent(eng)
    b = eng.add_request(p2, 6)           # admits via prefix-cache hit
    eng.step()
    assert eng.stats["prefix_blocks_reused"] >= 2
    shared = [eng.prefix_index[k] for k in eng.prefix_index]
    assert any(r >= 2 for p, r in eng.alloc.ref.items() if p in shared)
    assert eng.cancel(b)                 # scheduled phase, mid-stream
    _assert_pool_consistent(eng)
    # shared pages survive with exactly the index's reference
    for p in shared:
        assert eng.alloc.ref.get(p) == 1, eng.alloc.ref
    # the same prefix must still hit from the intact index
    c = eng.add_request(p2, 6)
    out = eng.run_to_completion()
    assert c in out
    _assert_pool_consistent(eng)


def test_refpool_double_free_raises(model):
    """The pool refuses accounting drift loudly: releasing or sharing a
    block with no live reference is a typed error, not silent KV
    corruption of whoever owns the re-handed-out page."""
    from paddle_tpu.inference.serving import _RefPool
    pool = _RefPool(4)
    got = pool.acquire(2)
    pool.release(got)
    with pytest.raises(RuntimeError, match="double free"):
        pool.release(got)
    with pytest.raises(RuntimeError, match="no live reference"):
        pool.share(got)
    # still serviceable after the failed calls
    assert pool.free_blocks == 4
    assert pool.acquire(4) is not None


def test_cancel_mid_speculation_accounting(model):
    """ISSUE 8 regression (extends the ISSUE 7 exactly-once suite): a
    speculating slot's KV contains rolled-back tail writes and shares
    prefix pages; cancelling it mid-speculation must satisfy the FULL
    pool invariant (each refcount == holders), keep the prefix index
    serving other requests, and leave the engine leak-free after
    drain."""
    from paddle_tpu.spec_decode import SpecDecodeConfig
    cfg, params = model
    prefix = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    p1 = np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (3,))
                         .astype(np.int32)])
    p2 = np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (5,))
                         .astype(np.int32)])
    eng = ContinuousBatchingEngine(
        cfg, params, max_batch=2, block_size=8, num_blocks=16,
        spec_config=SpecDecodeConfig(draft_cfg=cfg, draft_params=params,
                                     k=3, window=12))
    a = eng.add_request(p1, 6)
    eng.run_to_completion()              # indexes the 2 prefix blocks
    _assert_pool_consistent(eng)
    b = eng.add_request(p2, 24)          # admits via prefix-cache hit
    eng.step()
    eng.step()                           # speculating over shared pages
    assert eng.spec_stats()["spec_steps"] >= 1
    assert eng.stats["prefix_blocks_reused"] >= 2
    assert eng.cancel(b)                 # cancel MID-speculation
    _assert_pool_consistent(eng)
    c = eng.add_request(p2, 6)           # prefix index still serves
    out = eng.run_to_completion()
    assert c in out and b not in out
    _assert_pool_consistent(eng)


def test_prefill_crash_releases_pages_exactly_once(model):
    """ISSUE 11 engine hardening: a crash INSIDE the prefill — after
    the request's pages are mapped into the slot but before it goes
    live — must release those pages exactly once and keep the request
    waiting.  Covers the phase the queued/scheduled cancel regressions
    above cannot reach (the slot is half-built, so neither ``cancel``
    nor ``kv_leak_report`` can see its references)."""
    import faults
    cfg, params = model
    p = rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32)
    eng = ContinuousBatchingEngine(cfg, params, max_batch=2,
                                   block_size=8, num_blocks=16)
    a = eng.add_request(p, 6)
    free_before = eng.alloc.free_blocks
    with faults.crash_mid_prefill(eng) as stats:
        with pytest.raises(faults.InjectedEngineCrash):
            eng.step()
    assert stats["crashed"] == 1
    assert eng.alloc.free_blocks == free_before   # exactly-once release
    _assert_pool_consistent(eng)
    # the request is still WAITING: a retry (injector exhausted) runs
    # it to completion with the result an uninjected engine produces
    assert eng.queue and eng.queue[0].req_id == a
    cold = ContinuousBatchingEngine(cfg, params, max_batch=2,
                                    block_size=8, num_blocks=16)
    cold.add_request(p, 6)
    want = list(cold.run_to_completion().values())[0]
    res = eng.run_to_completion()
    np.testing.assert_array_equal(res[a], want)
    _assert_pool_consistent(eng)


def test_prefill_crash_with_prefix_shared_pages(model):
    """Same phase, nastier accounting: the crashed admission reused
    prefix-cached blocks (slot took extra references on shared pages).
    The release must drop exactly the slot's references — the index's
    stay live and keep serving later requests."""
    import faults
    cfg, params = model
    prefix = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    p1 = np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (3,))
                         .astype(np.int32)])
    p2 = np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (5,))
                         .astype(np.int32)])
    eng = ContinuousBatchingEngine(cfg, params, max_batch=2,
                                   block_size=8, num_blocks=16)
    eng.add_request(p1, 6)
    eng.run_to_completion()              # indexes the 2 prefix blocks
    _assert_pool_consistent(eng)
    shared = list(eng.prefix_index.values())
    b = eng.add_request(p2, 6)           # admits via prefix-cache hit
    with faults.crash_mid_prefill(eng):
        with pytest.raises(faults.InjectedEngineCrash):
            eng.step()
    _assert_pool_consistent(eng)
    for pg in shared:                    # index refs survived, exactly
        assert eng.alloc.ref.get(pg) == 1, eng.alloc.ref
    # cancel of the still-waiting request is the queued-phase path
    assert eng.cancel(b)
    _assert_pool_consistent(eng)
    c = eng.add_request(p2, 6)           # the intact index still hits
    out = eng.run_to_completion()
    assert c in out
    assert eng.stats["prefix_blocks_reused"] >= 2
    _assert_pool_consistent(eng)


def test_cancel_queued_and_active(model):
    cfg, params = model
    p = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
    eng = ContinuousBatchingEngine(cfg, params, max_batch=1,
                                   block_size=8, num_blocks=16)
    a = eng.add_request(p, 6)
    b = eng.add_request(p, 6)            # queued behind a
    eng.step()
    assert eng.cancel(b)                 # cancel while queued
    assert eng.cancel(a)                 # cancel while active
    assert not eng.cancel(a)             # idempotent-false
    assert eng.alloc.free_blocks + len(eng.prefix_index) >= 14
    c = eng.add_request(p, 3)            # engine still serves
    out = eng.run_to_completion()
    assert c in out and a not in out and b not in out
