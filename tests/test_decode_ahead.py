"""Decode steps launched ahead (``ContinuousBatchingEngine._iterate``):
every family's step picks its greedy row on the device, and where no row
samples, step n+1 goes to the device on step n's greedy row before step
n is fetched.  Held to the same engine stepped in the synchronous order
(launch, consume: forced here by patching ``_runs_ahead``, never by an
option) for the four families, across EOS, budget ends, re-admission,
cancel, deadline expiry, a sampling row joining and the drain at the
end; and, for the Llama family alone, a prefix-cache hit and a priority
preemption with a step in flight.  Tiny sizes, float32, CPU: the tiny
engines of the families' own tests."""

import jax
import numpy as np
import pytest

import test_glm_moe_lite as glm
import test_granite_hybrid as granite
import test_ling_linear as ling
from paddle_tpu.inference import serving
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.serving import ServingFrontend


def _drawn(mod, config, scale=1.0):
    """A family's tiny configuration and its weights, drawn by the
    benchmark's program module."""
    def make():
        params = jax.tree_util.tree_map(
            lambda x: x * scale, mod.prog.make_params(config, mod.SEED))
        return mod.prog.program_config(config), params
    return make


def _llama():
    from paddle_tpu import parallel as dist
    from paddle_tpu.models.llama import build_llama_train_step, llama_tiny
    from paddle_tpu.parallel.topology import HybridTopology, set_topology
    cfg = llama_tiny()
    topo = dist.init_topology(devices=jax.devices()[:1])
    _, init_fn = build_llama_train_step(cfg, topo, num_microbatches=1)
    params = jax.tree_util.tree_map(lambda x: x * 4.0,
                                    init_fn(0)["params"])
    set_topology(HybridTopology())
    return cfg, params


#: family: its configuration and weights.  The tiny hybrid's draw
#: repeats one token an answer, the tiny Llama's runs of one; their
#: weights times 8 and 4 give answers that change token by token, so
#: that an EOS can fall mid-answer and a token fed twice shows
FAMILIES = {"granite": _drawn(granite, granite.CONFIG, 8.0),
            "glm": _drawn(glm, glm.CONFIG),
            "ling": _drawn(ling, ling.SHARE),
            "llama": _llama}

_ENGINES = {}


def _fresh(family, **kw):
    cfg, params = FAMILIES[family]()
    return ContinuousBatchingEngine(
        cfg, params, max_batch=3, block_size=4, num_blocks=64,
        max_blocks_per_seq=16, prefill_buckets=(8, 16), **kw)


def _engine(family):
    """ONE engine a family for the file (the programs compile once;
    every run leaves it drained).  No prefix cache: the second order
    would hit the first's prompts."""
    if family not in _ENGINES:
        _ENGINES[family] = _fresh(family, enable_prefix_caching=False)
    return _ENGINES[family]


def _prompts(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).astype(np.int32) for n in lengths]


def _drained(eng):
    assert eng._inflight is None
    leak = eng.kv_leak_report()
    assert leak["leaked"] == leak["unaccounted"] == leak["slot_blocks"] == 0
    assert leak.get("state_rows", 0) == 0 and not eng.queue


def _serve(eng, reqs, at=(), steps=None):
    """Serve ``reqs`` (prompt, budget, add_request keywords), doing
    ``at[k](eng, rids)`` after step ``k``; after ``steps`` steps, if
    given, ``run_to_completion`` drains the rest.  The answers in
    request order (None for one that never finished)."""
    at = dict(at)
    rids = [eng.add_request(p, n, **kw) for p, n, kw in reqs]
    out, k = {}, 0
    while (eng.queue or eng.finished or eng.active_requests) \
            and k != steps:
        out.update(eng.step())
        k += 1
        if k in at:
            at[k](eng, rids)
    out.update(eng.run_to_completion())
    _drained(eng)
    return [None if r not in out else out[r].tolist() for r in rids]


def _through_frontend(eng, reqs, deadline_of, expire_after):
    """The frontend over the engine, on a clock that advances a second a
    step: request ``deadline_of`` expires after ``expire_after``
    steps.  Every handle's streamed tokens and final state."""
    now = [0.0]
    fe = ServingFrontend(eng, clock=lambda: now[0])
    hs = [fe.submit(p, n, deadline_s=expire_after - 0.5
                    if i == deadline_of else None)
          for i, (p, n, _) in enumerate(reqs)]
    while fe.step():
        now[0] += 1.0
    _drained(eng)
    return [(h.state.name, h.tokens()) for h in hs]


# each case: what to serve on the engine, as a run that can be repeated
def _batch(eng):
    # five requests through three slots: two admissions into freed slots
    reqs = [(p, n, {}) for p, n in zip(_prompts((5, 19, 8, 33, 3)),
                                      (6, 4, 9, 5, 7))]
    return lambda: _serve(eng, reqs)


def _eos(eng):
    """An EOS that first appears mid-answer of the first request, which
    then frees its slot for the fourth while its extra step is in
    flight: the token of a row its slot no longer holds is dropped."""
    prompts = _prompts((7, 12, 5, 9), seed=4)
    plain = _serve(eng, [(prompts[0], 10, {})])[0][len(prompts[0]):]
    k = next(i for i in range(2, len(plain)) if plain[i] not in plain[:i])
    reqs = [(p, 10, {"eos_token_id": plain[k]}) for p in prompts]
    return lambda: _serve(eng, reqs)


def _budget(eng):
    # a one-token budget (the prefill's token ends it) and rows whose
    # budget ends with the step in flight
    reqs = [(p, n, {}) for p, n in zip(_prompts((6, 9, 4, 11, 3)),
                                      (1, 2, 3, 1, 2))]
    return lambda: _serve(eng, reqs)


def _readmit(eng):
    # slot 0 frees after two tokens and takes the waiting fourth
    reqs = [(p, n, {}) for p, n in zip(_prompts((5, 9, 7, 6)),
                                      (2, 9, 9, 5))]
    return lambda: _serve(eng, reqs)


def _cancel(eng):
    def cancel(eng, rids):
        assert eng.cancel(rids[1])
    reqs = [(p, 8, {}) for p in _prompts((5, 9, 7))]
    return lambda: _serve(eng, reqs, at={3: cancel})


def _sampling(eng):
    # a sampled request joins after two steps: the engine falls back to
    # the synchronous order while it lives (sampling is keyed by seed
    # and position, so both orders draw the same tokens)
    p = _prompts((6,), seed=9)[0]

    def join(eng, rids):
        rids.append(eng.add_request(p, 4, temperature=0.8, seed=5))
    reqs = [(q, 9, {}) for q in _prompts((5, 9))]
    return lambda: _serve(eng, reqs, at={2: join})


def _drain(eng):
    # stepped three times, then run_to_completion takes what is in flight
    reqs = [(p, 7, {}) for p in _prompts((5, 9, 7))]
    return lambda: _serve(eng, reqs, steps=3)


def _deadline(eng):
    # the second request's deadline passes with a step in flight
    reqs = [(p, 8, {}) for p in _prompts((5, 9, 7))]
    return lambda: _through_frontend(eng, reqs, 1, 3)


CASES = {"batch": _batch, "eos": _eos, "budget": _budget,
         "readmit": _readmit, "cancel": _cancel, "sampling": _sampling,
         "drain": _drain, "deadline": _deadline}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("family", list(FAMILIES))
def test_ahead_equals_the_synchronous_order(family, case, monkeypatch):
    eng = _engine(family)
    run = CASES[case](eng)
    # the launches fed by the step in flight
    fed, take = [], serving._ahead_tokens
    monkeypatch.setattr(serving, "_ahead_tokens",
                        lambda *a: fed.append(1) or take(*a))
    steps, ahead = eng.decode_steps, eng.stats["decode_ahead_steps"]
    got = run()
    steps, ahead = (eng.decode_steps - steps,
                    eng.stats["decode_ahead_steps"] - ahead)
    assert fed and 0 <= ahead <= steps
    with monkeypatch.context() as m:
        m.setattr(eng, "_runs_ahead", lambda: False)
        n, fed[:] = eng.stats["decode_ahead_steps"], []
        want = run()
        assert eng.stats["decode_ahead_steps"] == n and not fed
    assert got == want
    if case == "deadline":
        assert [s for s, _ in got] == ["FINISHED", "TIMED_OUT", "FINISHED"]
    if case == "cancel":
        assert got[1] is None and None not in (got[0], got[2])


def _prefix_hit(eng):
    """A request whose prompt starts with the first's 12 tokens (three
    whole pages, cached at its admission) arrives after two steps, so it
    is admitted onto the cached pages while a step is in flight."""
    first, tail = _prompts((14, 5), seed=11)

    def join(eng, rids):
        rids.append(eng.add_request(
            np.concatenate([first[:12], tail]), 6))
    return lambda: _serve(eng, [(first, 9, {}), (tail, 7, {})],
                          at={2: join})


def _preempt(eng):
    """Three rows fill the three slots; a request of a higher priority
    arrives after two steps and the admission spills the shortest row's
    pages with a step in flight (``preempt`` drains it), then restores
    them when a slot frees."""
    p = _prompts((6,), seed=12)[0]

    def join(eng, rids):
        rids.append(eng.add_request(p, 4, priority=5))
    reqs = [(q, n, {}) for q, n in zip(_prompts((5, 9, 7), seed=13),
                                       (10, 8, 9))]
    return lambda: _serve(eng, reqs, at={2: join})


@pytest.mark.parametrize("family", list(FAMILIES))
def test_one_decode_step_compile(family, monkeypatch):
    """The step fed the host's rows and the step fed the greedy row in
    flight are ONE program: the host's rows are uploaded with the
    placement the step's outputs have, committed weights (the Llama
    family's, on the train step's mesh) or not."""
    eng = _engine(family)
    fed, take = [], serving._ahead_tokens
    monkeypatch.setattr(serving, "_ahead_tokens",
                        lambda *a: fed.append(1) or take(*a))
    _batch(eng)()
    assert fed
    committed = jax.tree_util.tree_leaves(eng.params)[0].committed
    assert committed == (family == "llama")
    assert eng._step._cache_size() == 1


LLAMA_CASES = {"prefix_hit": (_prefix_hit, {}),
               "preempt": (_preempt, {"enable_prefix_caching": False})}


@pytest.mark.parametrize("case", list(LLAMA_CASES))
def test_llama_cache_and_preemption_with_a_step_in_flight(case,
                                                           monkeypatch):
    """The Llama family is the one that runs ahead with the prefix
    cache and priority preemption on: a page freed or spilled while a
    step is in flight is rewritten only by programs queued after that
    step.  A fresh engine an order (a second run on one engine would
    hit the first's cached prompts)."""
    make, kw = LLAMA_CASES[case]
    counts = {}
    for order in ("ahead", "sync"):
        eng = _fresh("llama", **kw)
        if order == "sync":
            monkeypatch.setattr(eng, "_runs_ahead", lambda: False)
        run = make(eng)
        inflight = []
        real_step = eng.step

        def step():
            # whether the admission of this iteration meets a step in
            # flight (the arrival comes after step 2)
            inflight.append(eng._inflight is not None)
            return real_step()
        monkeypatch.setattr(eng, "step", step)
        counts[order] = (run(), inflight[2],
                         eng.prefix_stats()["hits"],
                         dict(eng.resilience),
                         eng.stats["decode_ahead_steps"])
    (got, in_flight, hits, res, ahead), (want, sync_in_flight, hits_s,
                                         res_s, ahead_s) = \
        counts["ahead"], counts["sync"]
    assert got == want and None not in got
    assert in_flight and not sync_in_flight
    assert ahead > 0 and ahead_s == 0
    if case == "prefix_hit":
        assert hits == hits_s >= 1
    else:
        assert res["preemptions"] == res_s["preemptions"] >= 1
        assert res["restores"] == res_s["restores"] >= 1
