"""The latent-attention family (GLM-4.7-Flash's ``glm4_moe_lite``: MLA in
every layer, a dense layer, then sigmoid-routed experts beside a shared
one; ``models/glm_moe_lite.py``, ``ops/mla.py``) through the
continuous-batching engine, against the plain reference of the benchmark
(``benchmark/reference/glm_moe_lite_ref.py``, which imports nothing of
``paddle_tpu`` and knows the expanded form only) and, where
``transformers`` has the block, against ``DeepseekV3ForCausalLM``.  Tiny
sizes, float32, CPU."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.programs import glm_moe_lite as prog
from benchmark.reference import glm_moe_lite_ref as ref
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.ops import mla
from paddle_tpu.ops.pallas import moe_grouped_matmul
from paddle_tpu.parallel import moe

SEED = 7
CONFIG = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32,
    n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
    n_group=1, topk_group=1, norm_topk_prob=True,
    routed_scaling_factor=1.8, rope_theta=1000000, rope_scaling=None,
    partial_rotary_factor=1, rms_norm_eps=1e-5, latent_norm_eps=1e-6,
    rope_interleave=True, router_bias_range=0.1,
    max_position_embeddings=512, vocab_size=256, torch_dtype="float32",
    num_nextn_predict_layers=0, initializer_range=0.02,
    reference="glm_moe_lite_ref", program="glm_moe_lite")

_ENGINES = {}


def _engine(config=CONFIG, **kw):
    """The tiny engine; without further arguments ONE engine for the
    whole file (its programs compile once; every test leaves it drained
    and reads its counters as differences)."""
    key = None if kw else ref._items(config)
    if key in _ENGINES:
        return _ENGINES[key]
    cfg = prog.program_config(config)
    kw = dict(dict(max_batch=3, block_size=4, num_blocks=64,
                   max_blocks_per_seq=16, prefill_buckets=(8, 16)), **kw)
    eng = ContinuousBatchingEngine(cfg, prog.make_params(config, SEED),
                                   **kw)
    if key is not None:
        _ENGINES[key] = eng
    return eng


def _stats(eng):
    return dict(eng.scheduler_stats(), decode_steps=eng.decode_steps,
                **eng.resilience)


def _since(eng, before):
    return {k: v - before[k] for k, v in _stats(eng).items()
            if isinstance(v, int)}


def _ref_logits(seq, config=CONFIG, pad_to=64):
    ids = np.zeros((1, pad_to), np.int32)
    ids[0, :len(seq)] = seq
    return np.asarray(ref.reference_logits(config, SEED, ids,
                                           "float32"))[0, :len(seq)]


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).astype(np.int32) for n in lengths]


# ---------------------------------------------------------------------
# the ops
# ---------------------------------------------------------------------
def _layer(T=21, seed=1):
    """One attention layer's weights in the program's layout, and the
    projections of ``T`` random rows."""
    cfg = prog.program_config(CONFIG)
    from paddle_tpu.models.glm_moe_lite import mla_spec
    spec = mla_spec(cfg, 4)
    lp = prog.program_layer(CONFIG, ref.layer_weights(
        CONFIG, ref.seed_key(seed), 0, jnp.float32))
    # gains off one, so that a norm left out would show
    lp["q_a_ln_w"] = 1 + 0.1 * jnp.arange(24.0) / 24
    lp["kv_a_ln_w"] = 1 - 0.1 * jnp.arange(16.0) / 16
    y = jax.random.normal(jax.random.key(seed), (T, 64))
    return spec, lp, _project(y, lp, jnp.arange(T), spec)


# jitted: one compile a shape instead of one an operation
_project = jax.jit(mla.project, static_argnums=3)
_expanded = jax.jit(mla.expanded_attention, static_argnums=6)
_absorbed = jax.jit(mla.absorbed_attention, static_argnums=6)


def test_absorbed_equals_expanded():
    """One layer, the same numbers to float32 rounding: fold ``W_uk``
    into the query and ``W_uv`` after, or decompress the latent."""
    spec, lp, (q_n, q_r, latent) = _layer()
    T = latent.shape[0]
    mask = jnp.tril(jnp.ones((T, T), bool))
    a = _expanded(q_n, q_r, latent, lp["uk_w"], lp["uv_w"], mask,
                  spec.scale)
    b = _absorbed(q_n, q_r, latent, lp["uk_w"], lp["uv_w"], mask,
                  spec.scale)
    assert a.shape == (T, 4 * 32) and float(jnp.abs(a).max()) > 1e-3
    np.testing.assert_allclose(b, a, atol=2e-6 * float(jnp.abs(a).max()),
                               rtol=1e-5)


def _paged(latents, BS=4, MB=24, NB=128, seed=0):
    """``latents``: one ``[T_b, W]`` array a row -> a pool with the rows'
    pages scattered over it, their table and lengths."""
    rng = np.random.default_rng(seed)
    W = latents[0].shape[1]
    pool = np.asarray(rng.normal(size=(NB, BS, W)), np.float32)
    free = list(rng.permutation(NB - 1))        # the last page: nobody's
    bt = np.full((len(latents), MB), -1, np.int32)
    for b, lat in enumerate(latents):
        n = -(-len(lat) // BS)
        pages = [free.pop() for _ in range(n)]
        bt[b, :n] = pages
        pad = np.zeros((n * BS, W), np.float32)
        pad[:len(lat)] = lat
        pool[pages] = pad.reshape(n, BS, W)
    return jnp.asarray(pool), jnp.asarray(bt), jnp.asarray(
        [len(lat) for lat in latents], jnp.int32)


def test_paged_latent_walk_is_the_counted_walk(monkeypatch):
    """The decode walk over the latent pool against (a) the dense
    absorbed form and (b) ``paged_decode_attention`` handed the latent
    pool as key AND value pool at one KV head (the shape at which it
    could have been reused): the same numbers; pages past the walk's
    last trip are never read; one program whatever the lengths."""
    from paddle_tpu.ops.paged_kv import decode_walk, paged_decode_attention
    monkeypatch.setattr(mla, "WALK_POSITIONS", 16)     # several trips
    spec, lp, _ = _layer()
    rows = [_layer(T, seed=s)[2] for s, T in ((2, 37), (3, 9), (4, 0))]
    pool, bt, lengths = _paged([r[2] for r in rows])
    q = jnp.stack([mla.absorb_query(r[0][-1], r[1][-1], lp["uk_w"], 128)
                   if len(r[2]) else jnp.zeros((4, 128))
                   for r in rows])
    walk = jax.jit(lambda *a: mla.paged_latent_attention(
        *a, 16, spec.scale))
    got = walk(q, pool, bt, lengths)
    assert got.shape == (3, 4, 16) and bool(jnp.isfinite(got).all())
    for b, (q_n, q_r, lat) in enumerate(rows[:2]):
        s = jnp.einsum("hd,td->ht", q[b], lat) * spec.scale
        want = jax.nn.softmax(s, -1) @ lat[:, :16]
        np.testing.assert_allclose(got[b], want, atol=2e-6)
    old = paged_decode_attention(q, pool[:, :, None], pool[:, :, None],
                                 bt, lengths, scale=spec.scale)
    np.testing.assert_allclose(got[:2], old[:2, :, :16], atol=2e-6)
    # what lies past the last trip is never read
    trips, C = decode_walk(np.asarray(lengths), 24, 4, positions=16)
    assert (trips, C) == (3, 4) and trips * C > 37 // 4
    # (a NaN INSIDE the walked range would poison a row through 0 x NaN,
    # as in the other walk: only what lies past the last trip is free)
    far = walk(q, pool.at[127].set(jnp.nan),
               bt.at[:, trips * C:].set(127), lengths)
    np.testing.assert_array_equal(far, got)
    walk(q, pool, bt, jnp.asarray([5, 30, 2], jnp.int32))
    assert walk._cache_size() == 1


def test_fill_walk_is_the_expanded_form(monkeypatch):
    """A chunk fill's attention over the paged latent (a cached prefix,
    then the chunk up to each query; two trips) = the dense expanded
    form; the rows past ``last`` (a bucket's padding) stay finite."""
    monkeypatch.setattr(mla, "FILL_POSITIONS", 16)
    spec, lp, (q_n, q_r, latent) = _layer(T=29)
    pool, bt, _ = _paged([latent])
    start, Ts, valid = 13, 16, 11
    pos = start + jnp.arange(Ts)
    sel = slice(start, start + Ts)
    got = jax.jit(mla.paged_expanded_attention, static_argnums=8)(
        q_n[sel], q_r[sel], pool, bt[0], pos, start + valid - 1,
        lp["uk_w"], lp["uv_w"], spec.scale)
    want = _expanded(q_n, q_r, latent, lp["uk_w"], lp["uv_w"],
                     jnp.tril(jnp.ones((29, 29), bool)), spec.scale)[sel]
    np.testing.assert_allclose(got[:valid], want[:valid], atol=2e-6)
    assert bool(jnp.isfinite(got).all())


# ---------------------------------------------------------------------
# the gate and the expert layer's two forms
# ---------------------------------------------------------------------
def _routing(T=200, E=8, groups=1, seed=5):
    k = jax.random.split(jax.random.key(seed), 2)
    logits = 0.5 * jax.random.normal(k[0], (T, E))
    bias = jax.random.uniform(k[1], (E,), minval=-0.1, maxval=0.1)
    return logits, bias


def test_gate_bias_in_the_choice_only():
    logits, bias = _routing()
    s = jax.nn.sigmoid(logits)
    w, idx = moe.route_sigmoid(logits, bias, 2, scale=1.8)
    w0, idx0 = moe.route_sigmoid(logits, jnp.zeros_like(bias), 2,
                                 scale=1.8)
    # the choice is by s + b: it differs from the unbiased one for some
    # tokens (else the bias path would go untested), not for all
    differ = int((jnp.sort(idx, -1) != jnp.sort(idx0, -1)).any(-1).sum())
    assert 10 < differ < 190, differ
    np.testing.assert_array_equal(
        jnp.sort(idx, -1), jnp.sort(jax.lax.top_k(s + bias, 2)[1], -1))
    # the weights are the scores WITHOUT the bias, renormalised, x 1.8
    chosen = jnp.take_along_axis(s, idx, 1)
    np.testing.assert_allclose(
        w, 1.8 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 1.8, rtol=1e-6)
    # and without renormalisation the bare scores
    w1, _ = moe.route_sigmoid(logits, bias, 2, normalize=False)
    np.testing.assert_allclose(w1, chosen, rtol=1e-6)


@pytest.mark.parametrize("groups,kept", [(1, 1), (2, 1), (4, 2)])
def test_gate_equals_the_reference(groups, kept):
    """Groups of experts: only the ``topk_group`` groups whose two best
    ``s + b`` sum highest may be chosen from; against the reference's
    gate (and at one group the step is the identity)."""
    logits, bias = _routing()
    z = dict(ref.sizes(CONFIG), NG=groups, TG=kept)
    want_w, want_i = ref.gate(jax.nn.sigmoid(logits), bias, z)
    w, idx = moe.route_sigmoid(logits, bias, 2, n_group=groups,
                               topk_group=kept, scale=1.8)
    order, worder = jnp.argsort(idx, -1), jnp.argsort(want_i, -1)
    np.testing.assert_array_equal(jnp.take_along_axis(idx, order, 1),
                                  jnp.take_along_axis(want_i, worder, 1))
    np.testing.assert_allclose(jnp.take_along_axis(w, order, 1),
                               jnp.take_along_axis(want_w, worder, 1),
                               rtol=1e-6)
    if groups > 1:
        per = 8 // groups
        g = np.asarray(idx // per)
        assert all(len(set(r)) <= kept for r in g)
        _, free = moe.route_sigmoid(logits, bias, 2, scale=1.8)
        assert int((jnp.sort(free, -1) != jnp.sort(idx, -1)).any(-1).sum())


def _bank(E=8, H=64, F=32, seed=6, T=40):
    k = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(k[0], (T, H)),
            0.1 * jax.random.normal(k[1], (E, H, F)),
            0.1 * jax.random.normal(k[2], (E, H, F)),
            0.1 * jax.random.normal(k[3], (E, F, H)))


def _stacked(bank, n, layer):
    """``bank`` as layer ``layer`` of ``n``, the other layers NaN: an
    index into the wrong layer's experts shows."""
    return tuple(jnp.full((n,) + a.shape, jnp.nan).at[layer].set(a)
                 for a in bank)


def _even(T, E, k):
    return (jnp.arange(T)[:, None] * k + jnp.arange(k)[None]) % E


@pytest.mark.parametrize("case, T, layers, layer, choose", [
    ("even", 256, None, None, _even),
    ("one_expert_takes_every_row", 256, None, None,
     lambda T, E, k: jnp.full((T, k), 5)),
    ("experts_with_no_row", 256, None, None,
     lambda T, E, k: _even(T, 3, k) * 2),
    ("rows_no_multiple_of_the_tile", 301, None, None, None),
    ("first_layer_of_a_stack", 256, 3, 0, None),
    ("last_layer_of_a_stack", 256, 3, 2, None),
    ("a_steps_few_rows", 40, 3, 1, None),
    ("fewer_pairs_than_experts", 3, 3, 1, None),
])
def test_grouped_form_equals_the_loop_over_experts(case, T, layers, layer,
                                                   choose):
    """``moe_swiglu_ffn_routed`` = a loop over experts on the same
    tokens, for any routing (``choose``: the router's own where None):
    the grouped form (rows sorted by expert, each expert's rows in whole
    tiles, ``moe_grouped_matmul`` over the tiles that hold a row; the
    kernel interpreted here), for a chunk fill's rows and a decode
    step's few alike.  A bank inside a stack is found by index: the
    other layers' weights are NaN."""
    E, k = 8, 2
    x, *bank = _bank(T=T)
    logits, bias = _routing(T=T)
    w, idx = moe.route_sigmoid(logits, bias, k, scale=1.8)
    if choose is not None:
        idx = choose(T, E, k).astype(jnp.int32)
    wg, wu, wd = bank
    want = sum(
        jnp.where(idx == e, w, 0.0).sum(-1)[:, None]
        * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
        for e in range(E))
    if layers is not None:
        bank = _stacked(bank, layers, layer)
    fn = lambda *a: moe.moe_swiglu_ffn_routed(
        *a, layer=None if layer is None else jnp.int32(layer))
    got, rows = jax.jit(fn)(x, w, idx, *bank)
    np.testing.assert_allclose(got, want,
                               atol=1e-5 * float(jnp.abs(want).max()))
    assert str(jax.make_jaxpr(fn)(x, w, idx, *bank)).count(
        "pallas_call") == 2
    # whole tiles of each expert's rows: never fewer rows than pairs,
    # never a tile more an expert than its rows need, and none for an
    # expert no row chose
    tm = moe_grouped_matmul.grouped_tiles(T * k, E, 64, 32, 2, 4)[0]
    load = np.bincount(np.asarray(idx).reshape(-1), minlength=E)
    assert int(rows) == int((-(-load // tm) * tm).sum())
    assert T * k <= int(rows) < T * k + min(E, T * k) * tm


def test_grouped_tiles_follow_the_shape():
    """The cell's two chunks at GLM-4.7-Flash's widths: a row tile the
    height of an expert's even share (at most the MXU's 128), column
    blocks whose double-buffered weights fit the kernel's budget."""
    tiles = moe_grouped_matmul.grouped_tiles
    assert tiles(2048 * 4, 64, 2048, 1536, 2, 2) == (128, 512)
    assert tiles(2048 * 4, 64, 1536, 2048, 1, 2) == (128, 1024)
    assert tiles(512 * 4, 64, 2048, 1536, 2, 2) == (32, 512)
    assert tiles(256 * 2, 8, 64, 32, 2, 4) == (64, 32)     # toy widths
    assert tiles(3, 8, 64, 32, 1, 4) == (8, 32)            # a sublane pack


def test_expert_counts():
    idx = jnp.asarray([[0, 1], [0, 2], [0, 3], [5, 6]])
    np.testing.assert_array_equal(moe.expert_counts(idx, 8), [8, 6, 3])
    np.testing.assert_array_equal(
        moe.expert_counts(idx, 8, jnp.asarray([True, True, False, False])),
        [4, 3, 2])


# ---------------------------------------------------------------------
# the engine against the reference
# ---------------------------------------------------------------------
def _serve(eng, prompts, news):
    """Run to completion, keeping the logits every token was picked
    from: ``{rid: (ids, [logits a served token])}``."""
    logs = {}
    pick, append = eng._pick_token, eng._append_tok

    def spy_pick(req, logits, position):         # the prefill's token
        logs[req.req_id] = [np.asarray(logits)]
        return pick(req, logits, position)

    def spy_append(req, tok):                    # every token
        if req.out:                              # a decode step's
            slot = next(s for s in range(eng.B) if eng.slots[s] is req)
            logs[req.req_id].append(eng.last_logits[slot].copy())
        append(req, tok)

    eng._pick_token, eng._append_tok = spy_pick, spy_append
    try:
        rids = [eng.add_request(p, n) for p, n in zip(prompts, news)]
        out = eng.run_to_completion()
    finally:
        del eng._pick_token, eng._append_tok
    return {r: (out[r], logs[r]) for r in rids}


def test_the_programs_tree_is_the_references_draw():
    """The program holds what the reference draws, ``kv_b_w`` cut per
    head into ``uk_w`` and ``uv_w``."""
    params = prog.make_params(CONFIG, SEED)
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == ref.param_count(CONFIG)
    dense, expert = params["runs"]
    assert dense["gate_w"].shape == (1, 64, 128)
    assert expert["e_gate"].shape == (2, 8, 64, 32)
    assert expert["router_b"].shape == (2, 8)
    assert float(jnp.abs(expert["router_b"]).max()) <= 0.1 > 0.05
    w = ref.layer_weights(CONFIG, ref.seed_key(SEED), 1, jnp.float32)
    kvb = np.asarray(w["kv_b_w"]).reshape(16, 4, 24 + 32)
    np.testing.assert_allclose(expert["uk_w"][0, 2], kvb[:, 2, :24],
                               rtol=1e-6)
    np.testing.assert_allclose(expert["uv_w"][0, 3], kvb[:, 3, 24:],
                               rtol=1e-6)


def test_served_logits_match_the_reference():
    """Prefill in chunks through the latent pool (the expanded form),
    then decoding over it (the absorbed form), for slots at different
    lengths with admissions and retirements in between (5 requests
    through 3 slots: a reused slot and a reused page start clean) = the
    reference's full forward, which knows neither a cache nor the
    absorbed form; logits compared."""
    eng = _engine()
    prompts = _prompts((5, 19, 8, 33, 3))
    news = (6, 4, 9, 5, 7)
    served = _serve(eng, prompts, news)
    for (seq, logits), prompt, new in zip(served.values(), prompts, news):
        T0 = len(prompt)
        assert len(seq) == T0 + new
        want = _ref_logits(seq)[T0 - 1:len(seq) - 1]
        got = np.stack(logits[:new])
        scale = np.abs(want).max()
        np.testing.assert_allclose(got, want, atol=2e-4 * scale)
        assert (want.argmax(-1) == seq[T0:]).all()
    leak = eng.kv_leak_report()
    assert leak["leaked"] == leak["unaccounted"] == 0
    # one pool of one vector a token for all 3 layers, no value pool
    # (16 + 8 values a token, in whole lanes of 128)
    assert eng.pool_k.shape == (3, 64, 4, 128) and eng.pool_v is None
    assert eng._carry == ("pool_k",)
    assert eng._blocks_needed(9) == 3


def test_counters_and_prefix_cache():
    eng = _engine()
    before = _stats(eng)
    hits0 = eng.prefix_stats()["hits"]
    p = _prompts((14,), seed=3)[0]
    outs = []
    for _ in range(2):                      # the same prompt twice
        rid = eng.add_request(p, 5)
        outs.append(eng.run_to_completion()[rid])
    s = _since(eng, before)
    k, layers = CONFIG["num_experts_per_tok"], 2       # expert layers
    # every expert is held: every choice is local, live rows only
    assert s["moe_assignments_total"] == s["decode_slot_steps"] * k * layers
    assert s["moe_assignments_local"] == s["moe_assignments_total"]
    assert s["moe_expert_slots"] == s["decode_steps"] * 8 * layers
    assert 0 < s["moe_experts_hit"] <= s["moe_expert_slots"]
    # the busiest expert of a layer: at least an even share of its
    # pairs, at most one pair a live row
    assert s["moe_assignments_total"] / 8 <= s["moe_peak_load"] \
        <= s["decode_slot_steps"] * layers
    # the rows the steps' expert matmuls multiplied: whole tiles of 8 of
    # all 3 slots' pairs (an idle slot's row is routed too), at most a
    # tile a pair
    assert s["moe_assignments_local"] <= s["moe_step_rows"] \
        <= s["decode_steps"] * layers * 3 * k * 8
    assert s["moe_step_rows"] % 8 == 0
    assert s["decode_pages_walked"] >= s["decode_pages_live"] > 0
    # a latent page is position-absolute like a K/V page: the second
    # request reuses the first one's full pages and serves the same
    ps = eng.prefix_stats()
    assert ps["enabled"] is True and ps["hits"] - hits0 == 1
    np.testing.assert_array_equal(outs[0], outs[1])
    assert eng.kv_leak_report()["leaked"] == 0


def test_a_wide_bucket_fills_through_the_grouped_form():
    """The file's other engines fill in buckets of (8, 16).  A bucket
    of 256 takes the same grouped form at taller tiles (the kernel
    interpreted): a prompt of four chunks (256, 16, 16, 12 in 16) serves
    what the float32 reference computes, and the fills' counts say what
    was multiplied: every dispatched row routed (padded rows too), at
    least a row a pair."""
    eng = _engine(prefill_buckets=(16, 256), max_batch=2, num_blocks=96,
                  max_blocks_per_seq=80)
    before = _stats(eng)
    prompt, new = _prompts((300,), seed=4)[0], 4
    (seq, logits), = _serve(eng, [prompt], [new]).values()
    want = _ref_logits(seq, pad_to=320)[len(prompt) - 1:len(seq) - 1]
    np.testing.assert_allclose(np.stack(logits[:new]), want,
                               atol=2e-4 * np.abs(want).max())
    assert (want.argmax(-1) == seq[len(prompt):]).all()
    s = _since(eng, before)
    k, layers = CONFIG["num_experts_per_tok"], 2       # expert layers
    assert s["prefill_chunks"] == 4
    assert s["prefill_tokens_dispatched"] == 256 + 3 * 16
    assert s["moe_fill_pairs"] == s["prefill_tokens_dispatched"] * k * layers
    # whole tiles of an expert's rows, at most one more an expert than
    # its rows fill: tiles of 64 rows in the 256 chunk, of 8 in the
    # three 16-row chunks (never the 16 x 8 of every row by every expert)
    assert s["moe_fill_pairs"] <= s["moe_fill_rows"] \
        < s["moe_fill_pairs"] + 8 * (64 + 3 * 8) * layers
    assert s["moe_fill_rows"] < (256 * k + 8 * 64 + 3 * 16 * 8) * layers
    assert eng.kv_leak_report()["leaked"] == 0


def test_the_fill_slack_metric_reads_the_two_counters():
    """``benchmark/metrics/moe_fill_slack.glm47flash.json``: rows over
    pairs of the window's counters, as ``scheduler_stats()`` names them;
    a program without them (the parent commit) leaves the metric out of
    the line."""
    from benchmark.lib import cell, model
    (m,) = [m for m in cell.load_metrics("glm47flash-long", model.HERE)
            if m["name"] == "moe_fill_slack.glm47flash"]
    assert {m["numerator"], m["denominator"]} <= set(
        _engine().scheduler_stats())
    got = cell.reduce_metrics([m], {"counters": {
        "moe_fill_rows": 6 * (8192 + 4096), "moe_fill_pairs": 6 * 8192}})
    assert got == {"moe_fill_slack.glm47flash":
                   {"value": 1.5, "unit": "rows/pair"}}
    assert cell.reduce_metrics([m], {"counters": {"decode_steps": 3}}) == {}


def test_greedy_rows_are_picked_on_the_device():
    eng = _engine()
    eng.add_request(_prompts((9,))[0], 6)
    eng.step()
    eng.step()
    assert not isinstance(eng._last_logits, np.ndarray)
    picked = eng.slots[0].out[-1]
    assert eng.last_logits.shape == (3, 256)
    assert int(eng.last_logits[0].argmax()) == picked
    eng.run_to_completion()


def test_preempted_stream_resumes_bit_identical():
    """A preempted slot's latent pages leave and come back through the
    two fixed-width page programs (no value pages, the pool never on
    the host), and the stream decodes the same tokens — as does one
    whose snapshot is gone (replay from the committed tokens)."""
    from paddle_tpu.observability.tracing import TRACER
    prompts, news = _prompts((11, 7), seed=2), (10, 10)
    eng = _engine()

    def run(disturb):
        rids = [eng.add_request(p, n) for p, n in zip(prompts, news)]
        out, steps = {}, 0
        while eng.queue or eng.finished or eng.active_requests:
            out.update(eng.step())
            steps += 1
            if steps == 4:
                disturb(eng)
        assert eng.kv_leak_report()["leaked"] == 0
        return [out[r] for r in rids]

    plain = run(lambda eng: None)

    def preempt(eng):
        slot = next(s for s in range(eng.B) if eng.slots[s] is not None)
        used = -(-int(eng.lengths[slot]) // 4)
        snap = eng._spill.get(eng.preempt(slot))
        assert snap.k_pages.shape == (3, used, 4, 128)
        assert snap.v_pages.shape == (3, used, 4, 0)
        assert eng.spill_compatible(snap)
        snap.verify()

    before = _stats(eng)
    TRACER.reset()
    TRACER.enable()
    try:
        resumed = run(preempt)
        spans = {s.name for it in TRACER.timeline().iterations()
                 for s in it.spans}
    finally:
        TRACER.disable()
        TRACER.reset()
    assert "kv_restore" in spans          # the pool's host work, named
    assert _since(eng, before)["restores"] == 1
    for a, b in zip(plain, resumed):
        np.testing.assert_array_equal(a, b)

    def preempt_and_drop(eng):
        slot = next(s for s in range(eng.B) if eng.slots[s] is not None)
        del eng._spill[eng.preempt(slot)]

    before = _stats(eng)
    replayed = run(preempt_and_drop)
    assert _since(eng, before)["prefix_replays"] == 1
    for a, b in zip(plain, replayed):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("what", ["spec_config", "quant_config", "aot_dir",
                                  "prefix_cache_config"])
def test_refused_loudly_for_a_latent_cache(what, tmp_path):
    from paddle_tpu.serving.prefix_cache import PrefixCacheConfig
    kw = {"spec_config": object(), "quant_config": object(),
          "aot_dir": str(tmp_path),
          "prefix_cache_config": PrefixCacheConfig(
              offload_capacity_bytes=1 << 20)}
    with pytest.raises(NotImplementedError, match=what):
        _engine(**{what: kw[what]})


def test_aot_export_refuses_a_latent_cache(tmp_path):
    from paddle_tpu.aot import export_engine
    with pytest.raises(NotImplementedError, match="latent"):
        export_engine(_engine(), str(tmp_path))


def test_http_cli_builds_the_tiny_model():
    """``python -m paddle_tpu.serving.http --model glm_moe_lite_tiny``
    serves through ``build_frontend`` -> ``ServingFrontend`` ->
    ``ContinuousBatchingEngine``."""
    from paddle_tpu.serving import http
    args = http.parse_args(["--model", "glm_moe_lite_tiny",
                            "--num-layers", "2", "--max-batch", "2",
                            "--num-blocks", "32"])
    fe = http.build_frontend(args)
    assert type(fe.engine) is ContinuousBatchingEngine
    assert fe.engine.pool_k.shape[0] == 2 and fe.engine.pool_v is None
    h = fe.submit(_prompts((6,))[0], 5)
    fe.run_until_drained(timeout_s=120)
    assert h.state.name == "FINISHED" and len(h.tokens()) == 5
    assert fe.engine.kernel_tiers() == {}


# ---------------------------------------------------------------------
# the reference against the public implementation
# ---------------------------------------------------------------------
def test_reference_matches_transformers(monkeypatch):
    """``glm4_moe_lite`` is not in transformers 4.57; its block is
    DeepseekV3's, key for key: the reference against
    ``DeepseekV3ForCausalLM`` on the same weights (``rope_interleave``
    as assumed, the inner norms at that implementation's eps)."""
    monkeypatch.setenv("USE_TF", "0")
    monkeypatch.setenv("USE_FLAX", "0")
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip(
        "transformers.models.deepseek_v3.modeling_deepseek_v3")
    from transformers import DeepseekV3Config
    keys = ("hidden_size intermediate_size moe_intermediate_size "
            "num_hidden_layers first_k_dense_replace num_attention_heads "
            "num_key_value_heads q_lora_rank kv_lora_rank "
            "qk_nope_head_dim qk_rope_head_dim v_head_dim "
            "n_routed_experts n_shared_experts num_experts_per_tok "
            "n_group topk_group norm_topk_prob routed_scaling_factor "
            "rope_theta rms_norm_eps max_position_embeddings vocab_size "
            "rope_interleave").split()
    hcfg = DeepseekV3Config(
        **{k: CONFIG[k] for k in keys}, rope_scaling=None,
        tie_word_embeddings=False, attention_bias=False,
        hidden_act="silu", attn_implementation="eager")
    net = hf.DeepseekV3ForCausalLM(hcfg).eval()
    key = ref.seed_key(SEED)
    t = lambda a: torch.tensor(np.asarray(a, np.float32))
    outer = ref.outer_weights(CONFIG, key, jnp.float32)
    sd = {"model.embed_tokens.weight": t(outer["wte"]),
          "lm_head.weight": t(outer["head"].T),
          "model.norm.weight": t(outer["lnf_w"])}
    names = {"q_a_w": "self_attn.q_a_proj", "q_b_w": "self_attn.q_b_proj",
             "kv_a_w": "self_attn.kv_a_proj_with_mqa",
             "kv_b_w": "self_attn.kv_b_proj", "o_w": "self_attn.o_proj",
             "gate_w": "mlp.gate_proj", "up_w": "mlp.up_proj",
             "down_w": "mlp.down_proj", "router_w": "mlp.gate",
             "s_gate": "mlp.shared_experts.gate_proj",
             "s_up": "mlp.shared_experts.up_proj",
             "s_down": "mlp.shared_experts.down_proj"}
    norms = {"ln1_w": "input_layernorm", "ln2_w": "post_attention_layernorm",
             "q_a_ln_w": "self_attn.q_a_layernorm",
             "kv_a_ln_w": "self_attn.kv_a_layernorm"}
    for i in range(CONFIG["num_hidden_layers"]):
        w = ref.layer_weights(CONFIG, key, i, jnp.float32)
        p = f"model.layers.{i}."
        for n, v in w.items():
            if n in names:
                sd[p + names[n] + ".weight"] = t(v.T)
            elif n in norms:
                sd[p + norms[n] + ".weight"] = t(v)
            elif n == "router_b":
                sd[p + "mlp.gate.e_score_correction_bias"] = t(v)
            else:
                part = {"e_gate": "gate_proj", "e_up": "up_proj",
                        "e_down": "down_proj"}[n]
                for e in range(CONFIG["n_routed_experts"]):
                    sd[p + f"mlp.experts.{e}.{part}.weight"] = t(v[e].T)
    missing, unexpected = net.load_state_dict(sd, strict=False)
    assert not unexpected and not [m for m in missing
                                   if "rotary" not in m], (missing,
                                                           unexpected)
    ids = _prompts((23,), seed=4)[0]
    with torch.no_grad():
        want = net(torch.tensor(ids[None].astype(np.int64))).logits[0]
    got = _ref_logits(ids)
    np.testing.assert_allclose(got, want.numpy(),
                               atol=2e-5 * float(want.abs().max()))
