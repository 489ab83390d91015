"""The linear-attention family (Ling-3.0-flash's ``bailing_hybrid``: KDA
layers with a matrix state a head, one latent-attention layer in every
group, a dense layer, then group-limited sigmoid experts of which a rank
holds a share; ``models/ling_linear.py``, ``ops/kda.py``,
``ops/pallas/kda.py``) through the continuous-batching engine, against
the plain reference of the benchmark
(``benchmark/reference/ling_linear_ref.py``, which imports nothing of
``paddle_tpu`` and runs the recurrence a token at a time) and, where
``transformers`` has the algebra, against ``qwen3_next``'s recurrent
gated delta rule and ``DeepseekV3Attention``.  Tiny sizes, float32,
CPU."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.programs import ling_linear as prog
from benchmark.reference import ling_linear_ref as ref
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models import ling_linear as zoo
from paddle_tpu.ops import kda, mla, ssm
from paddle_tpu.ops.pallas.kda import kda_state_update_rows
from paddle_tpu.parallel import moe

SEED = 11
CONFIG = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=32, num_hidden_layers=4,
    first_k_dense_replace=1, layer_group_size=3, num_attention_heads=4,
    num_key_value_heads=4, num_kv_heads_for_linear_attn=0, head_dim=16,
    short_conv_kernel_size=4, kda_safe_gate=True, kda_lower_bound=-5,
    q_lora_rank=None, kv_lora_rank=16, qk_nope_head_dim=24,
    qk_rope_head_dim=8, rotary_dim=8, use_mla_nope=False, v_head_dim=16,
    num_experts=16, router_num_experts=16, expert_offset=0,
    num_experts_per_tok=2, n_group=4, topk_group=2, norm_topk_prob=True,
    routed_scaling_factor=2.5, rope_theta=6000000, rms_norm_eps=1e-6,
    rope_interleave=True, router_bias_range=0.1,
    expert_swiglu_limit_list=[0, 0, 0, 0, 4],
    share_expert_swiglu_limit_list=[0, 0, 0, 0, 5],
    max_position_embeddings=512, vocab_size=256, torch_dtype="float32",
    initializer_range=0.02, reference="ling_linear_ref",
    program="ling_linear")
#: this rank's share: experts 4..11 of the router's 16
SHARE = dict(CONFIG, num_experts=8, expert_offset=4)

_ENGINES = {}


def _engine(config=SHARE, **kw):
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


def _ref_logits(seq, config=SHARE, pad_to=64):
    ids = np.zeros((1, pad_to), np.int32)
    ids[0, :len(seq)] = seq
    return np.asarray(ref.reference_logits(config, SEED, ids,
                                           "float32"))[0, :len(seq)]


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).astype(np.int32) for n in lengths]


# ---------------------------------------------------------------------
# the recurrence's three forms
# ---------------------------------------------------------------------
def _draw(seed, B, T, nh, K, V):
    """Inputs as a layer makes them: ``q`` and ``k`` normed a head, the
    log decay a channel in (-5, 0), ``beta`` in (0, 1), a state that is
    not zero."""
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, nh, K))) * K ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, nh, K)))
    v = jax.random.normal(ks[2], (B, T, nh, V))
    log_a = -5.0 * jax.nn.sigmoid(
        2 * jax.random.normal(ks[3], (B, T, nh, K)) - 2)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, nh)))
    return q, k, v, log_a, beta, jax.random.normal(ks[5], (B, nh, K, V))


_scan = jax.jit(kda.kda_chunk_scan, static_argnames="chunk")
_loop = jax.jit(kda.kda_recurrence)


@pytest.mark.parametrize("T, chunk", [(70, None), (200, None), (20, 8),
                                      (5, None), (48, 32)])
def test_chunk_scan_equals_the_recurrence(T, chunk):
    """Chunks of 64 in sub-chunks of 16 (a length that is no multiple of
    either; a chunk shorter than a sub-chunk; one sub-chunk a chunk),
    the state handed from chunk to chunk and out."""
    args = _draw(0, 2, T, 3, 16, 24)
    o0, s0 = _loop(*args)
    o1, s1 = _scan(*args, chunk=chunk)
    np.testing.assert_allclose(o1, o0, atol=2e-6)
    np.testing.assert_allclose(s1, s0, atol=2e-5)


def test_chunk_sizes_follow_the_shape():
    assert kda.chunk_sizes(512) == (64, 16)
    assert kda.chunk_sizes(128) == (64, 16)
    assert kda.chunk_sizes(40) == (48, 16)      # whole sub-chunks
    assert kda.chunk_sizes(5) == (5, 5)
    with pytest.raises(ValueError, match="sub-chunks"):
        kda.kda_chunk_scan(*_draw(0, 1, 24, 1, 8, 8), chunk=24)


def test_the_floor_decay_over_whole_chunks_stays_finite():
    """A log decay of -5 on every channel (``kda_lower_bound``): 64
    tokens reach e^-320, which float32 does not hold; no cumulative
    decay is divided by, so nothing is inf or nan and the scan is still
    the recurrence."""
    q, k, v, log_a, beta, S = _draw(1, 1, 128, 2, 16, 16)
    log_a = jnp.full_like(log_a, -5.0)
    o0, s0 = _loop(q, k, v, log_a, beta, S)
    o1, s1 = _scan(q, k, v, log_a, beta, S)
    assert bool(jnp.isfinite(o1).all()) and bool(jnp.isfinite(s1).all())
    np.testing.assert_allclose(o1, o0, atol=2e-6)
    np.testing.assert_allclose(s1, s0, atol=2e-6)


def test_a_buckets_padding_leaves_the_state_alone():
    """The padding contract: ``log_a = 0`` and ``beta = 0`` past
    ``valid``, and the state after the bucket is the state after the
    last real token."""
    q, k, v, log_a, beta, S = _draw(2, 1, 32, 2, 16, 16)
    valid = 19
    real = jnp.arange(32) < valid
    o0, s0 = _loop(q[:, :valid], k[:, :valid], v[:, :valid],
                   log_a[:, :valid], beta[:, :valid], S)
    o1, s1 = _scan(q, k, v, jnp.where(real[None, :, None, None], log_a, 0),
                   jnp.where(real[None, :, None], beta, 0), S)
    np.testing.assert_allclose(o1[:, :valid], o0, atol=2e-6)
    np.testing.assert_allclose(s1, s0, atol=2e-6)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_state_update_row_steps_one_layers_rows(backend):
    """The decode form against one layer's row of the state array, per
    op and as the one-pass Pallas kernel (interpreted here): that row
    stepped once as the recurrence steps it, every other row as it
    was."""
    q, k, v, log_a, beta, S = _draw(3, 3, 1, 4, 16, 128)
    one = lambda a: a[:, 0]
    o0, s0 = _loop(q, k, v, log_a, beta, S)
    states = jnp.stack([S + 1, S, 2 * S])
    o1, st = jax.jit(kda.kda_state_update_row, static_argnames="backend")(
        one(q), one(k), one(v), one(log_a), one(beta), states,
        jnp.int32(1), backend=backend)
    np.testing.assert_allclose(o1, o0[:, 0], atol=2e-6)
    np.testing.assert_allclose(st[1], s0, atol=2e-6)
    np.testing.assert_array_equal(st[0], S + 1)
    np.testing.assert_array_equal(st[2], 2 * S)
    if backend == "pallas":
        assert "pallas_call" in str(jax.make_jaxpr(kda_state_update_rows)(
            one(q), one(k), one(v), one(log_a), one(beta), states,
            jnp.int32(1)))


def test_the_recurrence_is_the_public_gated_delta_rule(monkeypatch):
    """With the decay made equal across a head's channels, the
    REFERENCE's recurrence is ``qwen3_next``'s recurrent gated delta
    rule (an oracle for the algebra only: KDA's decay is a channel's)."""
    monkeypatch.setenv("USE_TF", "0")
    monkeypatch.setenv("USE_FLAX", "0")
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip(
        "transformers.models.qwen3_next.modeling_qwen3_next")
    rng = np.random.default_rng(5)
    T, nh, d = 37, 3, 16
    q, k, v = (rng.normal(size=(T, nh, d)).astype(np.float32)
               for _ in range(3))
    g = -5.0 / (1 + np.exp(-rng.normal(size=(T, nh)))).astype(np.float32)
    beta = (1 / (1 + np.exp(-rng.normal(size=(T, nh))))).astype(np.float32)
    t = lambda a: torch.tensor(a)[None]
    want, _ = hf.torch_recurrent_gated_delta_rule(
        t(q), t(k), t(v), t(g), t(beta), None, False,
        use_qk_l2norm_in_kernel=True)
    got = ref.delta_rule(
        ref.l2norm(jnp.asarray(q)) * d ** -0.5, ref.l2norm(jnp.asarray(k)),
        jnp.asarray(v), jnp.broadcast_to(g[..., None], (T, nh, d)),
        jnp.asarray(beta))
    np.testing.assert_allclose(got, want[0].numpy(), atol=2e-6)
    # and the program's oracle is the reference's
    mine, _ = _loop(*(jnp.asarray(a)[None] for a in (
        np.asarray(ref.l2norm(jnp.asarray(q))) * d ** -0.5,
        np.asarray(ref.l2norm(jnp.asarray(k))), v,
        np.broadcast_to(g[..., None], (T, nh, d)), beta)),
        jnp.zeros((1, nh, d, d)))
    np.testing.assert_allclose(mine[0], got, atol=2e-6)


def test_conv_step_is_the_conv_over_rows():
    """The decode step's 2-D conv (the tail one row of ``3 C``) against
    the chunk's, and the chunk's against the tail-as-columns form the
    state-space family keeps."""
    ks = jax.random.split(jax.random.key(4), 3)
    B, C, W = 3, 8, 4
    x = jax.random.normal(ks[0], (B, 5, C))
    w = jax.random.normal(ks[1], (C, W))
    tail = jax.random.normal(ks[2], (B, W - 1, C))
    y0, t0 = ssm.causal_conv(x, jnp.swapaxes(tail, 1, 2), w, None, valid=3)
    y1, t1 = ssm.causal_conv_rows(x, tail, w, None, valid=3)
    np.testing.assert_array_equal(y0, y1)
    np.testing.assert_array_equal(jnp.swapaxes(t0, 1, 2), t1)
    flat = tail.reshape(B, -1)
    for i in range(3):
        y, flat = ssm.causal_conv_step(x[:, i], flat, w, None)
        np.testing.assert_allclose(y, y1[:, i], atol=1e-6)
    np.testing.assert_allclose(flat.reshape(B, W - 1, C), t1, atol=1e-6)


# ---------------------------------------------------------------------
# the latent mixer
# ---------------------------------------------------------------------
def test_latent_mixer_is_deepseek_v3s_block(monkeypatch):
    """The reference's latent mixer with its gate held at a half
    (``W_g = 0``) is ``DeepseekV3Attention`` without a query rank, on
    the same weights; and the program's two forms of it agree."""
    monkeypatch.setenv("USE_TF", "0")
    monkeypatch.setenv("USE_FLAX", "0")
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip(
        "transformers.models.deepseek_v3.modeling_deepseek_v3")
    from transformers import DeepseekV3Config
    keys = ("hidden_size num_attention_heads num_key_value_heads "
            "q_lora_rank kv_lora_rank qk_nope_head_dim qk_rope_head_dim "
            "v_head_dim rope_theta rms_norm_eps max_position_embeddings "
            "rope_interleave").split()
    hcfg = DeepseekV3Config(
        **{k: CONFIG[k] for k in keys}, rope_scaling=None,
        attention_bias=False, attn_implementation="eager")
    attn = hf.DeepseekV3Attention(hcfg, 0).eval()
    z = ref.sizes(CONFIG)
    w = {k: jnp.asarray(v, jnp.float32) for k, v in ref.layer_weights(
        CONFIG, ref.seed_key(SEED), 2, jnp.float32).items()
        if not callable(v)}
    assert z["types"][2] == "attention_expert"
    w["kv_a_ln_w"] = 1 - 0.1 * jnp.arange(16.0) / 16
    t = lambda a: torch.tensor(np.asarray(a, np.float32))
    missing, unexpected = attn.load_state_dict({
        "q_proj.weight": t(w["q_w"].T),
        "kv_a_proj_with_mqa.weight": t(w["kv_a_w"].T),
        "kv_a_layernorm.weight": t(w["kv_a_ln_w"]),
        "kv_b_proj.weight": t(w["kv_b_w"].T),
        "o_proj.weight": t(w["o_w"].T)}, strict=False)
    assert not missing and not unexpected
    T = 21
    u = jax.random.normal(jax.random.key(1), (T, 64))
    rot = hf.DeepseekV3RotaryEmbedding(config=hcfg)
    mask = torch.full((T, T), float("-inf")).triu(1)[None, None]
    with torch.no_grad():
        want = attn(t(u)[None], rot(t(u)[None], torch.arange(T)[None]),
                    mask)[0][0]
    got = ref.latent_mix(u, dict(w, g_w=jnp.zeros((64, 4))), z, "highest")
    np.testing.assert_allclose(2 * got, want.numpy(),
                               atol=2e-5 * float(want.abs().max()))
    # the program: no query rank, the expanded and the absorbed form
    spec = zoo.mla_spec(prog.program_config(CONFIG), 4)
    lp = prog.program_layer(CONFIG, w)
    q_n, q_r, latent = mla.project(u, lp, jnp.arange(T), spec)
    tri = jnp.tril(jnp.ones((T, T), bool))
    lat = latent[:, :spec.latent_width]
    a = mla.expanded_attention(q_n, q_r, lat, lp["uk_w"], lp["uv_w"], tri,
                               spec.scale)
    b = mla.absorbed_attention(q_n, q_r, lat, lp["uk_w"], lp["uv_w"], tri,
                               spec.scale)
    np.testing.assert_allclose(a, b, atol=2e-5)
    full = ref.latent_mix(u, w, z, "highest")
    np.testing.assert_allclose(
        mla.gate_heads(a, u @ lp["g_w"]) @ lp["o_w"], full,
        atol=2e-5 * float(jnp.abs(full).max()))


# ---------------------------------------------------------------------
# the experts: a share of the router's
# ---------------------------------------------------------------------
def _bank(T, E=16, H=32, F=16, k=4, seed=6):
    ks = jax.random.split(jax.random.key(seed), 6)
    wg, wu = (0.2 * jax.random.normal(ks[i], (E, H, F)) for i in (0, 1))
    wd = 0.2 * jax.random.normal(ks[2], (E, F, H))
    x = jax.random.normal(ks[3], (T, H))
    w, idx = moe.route_sigmoid(
        jax.random.normal(ks[4], (T, E)),
        0.1 * jax.random.normal(ks[5], (E,)), k, n_group=4, topk_group=2,
        scale=2.5)
    return x, w, idx, wg, wu, wd


@pytest.mark.parametrize("T", [20, 128, 256])
def test_the_shares_terms_add_up_to_the_whole_layers(T):
    """``moe_swiglu_ffn_routed`` for a rank that holds experts ``[off,
    off + 4)`` of 16, for a decode step's rows (20, 128) and a chunk
    fill's (256), all in the grouped form: the four shares' sums, gates
    unchanged, are the whole bank's; a share's ``rows`` are whole tiles
    of its own held pairs only, and none for an expert no row chose."""
    from paddle_tpu.ops.pallas.moe_grouped_matmul import grouped_tiles
    x, w, idx, wg, wu, wd = _bank(T)
    whole, _ = moe.moe_swiglu_ffn_routed(x, w, idx, wg, wu, wd)
    # a tile is an even share of the EXPECTED held pairs an expert:
    # a quarter of the T x 4 pairs over the 4 held
    tm = grouped_tiles(T, 4, 32, 16, 2, 4)[0]
    total = 0
    for off in range(0, 16, 4):
        part, r = moe.moe_swiglu_ffn_routed(
            x, w, idx, wg[off:off + 4], wu[off:off + 4], wd[off:off + 4],
            expert_offset=off, router_experts=16)
        total = total + part
        local, held = moe.held_choices(idx, 4, off)
        load = np.bincount(np.asarray(local[held]), minlength=4)
        assert int(r) == int((-(-load // tm) * tm).sum())
        assert int(held.sum()) <= int(r) < int(held.sum()) + 4 * tm
        assert int(r) < T * 4                    # not every row by each
    np.testing.assert_allclose(total, whole, atol=2e-5)
    # a rank none of whose experts was chosen multiplies no row and
    # adds nothing
    none, r = moe.moe_swiglu_ffn_routed(
        x, w, jnp.zeros_like(idx), wg[4:8], wu[4:8], wd[4:8],
        expert_offset=4, router_experts=16)
    assert float(jnp.abs(none).max()) == 0 and int(r) == 0


def test_held_choices_and_their_counts():
    idx = jnp.asarray([[0, 5], [6, 7], [11, 4], [12, 3]])
    local, held = moe.held_choices(idx, 8, 4)
    np.testing.assert_array_equal(local, [[8, 1], [2, 3], [7, 0], [8, 8]])
    np.testing.assert_array_equal(held.sum(), 5)
    # pairs on held experts, distinct held experts hit, the most on one
    np.testing.assert_array_equal(moe.expert_counts(local, 8), [5, 5, 1])
    np.testing.assert_array_equal(moe.expert_counts(
        local, 8, jnp.asarray([True, False, True, False])), [3, 3, 1])


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """THE share test: an expert layer of the uncut reference (all 16
    experts) against the four ranks' partial sums (``num_experts`` 4 at
    offsets 0, 4, 8, 12: each rank's routed terms and the shared
    expert), the shared expert counted once: what every rank computes
    alike is in each rank's output, so three of the four copies come
    off.  And each rank's program layer is that rank's reference."""
    z = ref.sizes(CONFIG)
    key = ref.seed_key(SEED)
    f32 = jnp.float32

    def leaves(config):
        return {k: jnp.asarray(v, f32) for k, v in ref.layer_weights(
            config, key, 1, f32).items()}

    w = leaves(CONFIG)
    x = jax.random.normal(jax.random.key(3), (40, 64))
    y = ref.rms_norm(x, w["ln2_w"], z["eps"])
    shared = ref.swiglu(y, w["s_gate"], w["s_up"], w["s_down"], "highest")
    uncut = ref.moe(y, w, z, "highest") + shared
    ranks = 0
    for off in range(0, 16, 4):
        cut = dict(CONFIG, num_experts=4, expert_offset=off)
        wc = leaves(cut)
        # a share draws what the whole draws
        np.testing.assert_array_equal(wc["e_up"], w["e_up"][off:off + 4])
        rank = ref.moe(y, wc, ref.sizes(cut), "highest") + shared
        ranks = ranks + rank
        # the program's layer for this rank: its bank one layer of a stack
        lp = dict(wc, bank=jnp.int32(0))
        for n in ref._EXPERT_LEAVES:
            lp[n] = lp[n][None]
        got, counts, fill = zoo._make_ffn_half(prog.program_config(cut))(
            x, lp, "expert")
        np.testing.assert_allclose(got - x, rank, atol=2e-5)
        assert int(counts[0]) == int(fill[0]) > 0       # its own pairs
    np.testing.assert_allclose(ranks - 3 * shared, uncut,
                               atol=2e-5 * float(jnp.abs(uncut).max()))


# ---------------------------------------------------------------------
# through the engine
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


def _check(served, prompts, news, config=SHARE, pad_to=64):
    for (seq, logits), prompt, new in zip(served.values(), prompts, news):
        T0 = len(prompt)
        assert len(seq) == T0 + new
        want = _ref_logits(seq, config, pad_to)[T0 - 1:len(seq) - 1]
        got = np.stack(logits[:new])
        np.testing.assert_allclose(got, want,
                                   atol=2e-4 * np.abs(want).max())
        assert (want.argmax(-1) == seq[T0:]).all()


def test_the_programs_tree_is_the_references_draw():
    """The program holds what the reference draws: ``q_w | k_w | v_w``
    and their convs side by side, ``kv_b_w`` cut per head, a run a
    stretch of layers of one kind, the held experts only."""
    params = prog.make_params(SHARE, SEED)
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == ref.param_count(SHARE)
    cfg = prog.program_config(SHARE)
    assert cfg.runs() == [("kda_dense", 1, 0), ("kda_expert", 1, 1),
                          ("attention_expert", 1, 2), ("kda_expert", 1, 3)]
    assert cfg.rows() == (0, 1, 0, 2) and cfg.num_attention_layers == 1
    assert [set(r) == set(zoo.layer_shapes(cfg, k)) for r, (k, _, _)
            in zip(params["runs"], cfg.runs())] == [True] * 4
    dense, kda_run, latent, _ = params["runs"]
    assert dense["gate_w"].shape == (1, 64, 128)
    assert kda_run["e_gate"].shape == (1, 8, 64, 32)
    assert kda_run["router_w"].shape == (1, 64, 16)
    w = ref.layer_weights(SHARE, ref.seed_key(SEED), 1, jnp.float32)
    np.testing.assert_allclose(kda_run["qkv_w"][0, :, 64:128], w["k_w"],
                               rtol=1e-6)
    np.testing.assert_allclose(kda_run["conv_w"][0, 128:], w["conv_v_w"],
                               rtol=1e-6)
    # log decays a token at x W_f = 0 between 0.002 and 0.3 (and A = 1)
    rate = 5 * jax.nn.sigmoid(kda_run["dt_bias"][0])
    assert 0.002 <= float(rate.min()) < 0.01 and \
        0.1 < float(rate.max()) <= 0.3
    w = ref.layer_weights(SHARE, ref.seed_key(SEED), 2, jnp.float32)
    kvb = np.asarray(w["kv_b_w"]).reshape(16, 4, 24 + 16)
    np.testing.assert_allclose(latent["uk_w"][0, 2], kvb[:, 2, :24],
                               rtol=1e-6)
    np.testing.assert_allclose(latent["uv_w"][0, 3], kvb[:, 3, 24:],
                               rtol=1e-6)


def test_served_logits_match_the_reference():
    """Prefill in chunks (the chunked scan, the expanded latent form,
    the state handed from chunk to chunk and to the decode step), then
    decoding through the latent pool and the state (the per-op update,
    the absorbed form), for slots at different lengths with admissions
    and retirements in between (5 requests through 3 slots: a reused
    slot's state and a reused page start clean) = the float32
    reference's full forward, a token at a time; logits compared."""
    eng = _engine()
    prompts = _prompts((5, 19, 8, 33, 3))
    news = (6, 4, 9, 5, 7)
    _check(_serve(eng, prompts, news), prompts, news)
    leak = eng.kv_leak_report()
    assert leak["leaked"] == leak["unaccounted"] == leak["state_rows"] == 0
    # ONE latent layer's pool (16 + 8 values a token in whole lanes of
    # 128, no value pool) beside three KDA layers' state and conv tails
    assert eng.pool_k.shape == (1, 64, 4, 128) and eng.pool_v is None
    assert eng.ssm_state.shape == (3, 3, 4, 16, 16)
    assert eng.ssm_state.dtype == jnp.float32
    assert eng.conv_state.shape == (3, 3, 3 * 3 * 64)
    assert eng._carry == ("pool_k", "ssm_state", "conv_state")


@pytest.mark.parametrize("length", [3, 8, 24, 41])
def test_padded_buckets_and_chunk_hand_over(length):
    """Buckets of 8 and 16: a prompt shorter than a bucket (padding
    leaves state, tail and pool alone), one that fills it, two chunks,
    four (16, 16, 8 and a padded 8)."""
    eng = _engine()
    before = _stats(eng)
    prompts, news = _prompts((length,), seed=length), (5,)
    _check(_serve(eng, prompts, news), prompts, news)
    d = _since(eng, before)
    assert d["prefill_tokens_computed"] == length
    assert d["prefill_tokens_dispatched"] >= length
    assert d["prefill_chunks"] == {3: 1, 8: 1, 24: 2, 41: 4}[length]


def test_a_wide_bucket_fills_through_the_grouped_form():
    """A bucket of 256 rows: the fill's expert layers sort their HELD
    pairs and run the grouped matmuls, the KDA layers four chunks of
    64; the logits are still the reference's, and the fill's counters
    count held pairs."""
    eng = _engine(prefill_buckets=(16, 256), max_blocks_per_seq=80,
                  num_blocks=96)
    prompts, news = _prompts((300,), seed=9), (3,)
    _check(_serve(eng, prompts, news), prompts, news, pad_to=320)
    s = eng.scheduler_stats()
    # 256 + 16 + 16 + 16 rows through 3 expert layers, 2 choices a row,
    # half of the experts held
    assert s["prefill_tokens_dispatched"] == 304
    assert 0 < s["moe_fill_pairs"] < 3 * 304 * 2
    assert s["moe_fill_rows"] >= s["moe_fill_pairs"]
    # the 256 rows' share is not the masked form's 256 x 8 a layer
    assert s["moe_fill_rows"] < 3 * (48 * 8 + 256 * 4)


def test_counters_and_what_is_off():
    """What the benchmark's metric files read: live slots x recurrent
    layers a step; pairs on held experts out of all pairs; the prefix
    cache off (a page hit cannot restore a state)."""
    eng = _engine()
    before = _stats(eng)
    prompts = _prompts((9, 9), seed=3)
    prompts[1] = prompts[0].copy()                 # the same prompt twice
    for p in prompts:
        eng.add_request(p, 6)
    eng.run_to_completion()
    d = _since(eng, before)
    assert d["state_slot_steps"] == 3 * d["decode_slot_steps"]
    assert d["moe_assignments_total"] == d["decode_slot_steps"] * 2 * 3
    assert 0 < d["moe_assignments_local"] < d["moe_assignments_total"]
    assert 0 < d["moe_experts_hit"] <= d["moe_expert_slots"]
    assert d["moe_expert_slots"] == d["decode_steps"] * 8 * 3
    assert 0 < d["moe_peak_load"] <= d["moe_assignments_local"]
    assert 0 < d["moe_fill_pairs"] <= d["moe_fill_rows"]
    p = eng.prefix_stats()
    assert p["enabled"] is False and p["hits"] == 0
    assert eng.kernel_tiers() == {"kda_state_update": {
        "tier": "xla", "reason": "not on a TPU"}}


def test_the_steps_experts_multiply_held_pairs_only():
    """``moe_step_rows``: the rows a decode step's expert matmuls
    multiplied, whole tiles of the pairs on HELD experts (all 16 rows
    are routed, an idle slot's too), summed over the 3 expert layers
    and the steps: it moves with the steps, is never fewer than the
    live rows' held pairs, and stays under every row by every held
    expert (16 x 8 a layer), which is what the masked form multiplied."""
    eng = _engine(max_batch=16)
    prompts = _prompts((5, 9, 7, 3), seed=12)
    seen = [_stats(eng)]
    for new in (3, 5):
        for p in prompts:
            eng.add_request(p, new)
        eng.run_to_completion()
        seen.append(_stats(eng))
    for before, after in zip(seen, seen[1:]):
        steps = after["decode_steps"] - before["decode_steps"]
        rows = after["moe_step_rows"] - before["moe_step_rows"]
        local = after["moe_assignments_local"] \
            - before["moe_assignments_local"]
        assert steps > 0 and 0 < local <= rows < steps * 3 * 16 * 8
        # tiles of 8 rows: at most 7 rows of padding a held expert
        assert rows % 8 == 0 and rows <= steps * 3 * (16 * 2 + 8 * 7)
    assert eng.kv_leak_report()["leaked"] == 0


@pytest.mark.parametrize("metric, cell", [
    ("moe_step_slack.ling3flash", "ling3flash-reason"),
    ("moe_step_slack.glm47flash", "glm47flash-long")])
def test_the_step_slack_metrics_read_the_two_counters(metric, cell):
    """``benchmark/metrics/moe_step_slack.*.json``: the steps' rows over
    their pairs on held experts, as ``scheduler_stats()`` names them; a
    program without the counter (the parent commit) leaves the metric
    out of the line."""
    from benchmark.lib import cell as harness, model
    (m,) = [m for m in harness.load_metrics(cell, model.HERE)
            if m["name"] == metric]
    assert {m["numerator"], m["denominator"]} <= set(
        _engine().scheduler_stats())
    got = harness.reduce_metrics([m], {"counters": {
        "moe_step_rows": 6 * 1040, "moe_assignments_local": 6 * 260}})
    assert got == {metric: {"value": 4.0, "unit": "rows/pair"}}
    assert harness.reduce_metrics(
        [m], {"counters": {"moe_assignments_local": 6 * 260}}) == {}


def test_greedy_rows_are_picked_on_the_device():
    eng = _engine()
    rid = eng.add_request(_prompts((6,), seed=8)[0], 4)
    steps = eng.decode_steps
    while eng.decode_steps == steps:
        eng.step()
    assert eng._last_logits is not None \
        and not isinstance(eng._last_logits, np.ndarray)   # not fetched
    slot = next(s for s in range(eng.B) if eng.slots[s] is not None)
    assert int(eng.last_logits[slot].argmax()) == eng.slots[slot].out[-1]
    assert len(eng.run_to_completion()[rid]) == 10


def test_preempted_stream_resumes_bit_identical():
    """A preempted slot's snapshot carries its latent pages (through the
    fixed-width page programs, no value pages) AND its state rows, the
    spans say how many bytes of state went with the pages, and the
    stream decodes the same tokens — as does one whose snapshot is gone
    (replay from the committed tokens)."""
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
    state_bytes = 3 * 4 * 16 * 16 * 4 + 3 * 3 * 192 * 4

    def preempt(eng):
        slot = next(s for s in range(eng.B) if eng.slots[s] is not None)
        used = -(-int(eng.lengths[slot]) // 4)
        snap = eng._spill.get(eng.preempt(slot))
        assert snap.k_pages.shape == (1, used, 4, 128)
        assert snap.v_pages.shape == (1, used, 4, 0)
        assert snap.ssm_state.shape == (3, 4, 16, 16)
        assert snap.conv_state.shape == (3, 3 * 192)
        assert snap.state_nbytes == state_bytes
        assert float(np.abs(snap.ssm_state).max()) > 0
        assert eng.spill_compatible(snap)
        snap.verify()

    before = _stats(eng)
    TRACER.reset()
    TRACER.enable()
    try:
        resumed = run(preempt)
        spans = {s.name: s.attrs for it in TRACER.timeline().iterations()
                 for s in it.spans}
    finally:
        TRACER.disable()
        TRACER.reset()
    assert spans["kv_restore"]["state_bytes"] == state_bytes
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


def test_a_priority_preemption_names_its_state_bytes():
    """Under ``admit`` a higher class evicts a running slot: the
    ``kv_snapshot`` span says what went with the pages."""
    from paddle_tpu.observability.tracing import TRACER
    eng = _engine(max_batch=1)
    low = eng.add_request(_prompts((9,), seed=5)[0], 12, priority=0)
    TRACER.reset()
    TRACER.enable()
    try:
        for _ in range(3):
            eng.step()
        high = eng.add_request(_prompts((5,), seed=6)[0], 3, priority=5)
        out = eng.run_to_completion()
        spans = {s.name: s.attrs for it in TRACER.timeline().iterations()
                 for s in it.spans}
    finally:
        TRACER.disable()
        TRACER.reset()
    assert len(out[low]) == 9 + 12 and len(out[high]) == 5 + 3
    assert spans["kv_snapshot"]["state_bytes"] == \
        spans["kv_restore"]["state_bytes"] > 0
    assert eng.kv_leak_report()["leaked"] == 0


# ---------------------------------------------------------------------
# what is refused, by name
# ---------------------------------------------------------------------
@pytest.mark.parametrize("what", ["spec_config", "quant_config", "aot_dir",
                                  "prefix_cache_config"])
def test_refused_loudly_for_a_state_beside_a_latent_cache(what, tmp_path):
    from paddle_tpu.serving.prefix_cache import PrefixCacheConfig
    kw = {"spec_config": object(), "quant_config": object(),
          "aot_dir": str(tmp_path),
          "prefix_cache_config": PrefixCacheConfig(
              offload_capacity_bytes=1 << 20)}
    with pytest.raises(NotImplementedError, match=what):
        _engine(**{what: kw[what]})


def test_aot_export_refuses_the_model(tmp_path):
    from paddle_tpu.aot import export_engine
    with pytest.raises(NotImplementedError, match="latent|state"):
        export_engine(_engine(), str(tmp_path))


@pytest.mark.parametrize("key, value, match", [
    ("num_hidden_layers", 5, "SwiGLU limit"),
    ("q_lora_rank", 24, "low-rank query"),
])
def test_what_the_family_does_not_hold_is_refused(key, value, match):
    """A layer whose published SwiGLU limit is not 0 (the last 8 of the
    published 42), a query rank: refused in the program's configuration
    and in the reference, not guessed at."""
    with pytest.raises(NotImplementedError, match=match):
        prog.program_config(dict(CONFIG, **{key: value}))
    with pytest.raises(NotImplementedError):
        ref.sizes(dict(CONFIG, **{key: value}))


def test_http_cli_builds_the_tiny_model():
    """``python -m paddle_tpu.serving.http --model ling_linear_tiny``
    serves through ``build_frontend`` -> ``ServingFrontend`` ->
    ``ContinuousBatchingEngine``."""
    from paddle_tpu.serving import http
    args = http.parse_args(["--model", "ling_linear_tiny",
                            "--max-batch", "2", "--num-blocks", "32"])
    fe = http.build_frontend(args)
    assert type(fe.engine) is ContinuousBatchingEngine
    assert fe.engine.pool_k.shape[0] == 1 and fe.engine.pool_v is None
    assert fe.engine.ssm_state.shape[:2] == (3, 2)
    h = fe.submit(_prompts((6,))[0], 5)
    fe.run_until_drained(timeout_s=120)
    assert h.state.name == "FINISHED" and len(h.tokens()) == 5


def test_the_planted_fault_moves_the_decay():
    """The benchmark's planted fault stands in for both ops through
    their module, and is another recurrence."""
    args = _draw(7, 1, 12, 2, 8, 8)
    sound = (kda.kda_chunk_scan, kda.kda_state_update_row)
    fault = prog.planted_fault()
    try:
        fault.wrap_engine(None)
        assert kda.kda_chunk_scan is not sound[0]
        o1, s1 = jax.jit(kda.kda_chunk_scan)(*args)
    finally:
        fault.unwrap()
    assert (kda.kda_chunk_scan, kda.kda_state_update_row) == sound
    o0, s0 = _scan(*args)
    assert float(jnp.abs(o1 - o0).max()) > 1e-2
    assert float(jnp.abs(s1 - s0).max()) > 1e-2
