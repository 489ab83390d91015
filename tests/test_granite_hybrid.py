"""The hybrid family (Mamba-2 + attention layers, experts after every
one; ``models/granite_hybrid.py``) through the continuous-batching
engine, against the plain reference of the benchmark
(``benchmark/reference/granite_hybrid_ref.py``, which imports nothing of
``paddle_tpu``) and, where ``transformers`` has the architecture,
against the public implementation.  Tiny sizes, float32, CPU."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.programs import granite_hybrid as prog
from benchmark.reference import granite_hybrid_ref as ref
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.ops import ssm

SEED = 7
CONFIG = dict(
    hidden_size=64, intermediate_size=16, shared_intermediate_size=32,
    layer_types=["mamba", "mamba", "attention", "mamba"],
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
    num_local_experts=8, num_experts_per_tok=3, mamba_n_heads=4,
    mamba_d_head=32, mamba_d_state=16, mamba_n_groups=1, mamba_d_conv=4,
    mamba_chunk_size=8, mamba_expand=2, embedding_multiplier=12,
    attention_multiplier=0.0625, residual_multiplier=0.22,
    logits_scaling=16, rms_norm_eps=1e-5, max_position_embeddings=512,
    vocab_size=256, torch_dtype="float32", position_embedding_type="nope",
    initializer_range=0.02, reference="granite_hybrid_ref",
    program="granite_hybrid")


_ENGINES = {}


def _engine(config=CONFIG, **kw):
    """The tiny engine; without further arguments ONE engine a
    configuration for the whole file (its programs compile once; every
    test leaves it drained and reads its counters as differences)."""
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
    """The reference's logits of one sequence, padded to one length so
    that its programs compile once (every layer is causal)."""
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
def _scan_inputs(T, B=2, nh=4, P=8, G=1, N=16):
    k = jax.random.split(jax.random.key(1), 6)
    return dict(
        x=jax.random.normal(k[0], (B, T, nh, P)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (B, T, nh)) - 2),
        A=-jnp.arange(1.0, nh + 1), D=jnp.ones(nh),
        Bm=jax.random.normal(k[2], (B, T, G, N)),
        Cm=jax.random.normal(k[3], (B, T, G, N)),
        state=jax.random.normal(k[4], (B, nh, P, N)))


@pytest.mark.parametrize("T,chunk", [(16, 8), (21, 8), (5, 8)])
def test_chunked_scan_equals_the_recurrence(T, chunk):
    """With a state in, whole chunks, a length off the chunk, and a
    length under one chunk."""
    a = _scan_inputs(T)
    args = (a["x"], a["dt"], a["A"], a["Bm"], a["Cm"], a["D"], a["state"])
    y0, s0 = ssm.ssm_recurrence(*args)
    y1, s1 = ssm.ssd_chunk_scan(*args, chunk=chunk)
    np.testing.assert_allclose(y1, y0, atol=2e-5)
    np.testing.assert_allclose(s1, s0, atol=2e-5)


def test_padded_bucket_leaves_state_and_tail_alone():
    """Positions at or past ``valid`` (``dt = 0`` there) change neither
    the state nor the conv tail: a bucket of 16 holding 11 tokens ends
    where 11 tokens end."""
    T, valid = 16, 11
    a = _scan_inputs(T)
    dt = jnp.where((jnp.arange(T) < valid)[None, :, None], a["dt"], 0.0)
    _, s_pad = ssm.ssd_chunk_scan(a["x"], dt, a["A"], a["Bm"], a["Cm"],
                                  a["D"], a["state"], chunk=8)
    _, s_cut = ssm.ssm_recurrence(
        a["x"][:, :valid], a["dt"][:, :valid], a["A"], a["Bm"][:, :valid],
        a["Cm"][:, :valid], a["D"], a["state"])
    np.testing.assert_allclose(s_pad, s_cut, atol=2e-5)
    k = jax.random.split(jax.random.key(2), 3)
    x = jax.random.normal(k[0], (2, T, 6))
    w, b = jax.random.normal(k[1], (6, 4)), jax.random.normal(k[2], (6,))
    tail = jnp.zeros((2, 6, 3))
    y_all, _ = ssm.causal_conv(x, tail, w, b)
    y_a, t_a = ssm.causal_conv(x[:, :valid], tail, w, b)
    _, t_pad = ssm.causal_conv(x, tail, w, b, valid=valid)
    np.testing.assert_array_equal(t_pad, t_a)
    # and the tail carries the conv across a cut
    y_b, _ = ssm.causal_conv(x[:, valid:], t_a, w, b)
    np.testing.assert_allclose(jnp.concatenate([y_a, y_b], 1), y_all,
                               atol=1e-6)


@pytest.mark.parametrize("nh,P,N,G", [(4, 8, 16, 1), (8, 8, 128, 2)])
def test_state_update_kernel_equals_the_per_op_update(nh, P, N, G):
    """The Pallas tier of the decode step's state update (interpreted
    here) against the per-op one: the chosen layer's row stepped once,
    every other row untouched, for one group and for two."""
    L, B = 3, 2
    a = _scan_inputs(1, B=B, nh=nh, P=P, G=G, N=N)
    states = jax.random.normal(jax.random.key(3), (L, B, nh, P, N))
    args = (a["x"][:, 0], a["dt"][:, 0], a["A"], a["Bm"][:, 0],
            a["Cm"][:, 0], a["D"])
    for row in (0, 2):
        y0, s0 = ssm.ssm_state_update_row(*args, states, jnp.int32(row),
                                          backend="xla")
        y1, s1 = jax.jit(lambda st, r: ssm.ssm_state_update_row(
            *args, st, r, backend="pallas"))(states, jnp.int32(row))
        np.testing.assert_allclose(y1, y0, atol=2e-5)
        np.testing.assert_allclose(s1, s0, atol=2e-6)
        keep = [r for r in range(L) if r != row]
        np.testing.assert_array_equal(np.asarray(s1)[keep],
                                      np.asarray(states)[keep])
    assert ssm.ssm_state_update_tier(states.shape, G)[0] == "xla"  # a CPU
    # the compiled kernel's own limit (the interpreter has none): lanes
    from paddle_tpu.ops.pallas.ssm import unsupported_reason
    reason = unsupported_reason(states.shape, G)
    assert reason is None if N % 128 == 0 else "128 lanes" in reason


# ---------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------
@pytest.mark.parametrize("gate", ["topk_softmax", "softmax_topk"])
@pytest.mark.parametrize("split", [4, 3])
def test_the_shares_add_up(split, gate):
    """The partial MoE outputs of the two shares of a split, the shared
    expert counted once, equal the uncut layer: with granite's gate the
    reference's, with Mixtral's the sorted whole-bank form's
    (``moe_swiglu_ffn_grouped``)."""
    from paddle_tpu.parallel import moe
    z = ref.sizes(CONFIG)
    key = ref.seed_key(SEED)
    w = ref.layer_weights(CONFIG, key, 0, jnp.float32)
    y = jax.random.normal(jax.random.key(3), (9, z["H"]))
    shared = ref.swiglu(y, w["s_gate"], w["s_up"], w["s_down"], "highest")
    if gate == "topk_softmax":
        want = ref.moe(y, w, z, "highest") + shared
    else:
        want = moe.moe_swiglu_ffn_grouped(
            y, w["router_w"], w["e_gate"], w["e_up"], w["e_down"],
            top_k=z["K"]) + shared
    got = shared
    local = 0
    for e0, n in ((0, split), (split, 8 - split)):
        share = dict(CONFIG, num_local_experts=n, router_num_experts=8,
                     expert_offset=e0)
        ws = ref.layer_weights(share, key, 0, jnp.float32)
        # a share draws what the whole draws
        np.testing.assert_array_equal(ws["e_up"], w["e_up"][e0:e0 + n])
        part, counts = moe.moe_swiglu_ffn_masked(
            y, ws["router_w"], ws["e_gate"], ws["e_up"], ws["e_down"],
            top_k=z["K"], gate=gate, expert_offset=e0, with_counts=True)
        if gate == "topk_softmax":      # the reference's own share
            np.testing.assert_allclose(
                part, ref.moe(y, ws, ref.sizes(share), "highest"),
                atol=1e-6)
        got = got + part
        local += int(counts[0])
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert local == 9 * z["K"]          # every choice landed on one share


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


def test_served_logits_match_the_reference():
    """Prefill in chunks, then decoding through the cache, for a batch
    of slots at different lengths with admissions and retirements in
    between (5 requests through 3 slots: a reused slot starts clean) =
    the reference's full forward, logits compared."""
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
    assert leak["state_rows"] == 0 and leak["free_blocks"] == 64
    assert eng.prefix_stats()["hits"] == 0
    # K/V pages for the attention layer only; state for the Mamba ones
    assert eng.pool_k.shape[0] == 1 and eng.ssm_state.shape[:2] == (3, 3)
    assert eng.ssm_state.dtype == jnp.float32


def test_counters_and_prefix_cache_for_a_model_with_state():
    eng = _engine()
    before = _stats(eng)
    p = _prompts((12,))[0]
    for _ in range(2):                      # the same prompt twice
        eng.add_request(p, 4)
    eng.run_to_completion()
    s = _since(eng, before)
    k, layers = CONFIG["num_experts_per_tok"], 4
    # all experts held: every choice is local, counted on live rows only
    assert s["moe_assignments_total"] == s["decode_slot_steps"] * k * layers
    assert s["moe_assignments_local"] == s["moe_assignments_total"]
    assert s["moe_expert_slots"] == s["decode_steps"] * 8 * layers
    assert 0 < s["moe_experts_hit"] <= s["moe_expert_slots"]
    # a page hit cannot restore a state: nothing registered or matched
    ps = eng.prefix_stats()
    assert ps["enabled"] is False
    assert ps["hits"] == ps["hit_blocks"] == ps["inserts"] == 0
    assert ps["cached_blocks"] == 0 and eng.prefix_index == {}


def test_greedy_rows_are_picked_on_the_device():
    """The step returns every row's first choice, so the logits stay on
    the device; ``last_logits`` fetches them when a test looks, and they
    say what the step picked."""
    eng = _engine()
    eng.add_request(_prompts((9,))[0], 6)
    eng.step()
    eng.step()
    assert not isinstance(eng._last_logits, np.ndarray)
    picked = eng.slots[0].out[-1]
    assert eng.last_logits.shape == (3, 256)
    assert int(eng.last_logits[0].argmax()) == picked
    eng.run_to_completion()


def test_a_step_fetches_two_small_arrays_not_the_logits():
    """``decode_fetch_bytes`` (always on): the expert layers' two int32
    sums and a greedy int32 a slot, a step; the ``[3, 256]`` float32
    logits (3072 bytes) stay where they are."""
    eng = _engine()
    b0, n0 = eng.stats["decode_fetch_bytes"], eng.decode_steps
    eng.add_request(_prompts((9,))[0], 5)
    eng.run_to_completion()
    n = eng.decode_steps - n0
    assert n == 4
    assert eng.stats["decode_fetch_bytes"] - b0 == n * (2 * 4 + 3 * 4)


def test_a_share_of_the_experts_counts_about_half():
    half = dict(CONFIG, num_local_experts=4, router_num_experts=8)
    eng = _engine(half)
    before = _stats(eng)
    for p in _prompts((9, 14, 6), seed=1):
        eng.add_request(p, 8)
    eng.run_to_completion()
    s = _since(eng, before)
    share = s["moe_assignments_local"] / s["moe_assignments_total"]
    assert 0.3 < share < 0.7
    assert s["moe_expert_slots"] == s["decode_steps"] * 4 * 4


def test_preempted_stream_resumes_bit_identical():
    """The snapshot carries the slot's state rows with its pages, so a
    preempted and restored request decodes the same tokens — and so does
    one whose snapshot is gone (replay from the committed tokens)."""
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
        rid = eng.preempt(slot)
        snap = eng._spill.get(rid)
        assert snap.ssm_state.shape == (3, 4, 32, 16)
        assert snap.conv_state.shape == (3, 32 * 4 + 2 * 16, 3)
        snap.verify()

    before = _stats(eng)
    resumed = run(preempt)
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


def test_a_corrupt_state_snapshot_is_refused():
    from paddle_tpu.serving.resilience import SpillCorruptError
    eng = _engine()
    eng.add_request(_prompts((9,))[0], 8)
    eng.step()
    eng.step()
    rid = eng.preempt(0)
    snap = eng._spill.get(rid)
    snap.ssm_state = snap.ssm_state.copy()
    snap.ssm_state[0, 0, 0, 0] += 1.0
    with pytest.raises(SpillCorruptError):
        snap.verify()
    eng.cancel(rid)
    assert eng.kv_leak_report()["leaked"] == 0 and not eng._spill


@pytest.mark.parametrize("what", ["spec_config", "quant_config", "aot_dir"])
def test_refused_loudly_for_a_model_with_state(what, tmp_path):
    kw = {"spec_config": object(), "quant_config": object(),
          "aot_dir": str(tmp_path)}
    with pytest.raises(NotImplementedError, match=what):
        _engine(**{what: kw[what]})


def test_aot_export_refuses_a_model_with_state(tmp_path):
    from paddle_tpu.aot import export_engine
    with pytest.raises(NotImplementedError, match="recurrent state"):
        export_engine(_engine(), str(tmp_path))


def test_http_cli_builds_the_tiny_hybrid():
    """``python -m paddle_tpu.serving.http --model granite_hybrid_tiny``
    serves through ``build_frontend`` -> ``ServingFrontend`` ->
    ``ContinuousBatchingEngine``."""
    from paddle_tpu.serving import http
    args = http.parse_args(["--model", "granite_hybrid_tiny",
                            "--max-batch", "2", "--num-blocks", "32"])
    fe = http.build_frontend(args)
    assert type(fe.engine) is ContinuousBatchingEngine
    h = fe.submit(_prompts((6,))[0], 5)
    fe.run_until_drained(timeout_s=120)
    assert h.state.name == "FINISHED" and len(h.tokens()) == 5
    assert fe.engine.kernel_tiers() == {
        "ssm_state_update": {"tier": "xla", "reason": "not on a TPU"}}


# ---------------------------------------------------------------------
# the reference against the public implementation
# ---------------------------------------------------------------------
def test_reference_matches_transformers(monkeypatch):
    # transformers would import TensorFlow and Flax to look at them
    monkeypatch.setenv("USE_TF", "0")
    monkeypatch.setenv("USE_FLAX", "0")
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip(
        "transformers.models.granitemoehybrid.modeling_granitemoehybrid")
    from transformers import GraniteMoeHybridConfig
    keys = ("hidden_size intermediate_size shared_intermediate_size "
            "layer_types num_hidden_layers num_attention_heads "
            "num_key_value_heads num_local_experts num_experts_per_tok "
            "mamba_n_heads mamba_d_head mamba_d_state mamba_n_groups "
            "mamba_d_conv mamba_chunk_size mamba_expand "
            "embedding_multiplier attention_multiplier "
            "residual_multiplier logits_scaling rms_norm_eps "
            "max_position_embeddings vocab_size "
            "position_embedding_type").split()
    hcfg = GraniteMoeHybridConfig(
        **{k: CONFIG[k] for k in keys}, tie_word_embeddings=True,
        mamba_conv_bias=True, mamba_proj_bias=False, attention_bias=False,
        hidden_act="silu", attn_implementation="eager")
    net = hf.GraniteMoeHybridForCausalLM(hcfg).eval()
    key = ref.seed_key(SEED)
    t = lambda a: torch.tensor(np.asarray(a, np.float32))
    outer = ref.outer_weights(CONFIG, key, jnp.float32)
    sd = {"model.embed_tokens.weight": t(outer["wte"]),
          "lm_head.weight": t(outer["wte"]),
          "model.norm.weight": t(outer["lnf_w"])}
    for i, kind in enumerate(CONFIG["layer_types"]):
        w = ref.layer_weights(CONFIG, key, i, jnp.float32)
        p = f"model.layers.{i}."
        sd[p + "input_layernorm.weight"] = t(w["ln1_w"])
        sd[p + "post_attention_layernorm.weight"] = t(w["ln2_w"])
        sd[p + "block_sparse_moe.router.layer.weight"] = t(w["router_w"].T)
        sd[p + "block_sparse_moe.input_linear.weight"] = t(jnp.concatenate(
            [w["e_gate"], w["e_up"]], -1).transpose(0, 2, 1))
        sd[p + "block_sparse_moe.output_linear.weight"] = t(
            w["e_down"].transpose(0, 2, 1))
        sd[p + "shared_mlp.input_linear.weight"] = t(jnp.concatenate(
            [w["s_gate"], w["s_up"]], -1).T)
        sd[p + "shared_mlp.output_linear.weight"] = t(w["s_down"].T)
        if kind == "mamba":
            m = p + "mamba."
            sd[m + "in_proj.weight"] = t(w["in_w"].T)
            sd[m + "conv1d.weight"] = t(w["conv_w"][:, None, :])
            sd[m + "conv1d.bias"] = t(w["conv_b"])
            sd[m + "dt_bias"] = t(w["dt_bias"])
            sd[m + "A_log"] = t(w["A_log"])
            sd[m + "D"] = t(w["D"])
            sd[m + "norm.weight"] = t(w["norm_w"])
            sd[m + "out_proj.weight"] = t(w["out_w"].T)
        else:
            for n in "qkvo":
                sd[p + f"self_attn.{n}_proj.weight"] = t(w[n + "_w"].T)
    missing, unexpected = net.load_state_dict(sd, strict=False)
    assert not unexpected and not [m for m in missing
                                   if "rotary" not in m], (missing,
                                                           unexpected)
    ids = _prompts((23,), seed=4)[0]
    with torch.no_grad():
        want = net(torch.tensor(ids[None].astype(np.int64))).logits[0]
    got = _ref_logits(ids)
    np.testing.assert_allclose(got, want.numpy(),
                               atol=2e-4 * float(want.abs().max()))
