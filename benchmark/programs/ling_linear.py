"""The program module of the linear-attention family (``bailing_hybrid``:
KDA layers with one latent-attention layer in every group, a leading
dense layer, sigmoid-routed group-limited experts beside a shared one):
the one place under ``benchmark/`` that imports the model and engine
classes of ``paddle_tpu`` for it.  Serving only — the program has no
train step for this family, so this module offers no
``build_train_step``.

* ``program_config(config)``, ``make_params(config, seed)``: the
  program's ``LingLinearConfig`` and the reference's draw of the weights
  as the family's tree (``{"wte", "head", "lnf_w", "runs": (run, ...)}``,
  a run a stretch of layers of one kind, leaves stacked ``[n, ...]``; the
  reference's ``q_w | k_w | v_w`` and their three convs side by side as
  ``qkv_w`` / ``conv_w``, its ``kv_b_w`` cut per head into ``uk_w`` /
  ``uv_w``), made on the device in ONE jitted call in the served dtype;
* ``build_engine(cfg, params, engine)``: the same
  ``ContinuousBatchingEngine`` as every serving cell, with
  ``assumed.engine``;
* ``request_work`` / ``decode_step_work``: the work the model REQUIRES
  of this chip's share, checked against hand counts in
  ``tests/test_ling_cell.py``;
* ``planted_fault()``: hooks for ``calibrate_fault.py`` (the decay
  applied AFTER the rank-one update instead of before it).

The share: the file's ``num_experts`` experts are held of the router's
``router_num_experts``; a token's expected work is its ``k`` choices
times ``held / router`` experts.  A decode step must read the weights
once — of the held experts only those that some slot chose, in
expectation ``1 - (1 - k / router) ** slots`` of them — the KDA state
(float32) and conv tail of every live slot read AND written, and the
live latent of the attending layers.  The one Pallas kernel of the
family, ``kda_state_update``, has its own two keys."""

from __future__ import annotations

from typing import Any, Dict, Iterable

from ..lib import model


# ---------------------------------------------------------------------
# the program: its configuration, its weights, its engine
# ---------------------------------------------------------------------
def program_config(config: Dict[str, Any]):
    from paddle_tpu.models.ling_linear import LingLinearConfig
    if config.get("rope_scaling") is not None:
        raise ValueError("the program turns its rotary columns unscaled")
    if config["num_key_value_heads"] != config["num_attention_heads"] \
            or config.get("num_kv_heads_for_linear_attn", 0):
        raise ValueError("one latent for all heads, and as many KDA key "
                         "heads as query heads")
    if config["rotary_dim"] != config["qk_rope_head_dim"] \
            or config.get("use_mla_nope"):
        raise ValueError("the program turns the qk_rope_head_dim columns")
    L = config["num_hidden_layers"]
    return LingLinearConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=config[
            "moe_shared_expert_intermediate_size"],
        num_hidden_layers=L,
        first_k_dense_replace=min(config["first_k_dense_replace"], L),
        layer_group_size=config["layer_group_size"],
        num_heads=config["num_attention_heads"],
        head_dim=config["head_dim"],
        short_conv_kernel_size=config["short_conv_kernel_size"],
        kda_safe_gate=config["kda_safe_gate"],
        kda_lower_bound=config["kda_lower_bound"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        num_experts=config.get("router_num_experts", config["num_experts"]),
        experts_held=config["num_experts"],
        expert_offset=config.get("expert_offset", 0),
        num_shared_experts=config.get("num_shared_experts", 1),
        num_experts_per_tok=config["num_experts_per_tok"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scaling_factor=config["routed_scaling_factor"],
        swiglu_limits=tuple(config.get("expert_swiglu_limit_list", [])[:L])
        + tuple(config.get("share_expert_swiglu_limit_list", [])[:L]),
        rope_theta=config["rope_theta"],
        rope_interleave=config.get("rope_interleave", True),
        rms_norm_eps=config["rms_norm_eps"],
        latent_norm_eps=config["rms_norm_eps"],
        max_position_embeddings=config["max_position_embeddings"],
        initializer_range=config.get("initializer_range", 0.02),
        dtype=model.dtype_of(config))


def program_layer(config: Dict[str, Any], w: Dict) -> Dict:
    """One layer of the reference's draw as the program lays it out."""
    import jax.numpy as jnp
    z = _z(config)
    w = dict(w)
    if "kv_b_w" in w:
        kvb = w.pop("kv_b_w").reshape(z["RKV"], z["NH"], z["DN"] + z["DV"])
        w["uk_w"] = kvb[..., :z["DN"]].transpose(1, 0, 2)
        w["uv_w"] = kvb[..., z["DN"]:].transpose(1, 0, 2)
    else:
        w["qkv_w"] = jnp.concatenate(
            [w.pop(n) for n in ("q_w", "k_w", "v_w")], axis=1)
        w["conv_w"] = jnp.concatenate(
            [w.pop(n) for n in ("conv_q_w", "conv_k_w", "conv_v_w")],
            axis=0)
    return w


def make_params(config: Dict[str, Any], seed: int):
    """The reference's weights for ``seed`` as the program's tree, on
    the default device, in one jitted call (a run's layers drawn one
    after the other, so that one layer's temporaries live at a time)."""
    import jax
    import jax.numpy as jnp
    ref = model.reference_module(config)
    dt = jnp.dtype(model.dtype_of(config))
    cfg = program_config(config)

    @jax.jit
    def draw(key):
        runs = tuple(jax.lax.map(
            lambda j, kind=kind: program_layer(
                config, ref.layer_weights(config, key, j, dt, kind)),
            first + jnp.arange(n, dtype=jnp.int32))
            for kind, n, first in cfg.runs())
        return dict(ref.outer_weights(config, key, dt), runs=runs)

    return draw(ref.seed_key(seed))


def build_engine(cfg, params, engine: Dict[str, Any]):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    return ContinuousBatchingEngine(
        cfg, params, max_batch=engine["max_batch"],
        block_size=engine["block_size"], num_blocks=engine["num_blocks"],
        max_blocks_per_seq=engine["max_blocks_per_seq"],
        prefill_buckets=tuple(engine["prefill_buckets"]))


class DecayAfterUpdate:
    """The planted fault: a token's decay applied AFTER its rank-one
    update (``S <- S + beta k (v - S^T k)^T; S <- Diag(a) S``) instead
    of before it, in the fills and in the decode step alike (both as the
    recurrence, a token at a time).  Put in place before the engine's
    programs are traced (``wrap_engine`` runs between construction and
    warm-up; the model calls the two ops through their module)."""
    control = ""

    def wrap_engine(self, eng) -> None:
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops import kda
        if hasattr(kda.kda_chunk_scan, "sound"):
            return
        sound = (kda.kda_chunk_scan, kda.kda_state_update_row)
        f32 = jnp.float32

        def update(q, k, v, log_a, beta, state):
            q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
            r = v - jnp.sum(state * k[..., None], axis=-2)
            new = state + (beta.astype(f32)[..., None] * k)[..., None] \
                * r[..., None, :]
            new = new * jnp.exp(log_a.astype(f32))[..., None]
            return jnp.sum(new * q[..., None], axis=-2), new

        def scan(q, k, v, log_a, beta, state, **_):
            def step(s, inp):
                o, s = update(*inp, s)
                return s, o
            mv = lambda a: jnp.moveaxis(a, 1, 0)
            state, o = jax.lax.scan(step, state.astype(f32), (
                mv(q), mv(k), mv(v), mv(log_a), mv(beta)))
            return jnp.moveaxis(o, 0, 1), state

        def row_update(q, k, v, log_a, beta, states, row, **_):
            o, new = update(q, k, v, log_a, beta,
                            jax.lax.dynamic_index_in_dim(
                                states, row, 0, keepdims=False))
            return o, jax.lax.dynamic_update_index_in_dim(
                states, new, row, 0)

        scan.sound = sound
        kda.kda_chunk_scan, kda.kda_state_update_row = scan, row_update

    @staticmethod
    def unwrap() -> None:
        """Put the sound ops back (the tests' clean-up)."""
        from paddle_tpu.ops import kda
        sound = getattr(kda.kda_chunk_scan, "sound", None)
        if sound is not None:
            kda.kda_chunk_scan, kda.kda_state_update_row = sound


def planted_fault():
    return DecayAfterUpdate()


# ---------------------------------------------------------------------
# the work the model requires of this share, from shapes alone
# ---------------------------------------------------------------------
def _z(cfg: Dict) -> Dict[str, Any]:
    return model.reference_module(cfg).sizes(cfg)


def mix_params(cfg: Dict, mixer: str) -> int:
    """Weights of one layer's mixer that a token is multiplied with (a
    latent layer's ``kv_b`` once: to decompress its latent, or to fold
    its query and lift its output)."""
    z = _z(cfg)
    H, nh = z["H"], z["NH"]
    if mixer == "kda":
        return 4 * H * z["W"] + z["W"] * H + 2 * H * nh
    return H * nh * (z["DN"] + z["DR"]) + H * (z["RKV"] + z["DR"]) \
        + z["RKV"] * nh * (z["DN"] + z["DV"]) + nh * z["DV"] * H + H * nh


def dense_mlp_params(cfg: Dict) -> int:
    z = _z(cfg)
    return 3 * z["H"] * z["F"]


def expert_params(cfg: Dict) -> int:
    z = _z(cfg)
    return 3 * z["H"] * z["FE"]


def expert_layer_fixed_params(cfg: Dict) -> int:
    """Router and shared expert: what every token of an expert layer
    meets."""
    z = _z(cfg)
    return z["H"] * z["E"] + 3 * z["H"] * z["FS"]


def experts_per_token(cfg: Dict) -> float:
    """A token's expected choices among the held experts."""
    z = _z(cfg)
    return z["K"] * z["EH"] / z["E"]


def matmul_params_per_token(cfg: Dict) -> float:
    z = _z(cfg)
    per = sum(mix_params(cfg, m) for m in z["mixers"]) \
        + z["KD"] * dense_mlp_params(cfg)
    per += (z["L"] - z["KD"]) * (
        expert_layer_fixed_params(cfg)
        + experts_per_token(cfg) * expert_params(cfg))
    return per + z["H"] * z["V"]


#: one step of the recurrence, an element of the state: the decay (1),
#: ``S^T k`` (2), the rank-one update (2), ``S^T q`` (2)
STATE_FLOPS = 7


def kda_flops_per_token(cfg: Dict) -> int:
    """One step of the recurrence and the three convs (2 a tap), every
    KDA layer."""
    z = _z(cfg)
    return z["mixers"].count("kda") * (
        STATE_FLOPS * z["NH"] * z["D"] * z["D"] + 2 * 3 * z["W"] * z["CW"])


def pair_flops(cfg: Dict, form: str) -> int:
    """Attention FLOPs of one (query, cached token) pair, one latent
    layer."""
    z = _z(cfg)
    if form == "expanded":
        return 2 * z["NH"] * (z["DN"] + z["DR"] + z["DV"])
    return 2 * z["NH"] * (2 * z["RKV"] + z["DR"])          # absorbed


def forward_flops(cfg: Dict, n_tokens: int, pairs: int, form: str) -> float:
    z = _z(cfg)
    return (2 * matmul_params_per_token(cfg)
            + kda_flops_per_token(cfg)) * int(n_tokens) \
        + z["mixers"].count("attention") * pair_flops(cfg, form) * int(pairs)


def prefill_flops(cfg: Dict, prompt_len: int) -> float:
    return forward_flops(cfg, prompt_len,
                         prompt_len * (prompt_len + 1) // 2, "expanded")


def decode_flops(cfg: Dict, contexts: Iterable[int]) -> float:
    contexts = list(contexts)
    return forward_flops(cfg, len(contexts), sum(contexts), "absorbed")


def experts_hit_share(cfg: Dict, slots: int) -> float:
    """Expected share of the held experts that at least one of
    ``slots`` tokens chose."""
    z = _z(cfg)
    return 1.0 - (1.0 - z["K"] / z["E"]) ** slots


def weight_bytes(cfg: Dict, slots: int, itemsize: int = 2) -> float:
    z = _z(cfg)
    fixed = sum(mix_params(cfg, m) for m in z["mixers"]) \
        + z["KD"] * dense_mlp_params(cfg) \
        + (z["L"] - z["KD"]) * expert_layer_fixed_params(cfg) \
        + z["H"] * z["V"]
    experts = (z["L"] - z["KD"]) * z["EH"] * expert_params(cfg) \
        * experts_hit_share(cfg, slots)
    return (fixed + experts) * itemsize


def kda_state_update_work(cfg: Dict, slots: int) -> Dict[str, int]:
    """What the kernel ``kda_state_update`` must do in a decode step:
    the float32 state of ``slots`` sequences read once and written once,
    every KDA layer (the sides, 2 KB a head, left out), and the
    recurrence's 7 FLOPs an element."""
    z = _z(cfg)
    elements = z["mixers"].count("kda") * slots * z["NH"] * z["D"] * z["D"]
    return {"flops": STATE_FLOPS * elements, "bytes": 2 * 4 * elements}


def state_bytes(cfg: Dict, slots: int, itemsize: int = 2) -> int:
    """Recurrent state (float32) and conv tails of ``slots`` sequences,
    every KDA layer, read and written."""
    z = _z(cfg)
    tails = 2 * z["mixers"].count("kda") * slots \
        * 3 * z["W"] * (z["CW"] - 1) * itemsize
    return kda_state_update_work(cfg, slots)["bytes"] + tails


def latent_bytes(cfg: Dict, tokens: int, itemsize: int = 2) -> int:
    """The latent cache of ``tokens`` cached tokens, every latent
    layer."""
    z = _z(cfg)
    return z["mixers"].count("attention") * (z["RKV"] + z["DR"]) \
        * itemsize * int(tokens)


def decode_bytes(cfg: Dict, contexts: Iterable[int],
                 itemsize: int = 2) -> float:
    contexts = list(contexts)
    n = len(contexts)
    return weight_bytes(cfg, n, itemsize) + state_bytes(cfg, n, itemsize) \
        + latent_bytes(cfg, sum(contexts), itemsize)


# ---------------------------------------------------------------------
# what the harness puts into readings["work"], under these names
# ---------------------------------------------------------------------
def request_work(cfg: Dict, prompt_len: int, new_tokens: int
                 ) -> Dict[str, float]:
    return {"flops": prefill_flops(cfg, prompt_len) + decode_flops(
        cfg, [prompt_len + j for j in range(1, new_tokens)])}


def decode_step_work(cfg: Dict, contexts: Iterable[int]
                     ) -> Dict[str, float]:
    contexts = list(contexts)
    kernel = kda_state_update_work(cfg, len(contexts))
    return {"decode_flops": decode_flops(cfg, contexts),
            "decode_bytes": decode_bytes(cfg, contexts),
            "kda_state_update_flops": kernel["flops"],
            "kda_state_update_bytes": kernel["bytes"]}
