"""The program module of the latent-attention family (``glm4_moe_lite``:
MLA in every layer, a leading dense layer, sigmoid-routed experts beside
a shared one): the one place under ``benchmark/`` that imports the model
and engine classes of ``paddle_tpu`` for it.  Serving only — the program
has no train step for this family, so this module offers no
``build_train_step``.

* ``program_config(config)``, ``make_params(config, seed)``: the
  program's ``GlmMoeLiteConfig`` and the reference's draw of the weights
  as the family's tree (``{"wte", "head", "lnf_w", "runs": (run, ...)}``,
  a run the layers of one kind, leaves stacked ``[n, ...]``; the
  reference's ``kv_b_w [r_kv, nh (d_n + d_v)]`` cut per head into
  ``uk_w [nh, r_kv, d_n]`` and ``uv_w [nh, r_kv, d_v]``), made on the
  device in ONE jitted call in the served dtype;
* ``build_engine(cfg, params, engine)``: the same
  ``ContinuousBatchingEngine`` as every serving cell, with
  ``assumed.engine``;
* ``request_work`` / ``decode_step_work``: the work the model REQUIRES,
  checked against hand counts in ``tests/test_glm_cell.py``;
* ``planted_fault()``: hooks for ``calibrate_fault.py`` (the router's
  bias added to the weights too).

The required work: a token meets every layer's latent projections (the
per-head ``W_uk`` / ``W_uv`` once a token either way: to decompress its
own latent, or to fold its query and lift its output), the dense layer's
MLP or an expert layer's router, shared expert and ``k`` chosen experts,
and the head.  Attention a (query, cached token) pair: the expanded
form's ``2 nh (d_n + d_r + d_v)`` in a prefill, whose tokens are
decompressed once each (counted above); the absorbed form's ``2 nh (2
r_kv + d_r)`` in a decode step, which decompresses nothing.  A decode
step must read the weights once — of an expert layer's experts only
those that some slot chose, in expectation ``1 - (1 - k / E) ** slots``
of them — and the live latent cache once."""

from __future__ import annotations

from typing import Any, Dict, Iterable

from ..lib import model


# ---------------------------------------------------------------------
# the program: its configuration, its weights, its engine
# ---------------------------------------------------------------------
def program_config(config: Dict[str, Any]):
    from paddle_tpu.models.glm_moe_lite import GlmMoeLiteConfig
    if config.get("rope_scaling") is not None \
            or config.get("partial_rotary_factor", 1) != 1:
        raise ValueError("the program turns every rotary column, unscaled")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention has one latent for all heads")
    if config.get("num_nextn_predict_layers", 0):
        raise ValueError("the program holds no multi-token-prediction "
                         "layer: the configuration cuts it to 0")
    return GlmMoeLiteConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        first_k_dense_replace=min(config["first_k_dense_replace"],
                                  config["num_hidden_layers"]),
        num_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        n_routed_experts=config["n_routed_experts"],
        n_shared_experts=config["n_shared_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scaling_factor=config["routed_scaling_factor"],
        rope_theta=config["rope_theta"],
        rope_interleave=config.get("rope_interleave", True),
        rms_norm_eps=config["rms_norm_eps"],
        latent_norm_eps=config.get("latent_norm_eps", 1e-6),
        max_position_embeddings=config["max_position_embeddings"],
        initializer_range=config.get("initializer_range", 0.02),
        dtype=model.dtype_of(config))


def program_layer(config: Dict[str, Any], w: Dict) -> Dict:
    """One layer of the reference's draw as the program lays it out."""
    z = _z(config)
    w = dict(w)
    kvb = w.pop("kv_b_w").reshape(z["RKV"], z["NH"], z["DN"] + z["DV"])
    w["uk_w"] = kvb[..., :z["DN"]].transpose(1, 0, 2)
    w["uv_w"] = kvb[..., z["DN"]:].transpose(1, 0, 2)
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


class BiasInWeights:
    """The planted fault: the router's bias added to the WEIGHTS of the
    chosen experts too, not to the choice alone.  Put in place before
    the engine's programs are traced (``wrap_engine`` runs between
    construction and warm-up; the model calls the gate through its
    module)."""
    control = ""

    def wrap_engine(self, eng) -> None:
        import jax.numpy as jnp
        from paddle_tpu.parallel import moe
        sound = getattr(moe.route_sigmoid, "sound", moe.route_sigmoid)

        def faulty(logits, bias, top_k, *, normalize=True, scale=1.0,
                   **kw):
            w, idx = sound(logits, bias, top_k, normalize=False, **kw)
            w = w + bias.astype(jnp.float32)[idx]
            if normalize:
                w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
            return w * scale, idx

        faulty.sound = sound
        moe.route_sigmoid = faulty


def planted_fault():
    return BiasInWeights()


# ---------------------------------------------------------------------
# the work the model requires, from shapes alone
# ---------------------------------------------------------------------
def _z(cfg: Dict) -> Dict[str, Any]:
    return model.reference_module(cfg).sizes(cfg)


def mla_params(cfg: Dict) -> int:
    """Weights of one layer's attention that a token is multiplied
    with (``kv_b`` once: to decompress its latent, or to fold its query
    and lift its output)."""
    z = _z(cfg)
    return z["H"] * z["RQ"] + z["RQ"] * z["NH"] * (z["DN"] + z["DR"]) \
        + z["H"] * (z["RKV"] + z["DR"]) \
        + z["RKV"] * z["NH"] * (z["DN"] + z["DV"]) \
        + z["NH"] * z["DV"] * z["H"]


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


def matmul_params_per_token(cfg: Dict) -> int:
    z = _z(cfg)
    per = z["L"] * mla_params(cfg) + z["KD"] * dense_mlp_params(cfg)
    per += (z["L"] - z["KD"]) * (expert_layer_fixed_params(cfg)
                                 + z["K"] * expert_params(cfg))
    return per + z["H"] * z["V"]


def pair_flops(cfg: Dict, form: str) -> int:
    """Attention FLOPs of one (query, cached token) pair, one layer."""
    z = _z(cfg)
    if form == "expanded":
        return 2 * z["NH"] * (z["DN"] + z["DR"] + z["DV"])
    return 2 * z["NH"] * (2 * z["RKV"] + z["DR"])          # absorbed


def prefill_flops(cfg: Dict, prompt_len: int) -> float:
    z = _z(cfg)
    pairs = prompt_len * (prompt_len + 1) // 2
    return 2 * matmul_params_per_token(cfg) * prompt_len \
        + z["L"] * pair_flops(cfg, "expanded") * pairs


def decode_flops(cfg: Dict, contexts: Iterable[int]) -> float:
    z = _z(cfg)
    contexts = list(contexts)
    return 2 * matmul_params_per_token(cfg) * len(contexts) \
        + z["L"] * pair_flops(cfg, "absorbed") * sum(contexts)


def experts_hit_share(cfg: Dict, slots: int) -> float:
    """Expected share of a layer's experts that at least one of
    ``slots`` tokens chose."""
    z = _z(cfg)
    return 1.0 - (1.0 - z["K"] / z["E"]) ** slots


def weight_bytes(cfg: Dict, slots: int, itemsize: int = 2) -> float:
    z = _z(cfg)
    fixed = z["L"] * mla_params(cfg) + z["KD"] * dense_mlp_params(cfg) \
        + (z["L"] - z["KD"]) * expert_layer_fixed_params(cfg) \
        + z["H"] * z["V"]
    experts = (z["L"] - z["KD"]) * z["E"] * expert_params(cfg) \
        * experts_hit_share(cfg, slots)
    return (fixed + experts) * itemsize


def latent_bytes(cfg: Dict, tokens: int, itemsize: int = 2) -> int:
    """The latent cache of ``tokens`` cached tokens, every layer."""
    z = _z(cfg)
    return z["L"] * (z["RKV"] + z["DR"]) * itemsize * int(tokens)


def decode_bytes(cfg: Dict, contexts: Iterable[int],
                 itemsize: int = 2) -> float:
    contexts = list(contexts)
    return weight_bytes(cfg, len(contexts), itemsize) \
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
    """One decode step; ``mla_decode_*`` are the absorbed attention's
    own share of it (every layer's pairs; the live latent read once)."""
    contexts = list(contexts)
    z = _z(cfg)
    return {"decode_flops": decode_flops(cfg, contexts),
            "decode_bytes": decode_bytes(cfg, contexts),
            "mla_decode_flops":
                z["L"] * pair_flops(cfg, "absorbed") * sum(contexts),
            "mla_decode_bytes": latent_bytes(cfg, sum(contexts))}
