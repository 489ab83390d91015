"""The program module of the hybrid family (``granitemoehybrid``:
Mamba-2 and attention layers, experts after every one): the one place
under ``benchmark/`` that imports the model and engine classes of
``paddle_tpu`` for it.  Serving only — the program has no train step for
this family, so this module offers no ``build_train_step``.

* ``program_config(config)``, ``make_params(config, seed)``: the
  program's ``GraniteHybridConfig`` and the reference's draw of the
  weights as the family's tree (``{"wte", "lnf_w", "runs": (run, ...)}``,
  one ``run`` a stretch of layers of one kind, leaves stacked ``[n,
  ...]``), made on the device in ONE jitted call in the served dtype;
* ``build_engine(cfg, params, engine)``: the same
  ``ContinuousBatchingEngine`` as every serving cell, with
  ``assumed.engine``;
* ``request_work`` / ``decode_step_work``: the work the model REQUIRES
  of this chip's share (below), checked against hand counts in
  ``tests/test_granite_cell.py``.

The share: the file's ``num_local_experts`` experts are held of the
router's ``router_num_experts``; a token's expected work is its ``k``
choices times ``held / router`` experts.  A decode step must read the
weights once — of the held experts only those that some slot chose, in
expectation ``1 - (1 - k / router) ** slots`` of them — the recurrent
state and conv tail of every live slot read AND written, and the live
K/V of the attention layers.  The one Pallas kernel of the family,
``ssm_state_update``, has its own two keys."""

from __future__ import annotations

from typing import Any, Dict, Iterable

from ..lib import model


# ---------------------------------------------------------------------
# the program: its configuration, its weights, its engine
# ---------------------------------------------------------------------
def program_config(config: Dict[str, Any]):
    from paddle_tpu.models.granite_hybrid import GraniteHybridConfig
    if config.get("position_embedding_type") != "nope":
        raise ValueError("the program's hybrid attends without positions")
    return GraniteHybridConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        shared_intermediate_size=config["shared_intermediate_size"],
        layer_types=tuple(config["layer_types"]),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        num_local_experts=config.get("router_num_experts",
                                     config["num_local_experts"]),
        experts_held=config["num_local_experts"],
        expert_offset=config.get("expert_offset", 0),
        num_experts_per_tok=config["num_experts_per_tok"],
        mamba_n_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_n_groups=config["mamba_n_groups"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_chunk_size=config["mamba_chunk_size"],
        embedding_multiplier=config["embedding_multiplier"],
        attention_multiplier=config["attention_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        logits_scaling=config["logits_scaling"],
        rms_norm_eps=config["rms_norm_eps"],
        max_position_embeddings=config["max_position_embeddings"],
        initializer_range=config.get("initializer_range", 0.02),
        dtype=model.dtype_of(config))


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
        runs, i = [], 0
        for kind, n, _ in cfg.runs():
            runs.append(jax.lax.map(
                lambda j, kind=kind: ref.layer_weights(config, key, j, dt,
                                                       kind),
                i + jnp.arange(n, dtype=jnp.int32)))
            i += n
        return dict(ref.outer_weights(config, key, dt), runs=tuple(runs))

    return draw(ref.seed_key(seed))


def build_engine(cfg, params, engine: Dict[str, Any]):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    return ContinuousBatchingEngine(
        cfg, params, max_batch=engine["max_batch"],
        block_size=engine["block_size"], num_blocks=engine["num_blocks"],
        max_blocks_per_seq=engine["max_blocks_per_seq"],
        prefill_buckets=tuple(engine["prefill_buckets"]))


# ---------------------------------------------------------------------
# the work the model requires of this share, from shapes alone
# ---------------------------------------------------------------------
def _z(cfg: Dict) -> Dict[str, Any]:
    return model.reference_module(cfg).sizes(cfg)


def mix_params(cfg: Dict, kind: str) -> int:
    """Weights of one layer's mixer that a token is multiplied with."""
    z = _z(cfg)
    if kind == "mamba":
        return z["H"] * (z["DI"] + z["C"] + z["MH"]) + z["DI"] * z["H"]
    return 2 * z["H"] * z["NH"] * z["D"] + 2 * z["H"] * z["KVH"] * z["D"]


def expert_params(cfg: Dict) -> int:
    z = _z(cfg)
    return 3 * z["H"] * z["F"]


def dense_ffn_params(cfg: Dict) -> int:
    """Router and shared MLP: what every token of a layer meets."""
    z = _z(cfg)
    return z["H"] * z["E"] + 3 * z["H"] * z["FS"]


def experts_per_token(cfg: Dict) -> float:
    """A token's expected choices among the held experts."""
    z = _z(cfg)
    return z["K"] * z["EH"] / z["E"]


def matmul_params_per_token(cfg: Dict) -> float:
    """Weights a token is multiplied with on this chip: every layer's
    mixer, router, shared MLP and its expected held experts, and the
    tied table as the output head."""
    z = _z(cfg)
    per = sum(mix_params(cfg, k) for k in z["types"])
    per += z["L"] * (dense_ffn_params(cfg)
                     + experts_per_token(cfg) * expert_params(cfg))
    return per + z["H"] * z["V"]


def ssm_flops_per_token(cfg: Dict) -> int:
    """One step of the recurrence and the conv, every Mamba layer: the
    state decays and takes ``dt x B^T`` (3 FLOPs an element), ``S C`` (2),
    the conv 2 a tap."""
    z = _z(cfg)
    return z["types"].count("mamba") * (
        5 * z["MH"] * z["MP"] * z["N"] + 2 * z["C"] * z["W"])


def attention_flops(cfg: Dict, contexts_sum: int) -> int:
    z = _z(cfg)
    return 4 * z["NH"] * z["D"] * z["types"].count("attention") \
        * int(contexts_sum)


def forward_flops(cfg: Dict, n_tokens: int, contexts_sum: int) -> float:
    return (2 * matmul_params_per_token(cfg)
            + ssm_flops_per_token(cfg)) * int(n_tokens) \
        + attention_flops(cfg, contexts_sum)


def prefill_flops(cfg: Dict, prompt_len: int) -> float:
    return forward_flops(cfg, prompt_len,
                         prompt_len * (prompt_len + 1) // 2)


def decode_flops(cfg: Dict, contexts: Iterable[int]) -> float:
    contexts = list(contexts)
    return forward_flops(cfg, len(contexts), sum(contexts))


def experts_hit_share(cfg: Dict, slots: int) -> float:
    """Expected share of the held experts that at least one of
    ``slots`` tokens chose."""
    z = _z(cfg)
    return 1.0 - (1.0 - z["K"] / z["E"]) ** slots


def weight_bytes(cfg: Dict, slots: int, itemsize: int = 2) -> float:
    z = _z(cfg)
    fixed = sum(mix_params(cfg, k) for k in z["types"]) \
        + z["L"] * dense_ffn_params(cfg) + z["H"] * z["V"]
    experts = z["L"] * z["EH"] * expert_params(cfg) \
        * experts_hit_share(cfg, slots)
    return (fixed + experts) * itemsize


def state_bytes(cfg: Dict, slots: int, itemsize: int = 2) -> int:
    """Recurrent state (float32) and conv tail of ``slots`` sequences,
    every Mamba layer, read and written."""
    z = _z(cfg)
    row = z["MH"] * z["MP"] * z["N"] * 4 + z["C"] * (z["W"] - 1) * itemsize
    return 2 * z["types"].count("mamba") * slots * row


def ssm_state_update_work(cfg: Dict, slots: int) -> Dict[str, int]:
    """What the kernel ``ssm_state_update`` must do in a decode step:
    the float32 state of ``slots`` sequences read once and written once,
    every Mamba layer (the sides, a few hundred bytes a head, left out),
    and the recurrence's 5 FLOPs an element."""
    z = _z(cfg)
    elements = z["types"].count("mamba") * slots \
        * z["MH"] * z["MP"] * z["N"]
    return {"flops": 5 * elements, "bytes": 2 * 4 * elements}


def kv_bytes(cfg: Dict, tokens: int, itemsize: int = 2) -> int:
    z = _z(cfg)
    return 2 * z["KVH"] * z["D"] * z["types"].count("attention") \
        * itemsize * int(tokens)


def decode_bytes(cfg: Dict, contexts: Iterable[int],
                 itemsize: int = 2) -> float:
    contexts = list(contexts)
    n = len(contexts)
    return weight_bytes(cfg, n, itemsize) + state_bytes(cfg, n, itemsize) \
        + kv_bytes(cfg, sum(contexts), itemsize)


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
    kernel = ssm_state_update_work(cfg, len(contexts))
    return {"decode_flops": decode_flops(cfg, contexts),
            "decode_bytes": decode_bytes(cfg, contexts),
            "ssm_state_update_flops": kernel["flops"],
            "ssm_state_update_bytes": kernel["bytes"]}
