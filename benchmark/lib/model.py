"""From a configuration file to what the program's entry points take.

The file holds the model's published keys (as in its ``config.json``)
and, under ``assumed``, what a deployment sets itself.  The weights are
the reference's draw from the seed, made on the device in ONE jitted
call in the served dtype and laid out as the program's entry points
expect them (``{"wte", "head", "lnf_w", "blocks": {name: [1, L, ...]}}``).
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Dict

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def dtype_of(config: Dict[str, Any]) -> str:
    """The dtype the configuration states, as JAX names it."""
    name = config["torch_dtype"]
    if name not in ("bfloat16", "float32", "float16"):
        raise ValueError(f"unknown torch_dtype {name!r}")
    return name


def load_json(kind: str, name: str, root: str = HERE) -> Dict[str, Any]:
    """``<root>/<kind>/<name>.json`` — how every cell, configuration,
    traffic mix and metric is found: by its name."""
    path = os.path.join(root, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def reference_module(config: Dict[str, Any]):
    return importlib.import_module(
        f"benchmark.reference.{config['reference']}")


def program_config(config: Dict[str, Any]):
    """The program's ``LlamaConfig`` for a published config."""
    from paddle_tpu.models.llama import LlamaConfig
    if config.get("sliding_window") is not None:
        raise ValueError("the program has no sliding-window attention")
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"],
        initializer_range=config.get("initializer_range", 0.02),
        tie_word_embeddings=config.get("tie_word_embeddings", False),
        dtype=dtype_of(config))


def make_params(config: Dict[str, Any], seed: int):
    """The reference's weights for ``seed`` as the program's tree, on
    the default device, in one jitted call."""
    import jax
    import jax.numpy as jnp
    ref = reference_module(config)
    dt = jnp.dtype(dtype_of(config))
    L = ref.sizes(config)["L"]

    @jax.jit
    def draw(key):
        blocks = jax.vmap(lambda i: ref.layer_weights(config, key, i, dt))(
            jnp.arange(L, dtype=jnp.int32))
        out = dict(ref.outer_weights(config, key, dt))
        out["blocks"] = {k: v[None] for k, v in blocks.items()}
        return out

    return draw(ref.seed_key(seed))
