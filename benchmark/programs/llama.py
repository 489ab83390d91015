"""The program module of the dense GQA decoder family (Llama, Mistral):
the one place under ``benchmark/`` that imports the model and engine
classes of ``paddle_tpu`` for it.  A configuration file names its
program module (``"program": "llama"``); the harness reaches the program
only through what this module offers (``lib/model.py:program_module``):

* ``program_config(config)``, ``make_params(config, seed)``: the
  program's own configuration object and the reference's draw of the
  weights, laid out as the family's entry points take them
  (``{"wte", "head", "lnf_w", "blocks": {name: [1, L, ...]}}``), made
  on the device in ONE jitted call in the served dtype;
* ``build_engine(cfg, params, engine)``, ``build_train_step(cfg, topo,
  train)``: the calls into the program, with ``assumed.engine`` /
  ``assumed.train`` of the configuration's file; ``first_grad_norms``
  and ``param_change_norms`` read the train state's layout for the
  comparison, under the reference's leaf names;
* the work the model REQUIRES (below), and the three sums of it that
  the harness puts into ``readings["work"]`` under their own names:
  ``request_work``, ``decode_step_work``, ``train_step_work``.

The counts are what the model requires, not what a kernel happens to
do: recomputation is not counted, causal attention is counted once
(each query against the keys it may see), the embedding lookup is not
a matmul.  Checked against hand counts in ``tests/test_flops.py``."""

from __future__ import annotations

from typing import Any, Dict, Iterable

from ..lib import model


# ---------------------------------------------------------------------
# the program: its configuration, its weights, its entry points
# ---------------------------------------------------------------------
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
        dtype=model.dtype_of(config))


def make_params(config: Dict[str, Any], seed: int):
    """The reference's weights for ``seed`` as the program's tree, on
    the default device, in one jitted call."""
    import jax
    import jax.numpy as jnp
    ref = model.reference_module(config)
    dt = jnp.dtype(model.dtype_of(config))
    L = ref.sizes(config)["L"]

    @jax.jit
    def draw(key):
        blocks = jax.vmap(lambda i: ref.layer_weights(config, key, i, dt))(
            jnp.arange(L, dtype=jnp.int32))
        out = dict(ref.outer_weights(config, key, dt))
        out["blocks"] = {k: v[None] for k, v in blocks.items()}
        return out

    return draw(ref.seed_key(seed))


def build_engine(cfg, params, engine: Dict[str, Any]):
    """The serving engine as ``serving/http.py:build_frontend`` builds
    it, with the operator's settings of ``assumed.engine``."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    return ContinuousBatchingEngine(
        cfg, params, max_batch=engine["max_batch"],
        block_size=engine["block_size"], num_blocks=engine["num_blocks"],
        max_blocks_per_seq=engine["max_blocks_per_seq"],
        prefill_buckets=tuple(engine["prefill_buckets"]))


def build_train_step(cfg, topo, train: Dict[str, Any]):
    """``(step_fn, init_fn)`` of the hybrid train step, with the
    settings of ``assumed.train``."""
    from paddle_tpu.models.llama import build_llama_train_step
    return build_llama_train_step(
        cfg, topo, num_microbatches=train["num_microbatches"],
        remat=train["remat"], sharding_stage=train["sharding_stage"],
        learning_rate=train["learning_rate"])


def first_grad_norms(state, train: Dict[str, Any]) -> Dict[str, Any]:
    """Per leaf (``{name: [per layer]}`` for the blocks): the norm of
    the first gradient as Adam got it, from the first moment after one
    step, m1 = (1 - b1) g.  On one chip a moment buffer is its leaf's
    rows, in order."""
    import jax
    import jax.numpy as jnp
    b1 = train["adam_betas"][0]

    @jax.jit
    def grad_norms(m, params):
        out = {}
        for k, p in params.items():
            if k == "blocks":
                for n, q in p.items():
                    g = m["blocks"][n].reshape(q.shape) / (1 - b1)
                    out[n] = jnp.sqrt(jnp.sum(
                        jnp.square(g), axis=tuple(range(2, q.ndim))))[0]
            else:
                out[k] = jnp.sqrt(jnp.sum(jnp.square(
                    m[k].reshape(p.shape) / (1 - b1))))
        return out

    return grad_norms(state["opt"]["m"], state["params"])


def param_change_norms(state, fresh) -> Dict[str, Any]:
    """Per leaf: the norm of the parameters' change from ``fresh`` (the
    seed's draw, ``make_params``)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def delta_norms(params, fresh):
        def nrm(a, b, axes):
            return jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - b.astype(jnp.float32)),
                axis=axes))
        out = {k: nrm(p, fresh[k], None) for k, p in params.items()
               if k != "blocks"}
        for n, q in params["blocks"].items():
            out[n] = nrm(q, fresh["blocks"][n],
                         tuple(range(2, q.ndim)))[0]
        return out

    return delta_norms(state["params"], fresh)


# ---------------------------------------------------------------------
# the work the model requires, from shapes alone
# ---------------------------------------------------------------------


def _z(cfg: Dict) -> Dict[str, int]:
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(H=h, F=cfg["intermediate_size"], NH=nh,
                KVH=cfg.get("num_key_value_heads") or nh,
                D=cfg.get("head_dim") or h // nh, V=cfg["vocab_size"],
                L=cfg["num_hidden_layers"])


def matmul_params(cfg: Dict) -> int:
    """Weights a token is multiplied with: every layer's projections and
    the output head (norm gains and the embedding table are not)."""
    z = _z(cfg)
    per_layer = (z["H"] * z["NH"] * z["D"] * 2          # q, o
                 + z["H"] * z["KVH"] * z["D"] * 2       # k, v
                 + 3 * z["H"] * z["F"])                 # gate, up, down
    return z["L"] * per_layer + z["H"] * z["V"]


def attention_flops(cfg: Dict, contexts_sum: int) -> int:
    """Forward attention FLOPs for queries whose context lengths (keys
    seen, itself included) sum to ``contexts_sum``: QK^T and PV, 2 FLOPs
    a multiply-add, every layer."""
    z = _z(cfg)
    return 4 * z["NH"] * z["D"] * z["L"] * int(contexts_sum)


def causal_contexts(seq_len: int) -> int:
    """Sum of context lengths over one causal sequence."""
    return seq_len * (seq_len + 1) // 2


def forward_flops(cfg: Dict, n_tokens: int, contexts_sum: int) -> int:
    return 2 * matmul_params(cfg) * int(n_tokens) \
        + attention_flops(cfg, contexts_sum)


def train_flops_per_token(cfg: Dict, seq_len: int) -> float:
    """Forward + backward (twice the forward) a token requires."""
    return 3 * forward_flops(cfg, seq_len, causal_contexts(seq_len)) \
        / seq_len


def train_attention_flops(cfg: Dict, batch: int, seq_len: int) -> int:
    """Causal attention, forward + backward, of one step."""
    return 3 * batch * attention_flops(cfg, causal_contexts(seq_len))


def prefill_flops(cfg: Dict, prompt_len: int) -> int:
    return forward_flops(cfg, prompt_len, causal_contexts(prompt_len))


def decode_flops(cfg: Dict, contexts: Iterable[int]) -> int:
    """One decode step over sequences with these context lengths."""
    contexts = list(contexts)
    return forward_flops(cfg, len(contexts), sum(contexts))


def weight_bytes(cfg: Dict, itemsize: int = 2) -> int:
    return matmul_params(cfg) * itemsize


def kv_bytes(cfg: Dict, tokens: int, itemsize: int = 2) -> int:
    """K and V of ``tokens`` cached positions, every layer."""
    z = _z(cfg)
    return 2 * z["KVH"] * z["D"] * z["L"] * itemsize * int(tokens)


def decode_bytes(cfg: Dict, contexts: Iterable[int],
                 itemsize: int = 2) -> int:
    """The least one decode step has to read: the weights once and the
    live K/V of its sequences."""
    return weight_bytes(cfg, itemsize) + kv_bytes(cfg, sum(contexts),
                                                  itemsize)


# ---------------------------------------------------------------------
# what the harness puts into readings["work"], under these names
# ---------------------------------------------------------------------
def request_work(cfg: Dict, prompt_len: int, new_tokens: int
                 ) -> Dict[str, float]:
    """One request as far as it got: its prompt and ``new_tokens``
    output tokens (the first comes out of the prefill).  Summed over
    the window's requests as ``window_<name>``."""
    return {"flops": prefill_flops(cfg, prompt_len) + decode_flops(
        cfg, [prompt_len + j for j in range(1, new_tokens)])}


def decode_step_work(cfg: Dict, contexts: Iterable[int]
                     ) -> Dict[str, float]:
    """One decode step over sequences of these context lengths.  Summed
    over the traced steps as ``traced_<name>``."""
    contexts = list(contexts)
    return {"decode_flops": decode_flops(cfg, contexts),
            "decode_bytes": decode_bytes(cfg, contexts)}


def train_step_work(cfg: Dict, batch: int, seq_len: int
                    ) -> Dict[str, float]:
    """One train step.  Times the steps before the trace as
    ``window_<name>``, times the traced steps as ``traced_<name>``."""
    return {"flops": train_flops_per_token(cfg, seq_len) * batch * seq_len,
            "attn_flops": train_attention_flops(cfg, batch, seq_len)}
