"""Operations and bytes the algorithm needs, from shapes alone.

These count what the model REQUIRES, not what a kernel happens to do:
recomputation is not counted, causal attention is counted once (each
query against the keys it may see), the embedding lookup is not a
matmul.  Checked against hand counts in ``tests/test_flops.py``."""

from __future__ import annotations

from typing import Dict, Iterable


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
