"""Serving-side quantization: the PTQ export path the engine consumes.

The layer-graph PTQ in this package (observers -> QuantedLayer ->
QuantizedLinear) serves the Layer/Predictor world; the continuous-
batching engine serves raw param PYTREES.  This module is the bridge:

* :class:`ServeQuantConfig` — the engine's ``quant_config`` ctor knob
  (weight dtype + group size + KV-pool dtype), hashed into the AOT
  ``engine_config`` so a warm start can never half-load a mismatched
  quantization.
* :func:`quantize_params_for_serving` — PTQ-export a zoo param tree to
  the ``<name>__q`` / ``<name>__s`` leaf convention that
  ``models.generation.build_llama_decoder(quant=...)`` and the quantized
  ``ops/decode_block`` matmul (``make_mm``) consume.  Scales are per-output-channel (or
  per (input-group, channel)) fp32 absmax — optionally the OBSERVER-
  calibrated per-channel absmax (:func:`calibrate_weight_thresholds`,
  the same ``PerChannelAbsMaxObserver`` statistic the layer-graph deploy
  path bakes), so calibration-time outlier clipping survives into the
  served tree.

Weight-only means exactly that: activations, norms, biases and the
embedding/head stay at the model dtype; only block matmul weights are
stored as int8 codes (or halves-packed int4 nibbles) + fp32 scales.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

__all__ = ["ServeQuantConfig", "quantize_params_for_serving",
           "calibrate_weight_thresholds", "dequantize_block_weight",
           "quantized_leaf_names"]

_WEIGHT_DTYPES = (None, "int8", "int4")
_KV_DTYPES = (None, "int8")
_GROUP_SIZES = (-1, 64, 128)


@dataclasses.dataclass(frozen=True)
class ServeQuantConfig:
    """The engine's quantization knob.

    ``weight_dtype``: None (full width) / "int8" / "int4" — storage of
    block matmul weights (``__q`` codes + ``__s`` fp32 scales).
    ``group_size``: -1 = one scale per output channel; 64/128 = one
    scale per (input-row group, channel).
    ``kv_dtype``: None / "int8" — paged-KV pool storage; int8 pools
    carry per-(token, head) fp32 scales (``ops.paged_kv.
    QuantizedKVPool``), chosen over per-page absmax so a rejected
    spec-decode draft can never retroactively requantize committed
    tokens.
    """
    weight_dtype: Optional[str] = None
    group_size: int = -1
    kv_dtype: Optional[str] = None

    def __post_init__(self):
        if self.weight_dtype not in _WEIGHT_DTYPES:
            raise ValueError(f"weight_dtype must be one of "
                             f"{_WEIGHT_DTYPES}, got {self.weight_dtype!r}")
        if self.kv_dtype not in _KV_DTYPES:
            raise ValueError(f"kv_dtype must be one of {_KV_DTYPES}, "
                             f"got {self.kv_dtype!r}")
        if self.group_size not in _GROUP_SIZES:
            raise ValueError(f"group_size must be one of {_GROUP_SIZES},"
                             f" got {self.group_size}")
        if self.weight_dtype is None and self.group_size != -1:
            raise ValueError("group_size without weight_dtype is "
                             "meaningless — set weight_dtype")

    @property
    def quantized_weights(self) -> bool:
        return self.weight_dtype is not None

    @property
    def quantized_kv(self) -> bool:
        return self.kv_dtype is not None

    @property
    def algo(self) -> Optional[str]:
        """The ``nn.quant.weight_quantize`` algo string."""
        if self.weight_dtype is None:
            return None
        return f"weight_only_{self.weight_dtype}"

    def describe(self) -> Dict[str, object]:
        """Stable dict for the AOT ``engine_config`` hash."""
        return {"weight_dtype": self.weight_dtype,
                "group_size": self.group_size,
                "kv_dtype": self.kv_dtype}


def quantized_leaf_names(name: str):
    """(codes, scales) leaf names for a quantized matmul weight."""
    return name + "__q", name + "__s"


def _is_block_matmul(name: str, v) -> bool:
    """A quantizable block leaf: a stacked matmul weight, not a norm
    gain / bias / already-quantized leaf (mirrors the predicate of
    ``models.generation.quantize_llama_params``)."""
    return (name.endswith("_w") and v.ndim >= 3
            and not name.startswith("ln") and "__" not in name)


def calibrate_weight_thresholds(params) -> Dict[str, np.ndarray]:
    """Observer-calibrated per-channel thresholds for every quantizable
    block weight: runs a ``PerChannelAbsMaxObserver`` over each layer's
    weight matrix (weight-only PTQ calibrates on the weights themselves)
    and returns ``{leaf name: [L, N] absmax}`` — the reference the
    round-trip test compares dequantized exports against."""
    from .observers import PerChannelAbsMaxObserver
    out: Dict[str, np.ndarray] = {}
    for name, v in params["blocks"].items():
        if not _is_block_matmul(name, v):
            continue
        flat = np.asarray(v).reshape((-1,) + v.shape[-2:])   # [L, K, N]
        rows = []
        for i in range(flat.shape[0]):
            obs = PerChannelAbsMaxObserver(axis=-1)
            obs.forward(jnp.asarray(flat[i]))
            rows.append(np.asarray(obs.cal_thresholds()).reshape(-1))
        out[name] = np.stack(rows)                           # [L, N]
    return out


def _quantize_matrix(w, config: ServeQuantConfig, thresholds=None):
    """One [K, N] matrix -> (codes, scales) under ``config``.

    Pure NUMPY, bit-for-bit the ``nn.quant.weight_quantize`` layout
    (absmax scales, halves-packed int4 nibbles, grouped [G, N] scales —
    pinned by the PTQ round-trip test through ``weight_dequantize``).
    Host-side on purpose: PTQ export runs at ENGINE CONSTRUCTION, and a
    warm-started quantized engine must stay at zero backend compiles
    (the ``serve_quant_warm`` budget row) — a traced quantize would
    recompile per construction.

    ``thresholds``: calibrated per-channel absmax [N]; int8 per-channel
    only (grouped / int4 scales re-derive absmax per group — the
    calibrated statistic IS the per-channel absmax, so raw and
    calibrated coincide unless an observer clipped)."""
    wf = np.asarray(w, np.float32)
    K = wf.shape[0]
    gs = config.group_size
    if (thresholds is not None and config.weight_dtype == "int8"
            and gs == -1):
        absmax = np.asarray(thresholds, np.float32).reshape(-1)
    elif gs != -1:
        G = -(-K // gs)
        wp = np.pad(wf, ((0, G * gs - K), (0, 0)))
        absmax = np.max(np.abs(wp.reshape(G, gs, -1)), axis=1)
    else:
        absmax = np.max(np.abs(wf), axis=0)
    qmax = 7.0 if config.weight_dtype == "int4" else 127.0
    scale = np.maximum(absmax, 1e-8) / qmax
    srow = np.repeat(scale, gs, axis=0)[:K] if gs != -1 else scale
    q = np.clip(np.round(wf / srow), -qmax - 1, qmax).astype(np.int8)
    if config.weight_dtype == "int4":
        if q.shape[0] % 2:
            q = np.pad(q, ((0, 1), (0, 0)))
        half = q.shape[0] // 2
        # HALVES packing: rows [0, K/2) low nibble, [K/2, K) high —
        # the nn.quant layout the kernels unpack
        q = ((q[:half] & 0x0F) | (q[half:] << 4)).astype(np.int8)
    return q, scale.astype(np.float32)


def quantize_params_for_serving(params, config: ServeQuantConfig,
                                thresholds: Optional[Dict] = None):
    """PTQ export: a zoo param tree -> the engine's quantized tree.

    Every stacked block matmul weight ``<name>`` (shape
    ``[*stages, L, K, N]``) is replaced by ``<name>__q`` (int8 codes;
    int4 halves-packed ``[..., ceil(K/2), N]``) and ``<name>__s`` (fp32
    scales ``[..., N]`` or grouped ``[..., G, N]``); everything else —
    norms, embedding, head, non-block leaves — passes through untouched.
    ``thresholds`` (from :func:`calibrate_weight_thresholds`) overrides
    raw absmax for per-channel int8.  Identity when the config has no
    weight quantization.
    """
    if not config.quantized_weights:
        return params
    blocks = params["blocks"]
    out = {k: v for k, v in params.items() if k != "blocks"}
    qblocks = {}
    for name, v in blocks.items():
        if not _is_block_matmul(name, v):
            qblocks[name] = v
            continue
        lead = v.shape[:-2]
        flat = np.asarray(v).reshape((-1,) + v.shape[-2:])   # [L, K, N]
        th = (thresholds or {}).get(name)
        qs, ss = [], []
        for i in range(flat.shape[0]):
            q, s = _quantize_matrix(flat[i], config,
                                    None if th is None else th[i])
            qs.append(q)
            ss.append(s)
        qn, sn = quantized_leaf_names(name)
        qblocks[qn] = jnp.asarray(
            np.stack(qs).reshape(lead + qs[0].shape))
        qblocks[sn] = jnp.asarray(
            np.stack(ss).reshape(lead + ss[0].shape))
    out["blocks"] = qblocks
    return out


def dequantize_block_weight(q, s, config: ServeQuantConfig, k: int):
    """Dequantize one layer's exported weight (``[K', N]`` codes +
    scales) back to fp32 ``[K, N]`` — the round-trip test's probe and
    the documentation of the storage layout in one place."""
    from ..nn.quant import weight_dequantize
    out = weight_dequantize(jnp.asarray(q), jnp.asarray(s),
                            algo=config.algo, k=k,
                            group_size=config.group_size)
    return out._value if hasattr(out, "_value") else jnp.asarray(out)
