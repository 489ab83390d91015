"""Continuous-batching serving engine over the paged KV cache.

Iteration-level scheduling (the vLLM recipe) on TPU terms: ONE jitted
decode step advances every active sequence in a fixed-size batch; between
steps the host scheduler admits queued requests into free slots, maps
pages from the shared block pool, and retires finished sequences —
requests join and leave the batch without recompilation (all shapes are
static: [max_batch] tokens/lengths, [max_batch, max_blocks] tables).

Relation to the reference: its serving stack is fused ops driven by an
external server (fused_multi_transformer + block_multihead_attention,
SURVEY §2.6); the block/page machinery here is ops/paged_kv.py (same
design as the reference's block attention), and this module adds the
in-framework scheduler the reference leaves to the serving layer.

Decoding is greedy by default; per-request sampling (temperature /
top-k / top-p) runs on per-slot PRNG streams folded per position, so a
sampled request's tokens depend only on its seed and its own content —
per-sequence results are independent of WHO ELSE shares the batch,
pinned by tests/test_serving_engine.py against a batch-of-one engine.
"""

from __future__ import annotations

import collections
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.decode_block import make_norm_ffn as _make_rms_ffn  # noqa: F401
from ..ops.paged_kv import (decode_walk, layer_pages, layers_as_one_pool,
                            pool_geometry)
#   ^ the norm/FFN closure pair moved to ops/decode_block.py (ISSUE 9)
#     so the decode step, the chunk fill, and the spec-decode draft all
#     read one definition; the old name stays importable for callers.

__all__ = ["ContinuousBatchingEngine", "GenRequest", "build_sampler",
           "derive_sample_seed"]


def _scan_layers(layer, x, blocks, pool_k, pool_v):
    """``lax.scan`` of ``layer(x, lp, pool_k, pool_v, i) -> (x, pool_k,
    pool_v)`` over the stacked ``blocks`` with the pools WHOLE in the
    carry, all layers as one pool (``layers_as_one_pool``): layer ``i``
    reaches its own pages through ``layer_pages`` and its append lands
    in place.  Scanned as inputs and outputs instead, the compiler
    copies both stacks whole on every call (the donated input is still
    being read while the stacked output is written) and moves each
    layer's ``[NB, ...]`` out of the stack and back: 4.3 GB a call at
    16 layers of 32 MiB pools, for 32 appended rows
    (``tests/test_chip_compile.py`` holds the compiled programs to
    it).  Returns ``(x, pool_k, pool_v)``, the pools stacked as they
    came."""
    def body(carry, inp):
        x, pk, pv = carry
        lp, i = inp
        return layer(x, lp, pk, pv, i), None

    n_layers = jax.tree.leaves(pool_k)[0].shape[0]
    (x, pk, pv), _ = jax.lax.scan(
        body, (x, layers_as_one_pool(pool_k), layers_as_one_pool(pool_v)),
        (blocks, jnp.arange(n_layers)))
    return (x, layers_as_one_pool(pk, like=pool_k),
            layers_as_one_pool(pv, like=pool_v))


def derive_sample_seed(seed: int, sample_idx: int) -> int:
    """Deterministic per-sample seed for n>1 parallel sampling (ROADMAP
    5(b)): sample 0 keeps the request's own seed (so ``n=1`` is exactly
    the single-request path), later samples hash (seed, sample_idx) —
    the per-sample stream is then keyed (seed, sample_idx, absolute
    position) end to end, and ``submit(n=k)`` is bit-identical to k
    independent submits carrying these derived seeds (pinned by
    tests/test_prefix_cache.py)."""
    if sample_idx == 0:
        return int(seed)
    import zlib
    return int(zlib.crc32(
        np.asarray([seed, sample_idx], np.int64).tobytes()) & 0x7FFFFFFF)


class _RefPool:
    """Refcounted page pool: prefix-cached blocks are shared read-only
    between sequences and the prefix index, freed when the last reference
    drops (the vLLM block-refcount scheme)."""

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, -1, -1))
        self.ref: Dict[int, int] = {}

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def acquire(self, n: int) -> Optional[List[int]]:
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self.ref[p] = 1
        return out

    def share(self, phys: List[int]) -> None:
        for p in phys:
            if p not in self.ref:
                raise RuntimeError(
                    f"KV-pool accounting bug: share() of block {p} that "
                    "holds no live reference (freed or never acquired)")
            self.ref[p] += 1

    def release(self, phys: List[int]) -> None:
        for p in phys:
            r = self.ref.get(p, 0)
            if r <= 0:
                raise RuntimeError(
                    f"KV-pool accounting bug: release() of block {p} "
                    "with no live reference (double free) — a scheduling "
                    "path released the same pages twice")
            if r == 1:
                del self.ref[p]
                self._free.append(p)
            else:
                self.ref[p] = r - 1


@dataclass
class GenRequest:
    req_id: int
    prompt: np.ndarray                 # [T0] int32
    max_new_tokens: int
    eos_token_id: Optional[int] = None
    temperature: float = 0.0           # <= 0: greedy
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    seed: int = 0
    # scheduling class (ISSUE 11): higher admits first; under
    # saturation strictly-lower-priority RUNNING work is preempted
    # (KV spilled to host RAM, resumed bit-identically later)
    priority: int = 0
    out: List[int] = field(default_factory=list)
    # index of the first EOS in ``out`` (set by the scheduler the step the
    # token is appended — O(1) per step instead of rescanning the list)
    eos_pos: Optional[int] = None


@dataclass
class _Dispatch:
    """A decode step handed to the device and not yet consumed: its
    outputs (``extra`` is the greedy row, after the expert layers'
    counts where the model routes), the request each slot ran
    when it was launched (None for a row the step did not run), and
    what the consumer counts for it."""
    logits: object
    extra: list
    reqs: List[Optional[GenRequest]]
    pages_walked: int
    pages_live: int
    # launched while its predecessor's greedy row was not yet computed
    ahead: bool
    t0: float


@jax.jit
def _ahead_tokens(host, greedy):
    """The next step's tokens while the last is in flight: its greedy
    row, where the host has no token of its own (-1) for the slot."""
    return jnp.where(host < 0, greedy, host)


def _row_placement(params):
    """Where a program's small outputs land for weights ``params``:
    replicated over the devices of committed weights; None (the
    runtime's choice) where the weights are not committed."""
    leaf = jax.tree_util.tree_leaves(params)[0]
    if not getattr(leaf, "committed", False):
        return None
    s = leaf.sharding
    if isinstance(s, jax.sharding.NamedSharding):
        return jax.sharding.NamedSharding(s.mesh,
                                          jax.sharding.PartitionSpec())
    return s


def build_sampler():
    """Row-vmapped fold-in + filter + categorical program shared by the
    engine's runtime sampler and the AOT exporter (``aot/serve.py``) —
    the deserialized program must be the very function the engine would
    have jitted.  HF sequential-warper semantics: top-p mass is computed
    over the top-k-FILTERED distribution, not the raw one."""

    def one(logits, seed, position, temperature, top_k, top_p):
        key = jax.random.fold_in(jax.random.key(seed), position)
        x = logits.astype(jnp.float32) / temperature
        srt = jnp.sort(x)[::-1]                  # descending
        # traced ranks must be POSITIVE take indices — a traced
        # negative index clamps to 0 under jit and would
        # silently disable the filter
        kth = jnp.take(srt, jnp.maximum(top_k, 1) - 1)
        x = jnp.where((top_k > 0) & (x < kth), -jnp.inf, x)
        srt2 = jnp.sort(x)[::-1]                 # filtered dist
        probs = jax.nn.softmax(srt2)
        cum = jnp.cumsum(probs)
        cidx = jnp.sum(cum < top_p)
        cutoff = jnp.take(srt2, cidx)
        x = jnp.where((top_p > 0.0) & (x < cutoff), -jnp.inf, x)
        return jax.random.categorical(key, x)

    return jax.vmap(one)


def sched_ratios(s: Dict[str, object]) -> Dict[str, object]:
    """``scheduler_stats`` counters (one engine's, or a fleet's summed)
    plus the ratios they exist for; None before the first prefill /
    decode step."""
    disp = s.get("prefill_tokens_dispatched", 0)
    steps = s.get("decode_slot_steps", 0)
    table = s.get("decode_pages_table", 0)
    walked = s.get("decode_pages_walked", 0)
    s["bucket_fill"] = (s.get("prefill_tokens_computed", 0) / disp
                        if disp else None)
    s["stalled_share"] = (s.get("stalled_slot_iterations", 0) / steps
                          if steps else None)
    s["kv_walk_share"] = walked / table if table else None
    s["kv_walk_fill"] = (s.get("decode_pages_live", 0) / walked
                         if walked else None)
    return s


def _model_module(cfg):
    """The module that defines ``cfg``'s class when it is a family's
    own serving module, else None (the Llama family, whose programs the
    engine builds itself).  Such a module offers ``build_step(cfg,
    block_size)`` and ``build_chunk_fill(cfg, block_size, Ts)``, the
    programs over the engine's ``_carry``; ``init_slot_state(cfg,
    max_batch)`` where its configuration has ``layer_types``; and may
    offer ``kernel_tiers(cfg, state_shape)`` and ``serving_layout(params)``
    (the tree as its programs read it, made once by the engine).  A
    further family is a further module, and no branch here."""
    mod = sys.modules.get(type(cfg).__module__)
    return mod if hasattr(mod, "build_step") else None


def _refuse(model: str, mechanisms) -> None:
    """Raise for the first ``(name, given, why)`` whose ``given`` is set:
    what the engine cannot do for ``model`` yet is refused by name."""
    for what, given, why in mechanisms:
        if given is not None:
            raise NotImplementedError(
                f"{what}= is not supported for a model with {model} "
                f"({why})")


class ContinuousBatchingEngine:
    """Continuous-batching engine (greedy by default, per-request
    sampling via temperature/top_k/top_p on add_request).  The kind of
    cache is read from ``cfg``, never a model's name: K/V pages for
    every layer (a Llama-family decoder: identical blocks, the programs
    built here); K/V pages for the layers that attend
    (``cfg.num_attention_layers``) and, beside the page table, a
    recurrent state and a conv tail per decode slot for the layers that
    recur, where ``cfg.layer_types`` exists (below) — a layer may do
    either or BOTH (a parallel hybrid's layer attends and recurs, and
    has a page row and a state row); or ONE pool of a vector a token
    and no value pool, where ``cfg.kv_lora_rank`` exists (latent
    attention, below).  The kinds COMPOSE: a model with a latent cache
    and a state gets the latent pool for its attending layers and state
    rows for the others.  A family other than Llama's brings its
    programs in its configuration's own module (:func:`_model_module`).
    Such a family's decode step picks its greedy row on the device and
    hands it back after the logits, preceded by its expert layers'
    counts only where the configuration routes tokens to experts
    (``cfg.num_experts_per_tok``; the ``moe_*`` counters); a dense
    family's step hands back the greedy row alone.

    Args:
      cfg: a LlamaConfig (dense or MoE — the FFN follows the config),
        or the configuration of a family module (its class's module
        offers the programs: ``models/granite_hybrid.py``,
        ``glm_moe_lite.py``, ``ling_linear.py``, ``falcon_h1.py``).
      params: the family's param pytree (Llama: the train-step tree,
        wte/head/lnf_w + stacked blocks; the other two: wte/lnf_w (and
        an untied head) + one stacked tree a run of layers of one
        kind).
      max_batch: decode-batch slots (static jit shape).
      block_size / num_blocks: shared KV page pool geometry.
      max_blocks_per_seq: page-table width per slot (caps per-sequence
        length at block_size * max_blocks_per_seq).
      prefill_buckets: declared prefill chunk lengths (aot/buckets.py).
        When set, EVERY prompt/suffix prefill is decomposed into these
        fixed-size chunk fills (last chunk zero-padded), so variable
        load runs on a fixed set of compiled programs instead of one
        jit per distinct prompt length.
      aot_dir: warm-start from a compile-artifact directory written by
        ``paddle_tpu.aot.export_engine`` — the decode step and the
        bucketed chunk fills are DESERIALIZED (zero backend compiles)
        instead of traced.  A rotation ROOT (a directory holding
        generation subdirs plus a ``latest`` pointer, see
        ``aot.artifact``) is followed through the pointer.  Any
        manifest mismatch (version skew, geometry drift, corruption,
        donation-unsafe artifact) falls back to fresh compiles with an
        ``aot`` telemetry event; the reason is kept on
        ``self.aot_error``.
      spec_config: a :class:`~paddle_tpu.spec_decode.SpecDecodeConfig`
        enabling speculative decoding — every decode iteration drafts
        ``k`` tokens per active request and verifies them in one
        fixed-width program (``spec_decode/``).  Greedy outputs are
        bit-identical to ``spec_config=None``; sampled outputs follow
        the same target law via rejection sampling.  ``spec_stats()``
        exposes acceptance counters.
      enable_preemption: priority classes with preemption (ISSUE 11).
        Requests carry a ``priority`` (``add_request(priority=)``);
        admission serves the highest class first, and under KV/batch
        saturation the scheduler evicts strictly-lower-priority running
        requests — committed KV pages spill to a CRC-checked host-RAM
        tier (``serving/resilience.py``) and restore into fresh blocks
        on re-admission, bit-identically.  With uniform priorities
        (the default) nothing is ever preempted, so the knob is inert
        for existing workloads.
      prefix_cache_config: a :class:`~paddle_tpu.serving.prefix_cache.
        PrefixCacheConfig` tuning the cross-request prefix cache
        (ISSUE 14) — most importantly ``offload_capacity_bytes``, the
        bounded host-RAM tier that parks evicted prefix pages as
        CRC-checked byte copies and restores them by exact-byte scatter
        (no recompute) on the next hit.  Default policy (no offload)
        matches the pre-ISSUE-14 drop-on-eviction behavior.
      quant_config: a :class:`~paddle_tpu.quantization.ServeQuantConfig`
        enabling quantized serving (ISSUE 16).  ``weight_dtype``
        ("int8"/"int4", optionally grouped) serves weight-only
        quantized block matmuls: ``params`` may be a pre-exported tree
        (``quantization.quantize_params_for_serving``) or a full-width
        tree, which is PTQ-exported at construction.  ``kv_dtype``
        ("int8") stores the paged KV pool as int8 codes with
        per-(token, head) fp32 scales (``ops.paged_kv.
        QuantizedKVPool``) — roughly halving KV bytes/token at
        head_dim 64+, so the same pool admits ~2x the concurrent
        sequences.  Greedy decode stays bit-identical WITHIN a quant
        config across every serve path (fused/unfused, spec-decode,
        prefix-cache hit, preempt/restore); the config is covered by
        the AOT ``engine_config`` hash so a warm start can never
        half-load a mismatched quantization.

    The engine keeps its own page table rather than reusing
    ops/paged_kv.PagedKVCache: that class sizes its table [B, num_blocks]
    (every slot could own the whole pool); the engine's
    [B, max_blocks_per_seq] table is the served context an operator
    allows.  The decode step's attention walks that table only as far
    as the longest live sequence reaches (``paged_decode_attention``;
    ``scheduler_stats()["kv_walk_share"]``), so a wide table costs a
    short request nothing per token; the chunk fill still gathers the
    row's whole width.

    What the compiled programs move (docs/serving.md, "The layer
    scan"): ``pool_k`` / ``pool_v`` are ``[L, NB, BS, Hkv, D]`` out
    here and ride through the step's and the fills' layer scan WHOLE,
    in its carry, as one pool of ``L*NB`` pages (``_scan_layers``);
    ``self.params["blocks"]`` holds q, k and v ``[N, K]`` as
    ``q_wt`` / ``k_wt`` / ``v_wt`` (``ops.decode_block.
    serving_layout``, made once in the constructor; the caller's tree
    keeps its layout, and a tree that is already laid out is taken as
    it is).

    Models with per-slot state (``cfg.layer_types``): ``ssm_state``
    ``[L_mamba, B, heads, head width, state]`` float32 and
    ``conv_state`` ``[L_mamba, B, channels, width - 1]`` ride through
    every program beside the pools, donated like them.  A slot's rows
    start at zero inside its first chunk fill (``start == 0``), every
    later chunk and decode step continues them, a bucket's padding
    leaves them alone, retirement needs no device work (the next
    admission overwrites).  For such a model the prefix cache is off (a
    page hit cannot restore a state: ``prefix_stats()`` stays at zero),
    a preempted slot's snapshot carries its state rows with its pages
    (CRC-checked; a snapshot the bounded tier dropped is replayed from
    the committed tokens), and ``spec_config`` / ``quant_config`` /
    ``aot_dir`` raise ``NotImplementedError``.

    Models with latent attention (``cfg.kv_lora_rank``): ``pool_k`` is
    the latent pool ``[L, NB, BS, W]`` (a token's normed latent and
    rotated shared key, ``r_kv + d_r`` values in whole lanes of 128:
    its key, whose first ``r_kv`` columns are also its value) and
    ``pool_v`` is None; the pool is made on the
    device (no host staging: at 4.7 GB beside 9 GB of weights a staged
    copy would not fit) and rides through every program whole, donated.
    The decode step walks it ``ops.mla.WALK_POSITIONS`` positions a
    trip, counted like the other walk.  Pages are position-absolute as
    K/V pages are, so the prefix cache's resident tier and the page
    accounting work unchanged; a preempted slot's pages leave and come
    back through two fixed-width page programs
    (``serving/resilience.py``), not through a host copy of the pool.
    ``spec_config`` / ``quant_config`` / ``aot_dir`` and a prefix
    cache's host offload tier raise ``NotImplementedError``.

    Models with both (``layer_types`` AND ``kv_lora_rank``): ``_carry``
    is ``("pool_k", "ssm_state", "conv_state")``, the pool ``[attending
    layers, NB, BS, W]``; the prefix cache is off (state); a preempted
    slot's snapshot carries its latent pages (through the page
    programs) AND its state rows; what either kind refuses is refused.
    """

    def __init__(self, cfg, params, *, max_batch: int = 4,
                 block_size: int = 16, num_blocks: int = 256,
                 max_blocks_per_seq: Optional[int] = None,
                 enable_prefix_caching: bool = True,
                 prefill_buckets=None, aot_dir: Optional[str] = None,
                 spec_config=None, enable_preemption: bool = True,
                 spill_tier=None, prefix_cache_config=None,
                 quant_config=None):
        self._hybrid = getattr(cfg, "layer_types", None) is not None
        self._latent = getattr(cfg, "kv_lora_rank", None) is not None
        # expert layers whose step counts their routing (a family's own
        # programs); a dense family's step hands back its greedy row
        # alone
        self._routed = getattr(cfg, "num_experts_per_tok", None) \
            is not None
        if self._latent:
            offload = prefix_cache_config is not None and getattr(
                prefix_cache_config, "offload_capacity_bytes", 0)
            _refuse("a latent cache", (
                ("spec_config", spec_config,
                 "the draft and verify programs know per-head K/V pools "
                 "only, and the model's own multi-token-prediction layer "
                 "is not held"),
                ("quant_config", quant_config,
                 "the PTQ export knows neither expert banks nor the "
                 "latent projections, and the latent pool has no "
                 "quantised form"),
                ("aot_dir", aot_dir,
                 "the AOT manifest does not hash a latent pool's "
                 "geometry"),
                ("prefix_cache_config.offload_capacity_bytes",
                 offload or None,
                 "the host offload tier copies a K and a V pool through "
                 "the host whole")))
        if self._hybrid:
            _refuse("per-slot recurrent state", (
                ("spec_config", spec_config,
                 "speculative decoding would have to roll a slot's "
                 "recurrent state back over rejected tokens"),
                ("quant_config", quant_config,
                 "the PTQ export knows neither expert banks nor "
                 "state-space projections, and the recurrent state has "
                 "no quantised form"),
                ("aot_dir", aot_dir,
                 "the AOT manifest does not hash the per-slot state's "
                 "geometry")))
        if getattr(cfg, "moe_num_experts", 0) and \
                getattr(cfg, "moe_router", "topk") != "topk":
            raise NotImplementedError("decode serves token-choice only")
        if quant_config is not None and quant_config.quantized_weights \
                and getattr(cfg, "moe_num_experts", 0):
            raise NotImplementedError(
                "weight-quantized serving covers dense FFNs only — the "
                "MoE expert matmuls keep full-width weights (ROADMAP)")
        rs = getattr(cfg, "rope_scaling", None)
        if rs and rs.get("rope_type", rs.get("type")) == "dynamic":
            raise NotImplementedError(
                "dynamic-NTK rope depends on the CURRENT sequence length; "
                "the engine bakes one table at max_position_embeddings, "
                "which would mis-scale every shorter sequence — use "
                "'linear' or 'llama3' scaling for serving")
        self.cfg = cfg
        self.quant_config = quant_config
        if quant_config is not None and quant_config.quantized_weights \
                and not any(k.endswith("__q")
                            for k in params["blocks"]):
            # full-width tree handed to a quantized engine: PTQ-export
            # it here (absmax scales); calibrated trees come in already
            # exported via quantize_params_for_serving(thresholds=...)
            from ..quantization.serve import quantize_params_for_serving
            params = quantize_params_for_serving(params, quant_config)
        self.params = params
        self.B = max_batch
        self.BS = block_size
        self.MB = max_blocks_per_seq or \
            -(-cfg.max_position_embeddings // block_size)
        # K/V pages exist for the layers that attend: all of them, or a
        # hybrid's attention layers only
        L = cfg.num_attention_layers if self._hybrid else cfg.num_layers
        dt = jnp.dtype(cfg.dtype)
        # a Llama-family engine builds its pools from HOST zeros through
        # the same pool-shaped copy op the preemption restore path uses
        # (jnp.array of a numpy array = convert_element_type
        # executable), so a restore under traffic hits a
        # compiled-at-construction op instead of tracing one — the
        # fleet_warm budget row pins serve-path compiles at zero.  A
        # quantized-KV config builds an int8 QuantizedKVPool (codes +
        # per-(token, head) fp32 scales).  A family's pools are made
        # where they live: the host staging stands beside a pool for a
        # moment, and two 1.81 GB pools staged beside 8.79 GB of weights
        # set a peak of 16.04 of the v5e's 16.9 GB (PERF.md, PR 40)
        from ..ops.paged_kv import zeros_kv_pool
        self._kv_quant = quant_config is not None \
            and quant_config.quantized_kv
        family = _model_module(cfg)
        if self._latent:
            # one vector a token
            self.pool_k = jnp.zeros(
                (L, num_blocks, block_size, cfg.pool_width), dt)
            self.pool_v = None
        else:
            shape = (L, num_blocks, block_size, cfg.kv_heads, cfg.head_dim)
            if family is None:
                self.pool_k = zeros_kv_pool(shape, dt,
                                            kv_quant=self._kv_quant)
                self.pool_v = zeros_kv_pool(shape, dt,
                                            kv_quant=self._kv_quant)
            else:
                self.pool_k = jnp.zeros(shape, dt)
                self.pool_v = jnp.zeros(shape, dt)
        # q, k and v laid out ONCE as the compiled programs read them (a
        # tree that already is comes back as it is): the Llama family's
        # here, a family's own by its module's ``serving_layout``; the
        # caller's tree, which training and checkpoints share, is not
        # touched.  After the pools stand: their host-side staging
        # copies are gone by then, and the three leaves that exist
        # twice until the caller lets go of its tree set no higher peak
        # than building the pools did
        if family is None:
            from ..ops.decode_block import serving_layout
            jax.block_until_ready((self.pool_k, self.pool_v))
            self.params = dict(params,
                               blocks=serving_layout(params["blocks"]))
        elif hasattr(family, "serving_layout"):
            jax.block_until_ready((self.pool_k, self.pool_v))
            self.params = family.serving_layout(params)
        # per-slot recurrent state beside the pages (hybrid models)
        self.ssm_state = self.conv_state = None
        if self._hybrid:
            self.ssm_state, self.conv_state = \
                _model_module(cfg).init_slot_state(cfg, max_batch)
        #: what every compiled program is given after the params,
        #: donated, and hands back first: the pools (one for a latent
        #: cache), and the two state arrays of a model with per-slot
        #: state (``_carried`` / ``_keep``)
        self._carry = (("pool_k",) if self._latent
                       else ("pool_k", "pool_v")) + (
            ("ssm_state", "conv_state") if self._hybrid else ())
        self._donate = tuple(range(1, 1 + len(self._carry)))
        self.block_table = np.full((max_batch, self.MB), -1, np.int32)
        self.lengths = np.zeros((max_batch,), np.int32)
        self.tokens = np.zeros((max_batch,), np.int32)
        self.alloc = _RefPool(num_blocks)
        self.slot_pages: List[List[int]] = [[] for _ in range(max_batch)]
        # cross-request prefix caching (ISSUE 14): a radix tree over
        # committed prompt pages, keyed by chained block digests; the
        # cache holds one pool reference per resident block, evicted
        # LRU (leaf-first) under page pressure — optionally into a
        # bounded CRC-checked host-RAM offload tier that restores by
        # exact-byte scatter instead of recompute
        from ..serving.prefix_cache import PrefixCache
        # a cached page cannot bring back the state that stood at its
        # end, so a model with per-slot state registers and matches
        # nothing
        self.enable_prefix_caching = bool(enable_prefix_caching) \
            and not self._hybrid
        self.prefix_cache = PrefixCache(block_size,
                                        config=prefix_cache_config)
        self.stats = {"prefix_blocks_reused": 0,
                      "prefix_blocks_registered": 0,
                      "pages_allocated": 0,
                      "prefill_tokens_computed": 0,
                      # what a decode dispatch's fetch brings back
                      "decode_fetch_bytes": 0,
                      # decode steps launched while the step before
                      # them was still computing their tokens
                      "decode_ahead_steps": 0}
        self.slots: List[Optional[GenRequest]] = [None] * max_batch
        self.queue: "collections.deque[GenRequest]" = collections.deque()
        self.finished: Dict[int, np.ndarray] = {}
        self._next_id = 0
        # priority preemption (ISSUE 11): spilled-KV snapshots for
        # preempted requests, keyed by req_id (serving/resilience.py
        # owns the snapshot/restore machinery + CRC conventions).  The
        # tier is BOUNDED (ISSUE 12): pass a capacity-limited
        # ``SpillTier`` and an over-cap spill evicts the oldest
        # snapshot, demoting its request to replay-from-prefix.
        self.enable_preemption = bool(enable_preemption)
        if spill_tier is None:
            from ..serving.resilience import SpillTier
            spill_tier = SpillTier()
        self._spill = spill_tier
        self.resilience = {"preemptions": 0, "restores": 0,
                           "spill_save_secs": 0.0,
                           "spill_restore_secs": 0.0,
                           "spill_evictions": 0, "prefix_replays": 0}
        # LRU-bounded (a serving workload with many distinct prompt
        # lengths must not retain unboundedly many XLA executables)
        from ..utils.lru import LRUCache
        self._prefill_cache = LRUCache(16)
        self._chunk_fill_cache = LRUCache(16)
        # declared-bucket prefill + AOT warm start (paddle_tpu/aot)
        self._buckets = None
        self._bucket_fills: Dict[int, object] = {}
        self.aot_loaded = False
        self.aot_error: Optional[str] = None
        self._step = None
        self._sampler_fn = None
        # the merge of ``_ahead_tokens`` as an AOT artifact loaded it
        # (None: the module's own jitted function)
        self._ahead_fn = None
        self._spec = None
        self.spec_config = spec_config
        # decode-phase accounting (extra.spec bench row).
        # decode_slot_steps counts PER-SLOT decode iterations so that
        # engine_steps_per_token is exactly 1.0 for baseline decode
        # regardless of batching — only accepted speculation pushes it
        # below 1.0.
        self.decode_steps = 0
        self.decode_slot_steps = 0
        self.decode_tokens = 0
        # scheduler accounting at the timeline's boundaries (ISSUE 27),
        # always on, read by scheduler_stats(): prefill_tokens_dispatched
        # counts PADDED chunk sizes; stalled_slot_iterations sums, over
        # the iterations that ran a prefill, the streams that were live
        # and waited through it
        self.admissions = 0
        self.prefill_chunks = 0
        self.prefill_tokens_dispatched = 0
        self.stalled_slot_iterations = 0
        # the decode program's page walk (ISSUE 28), per plain decode
        # dispatch: table entries its attention gathered over all B
        # rows (ops.paged_kv.decode_walk, the program's own arithmetic)
        # and those of them that hold a live token of an active slot
        self.decode_pages_walked = 0
        self.decode_pages_live = 0
        # the decode program's state updates (a model with per-slot
        # state): live slots x recurrent layers, per decode dispatch
        self.state_slot_steps = 0
        # expert layers that hold a share of the router's experts (a
        # family with routed experts): per decode dispatch, token-expert
        # pairs that landed on held experts and distinct held experts
        # hit (summed over the layers on the device, fetched with the
        # greedy row), out of live slots x k x layers and held experts
        # x layers
        self.moe = {"moe_assignments_local": 0, "moe_experts_hit": 0,
                    "moe_assignments_total": 0, "moe_expert_slots": 0}
        if self._latent:
            # the most pairs on ONE expert of a layer, summed over the
            # expert layers and the steps: against the pairs, how
            # uneven the routing is
            self.moe["moe_peak_load"] = 0
            # the rows the decode steps' expert matmuls multiplied (row
            # tiles visited x tile height, summed over the layers):
            # against the held pairs, the padding of the experts' tiles
            self.moe["moe_step_rows"] = 0
            # the chunk fills' expert layers: token-expert pairs routed
            # (a bucket's padded rows too) and the rows the experts'
            # matmuls multiplied for them, summed on the device through
            # a prompt's chunks (models/glm_moe_lite.py) and fetched
            # where the engine waits for the prompt's first token
            self.moe.update(moe_fill_pairs=0, moe_fill_rows=0)
            self._moe_fill_zero = jnp.zeros((2,), jnp.int32)
            self._moe_fill = self._moe_fill_zero
        # positions a trip of the decode program's page walk covers
        if self._latent:
            from ..ops.mla import WALK_POSITIONS
        else:
            from ..ops.paged_kv import WALK_POSITIONS
        self._walk_positions = WALK_POSITIONS
        # the engine timeline while the tracer is on, else None: set
        # once per step(), read by the phases underneath it
        self._tl = None
        # the decode step on the device whose tokens the host has not
        # taken yet (``_iterate``)
        self._inflight: Optional[_Dispatch] = None
        # the decode step's rows from the host are placed as the step's
        # greedy row is: a step fed by the host and one fed the row in
        # flight then see ONE placement, and the step compiles once
        self._row_place = _row_placement(self.params)
        _spec_programs = {}
        if spec_config is not None:
            spec_config.validate_against(cfg)
        if aot_dir is not None:
            from ..aot.artifact import AotError
            from ..aot.serve import load_engine_artifacts
            try:
                (self._step, self._bucket_fills, self._buckets,
                 self._sampler_fn, self._ahead_fn, _spec_programs) = \
                    load_engine_artifacts(self, aot_dir)
                self.aot_loaded = True
            except AotError as e:
                # fresh-compile fallback, loudly: the reason stays on
                # the engine and goes to the telemetry event stream
                self.aot_error = str(e)
                from ..observability import REGISTRY
                if REGISTRY.enabled:
                    REGISTRY.counter("aot.fallback_total").inc()
                    REGISTRY.event("aot", action="fallback", dir=aot_dir,
                                   reason=str(e)[:300])
        if self._buckets is None and prefill_buckets is not None:
            from ..aot.buckets import ShapeBucketRegistry
            self._buckets = ShapeBucketRegistry(prefill_buckets,
                                                max_batch=max_batch)
        if self._step is None:
            # pools are donated: the decode step rewrites them every
            # iteration and the old buffers must not stay live
            self._step = jax.jit(self._build_step(),
                                 donate_argnums=self._donate)
        if spec_config is not None:
            from ..spec_decode import SpecDecodeRunner
            self._spec = SpecDecodeRunner(
                self, spec_config,
                draft_fn=_spec_programs.get("draft"),
                verify_fn=_spec_programs.get("verify"))
        self._last_logits = None                        # [B, V] debug/test

    # ------------------------------------------------------------------
    # compiled per-iteration decode over every slot
    # ------------------------------------------------------------------
    def _quant_kw(self):
        """The weight-quantization fields every block spec in this
        engine is built with — ONE source so the decode step, the chunk
        fills, and the spec-decode verify always agree."""
        qc = self.quant_config
        if qc is None or not qc.quantized_weights:
            return {}
        return {"weight_dtype": qc.weight_dtype,
                "group_size": qc.group_size}

    def _build_step(self):
        cfg = self.cfg
        family = _model_module(cfg)
        if family is not None:
            return family.build_step(cfg, self.BS)
        from ..models.llama import _rope_cos_sin
        from ..models.generation import _collapse_blocks
        from ..ops.decode_block import decode_block, decode_block_spec
        D = cfg.head_dim
        cos_full, sin_full = _rope_cos_sin(
            cfg.max_position_embeddings, D, cfg.rope_theta,
            jnp.dtype(cfg.dtype), getattr(cfg, "rope_scaling", None))
        rms, moe_ffn = _make_rms_ffn(cfg)
        spec = decode_block_spec(cfg, self.BS, **self._quant_kw())
        ffn_override = moe_ffn if getattr(cfg, "moe_num_experts", 0) \
            else None

        def step(params, pool_k, pool_v, bt, lengths, tokens):
            blocks = _collapse_blocks(params["blocks"])
            x = jnp.take(params["wte"], tokens, axis=0)       # [B, h]
            # per-slot rope position = current length (0-based slot of
            # the incoming token)
            cos = jnp.take(cos_full, lengths, axis=0)         # [B, D]
            sin = jnp.take(sin_full, lengths, axis=0)

            NB = pool_geometry(pool_k)[0]

            def layer(x, lp, pk, pv, i):
                return decode_block(
                    x, lp, pk, pv, layer_pages(bt, i, NB), lengths, cos,
                    sin, spec=spec, ffn=ffn_override)

            x, pk2, pv2 = _scan_layers(layer, x, blocks, pool_k, pool_v)
            xf = rms(x, params["lnf_w"])
            logits = jnp.einsum("bh,hv->bv", xf, params["head"],
                                preferred_element_type=jnp.float32)
            return (pk2, pv2, logits,
                    jnp.argmax(logits, axis=-1).astype(jnp.int32))

        return step

    def _build_chunk_fill(self, Ts: int):
        """Suffix prefill against the paged pool: runs ``Ts`` prompt
        tokens starting at a cached prefix of length ``start``, writing
        their KV into the (private) pages and returning next-token
        logits.  This is what makes a prefix-cache hit SKIP the prefix
        compute, not just dedupe its storage.

        Called with the optional trailing ``valid`` argument (the
        declared-bucket path), only the first ``valid`` tokens are
        real: padded rows write their KV to an out-of-range block index
        (scatter drops out-of-bounds updates, so the pool is untouched)
        and the returned logits come from row ``valid - 1`` instead of
        the last row.  With ``valid == Ts`` the computation is
        identical to the unpadded call."""
        cfg = self.cfg
        family = _model_module(cfg)
        if family is not None:
            return family.build_chunk_fill(cfg, self.BS, Ts)
        from ..models.llama import _rope_cos_sin
        from ..models.generation import _collapse_blocks
        from ..ops.decode_block import decode_block_spec, prefill_block
        D = cfg.head_dim
        BS = self.BS
        cos_full, sin_full = _rope_cos_sin(
            cfg.max_position_embeddings, D, cfg.rope_theta,
            jnp.dtype(cfg.dtype), getattr(cfg, "rope_scaling", None))
        scale = 1.0 / (D ** 0.5)
        rms, moe_ffn = _make_rms_ffn(cfg)
        spec = decode_block_spec(cfg, BS, **self._quant_kw())
        ffn_override = moe_ffn if getattr(cfg, "moe_num_experts", 0) \
            else None

        def fill(params, pool_k, pool_v, bt_row, start, toks, valid=None):
            # toks [Ts]; bt_row [MB]; start: prefix length
            blocks = _collapse_blocks(params["blocks"])
            pos = start + jnp.arange(Ts)                     # [Ts]
            x = jnp.take(params["wte"], toks, axis=0)[None]  # [1, Ts, h]
            cos = jnp.take(cos_full, pos, axis=0)
            sin = jnp.take(sin_full, pos, axis=0)
            blk = jnp.take(jnp.maximum(bt_row, 0), pos // BS)
            off = pos % BS
            jpos = jnp.arange(bt_row.shape[0] * BS)[None, None, None, :]
            mask = jpos <= pos[None, None, :, None]
            NB = pool_geometry(pool_k)[0]

            def layer(x, lp, pk, pv, i):
                blk_i = blk + i * NB
                if valid is not None:
                    # bucketed call: padded rows scatter out of range
                    # of ALL layers' pages (``pk`` is the one pool of
                    # them; the update is dropped) so stale pool pages
                    # stay intact: one layer's page NB is the next
                    # layer's page 0
                    blk_i = jnp.where(jnp.arange(Ts) < valid, blk_i,
                                      pool_geometry(pk)[0])
                return prefill_block(
                    x, lp, pk, pv, blk_i, off, layer_pages(bt_row, i, NB),
                    mask, cos, sin, spec=spec, ffn=ffn_override,
                    scale=scale)

            x, pk2, pv2 = _scan_layers(layer, x, blocks, pool_k, pool_v)
            last = x[:, -1] if valid is None \
                else jnp.take(x, valid - 1, axis=1)
            xf = rms(last, params["lnf_w"])
            logits = jnp.einsum("bh,hv->bv", xf, params["head"],
                                preferred_element_type=jnp.float32)
            return pk2, pv2, logits

        return fill

    def _chunk_fill(self, Ts: int):
        fn = self._chunk_fill_cache.get(Ts)
        if fn is None:
            fn = jax.jit(self._build_chunk_fill(Ts),
                         donate_argnums=self._donate)
            self._chunk_fill_cache.put(Ts, fn)
        return fn

    @property
    def last_logits(self) -> Optional[np.ndarray]:
        """``[B, V]`` float32 logits of the last decode dispatch (None
        when the last iteration decoded nothing), for tests and
        debugging.  The step leaves them on the device (it picks the
        greedy tokens itself); they are fetched here, on demand."""
        if self._last_logits is not None \
                and not isinstance(self._last_logits, np.ndarray):
            self._last_logits = np.asarray(self._last_logits)
        return self._last_logits

    @last_logits.setter
    def last_logits(self, value) -> None:
        self._last_logits = value

    def _carried(self):
        """The donated arguments of every compiled program, as they
        stand."""
        return tuple(getattr(self, name) for name in self._carry)

    def _keep(self, out):
        """Take back what a compiled program returned for
        :meth:`_carried`; the rest of its results."""
        for name, value in zip(self._carry, out):
            setattr(self, name, value)
        return out[len(self._carry):]

    def _run_fill(self, fill, slot: int, bt_row, start, toks, *valid):
        """Call a compiled chunk fill for ``slot`` (a fill of a model
        with per-slot state is told the slot: its state rows are that
        slot's; a latent model's is handed its expert layers' running
        counts and hands them back); returns the logits."""
        more = ((jnp.int32(slot),) if self._hybrid else ()) + (
            (self._moe_fill,) if self._latent else ())
        logits, *counts = self._keep(fill(
            self.params, *self._carried(), bt_row, start, toks, *more,
            *valid))
        if counts:
            (self._moe_fill,) = counts
        return logits

    def _take_fill_counts(self) -> None:
        """Bring a latent model's fill counts to the host (the caller is
        about to wait for the same fills' logits) and start anew."""
        if self._latent and self._moe_fill is not self._moe_fill_zero:
            pairs, rows = (int(c) for c in np.asarray(self._moe_fill))
            self.moe["moe_fill_pairs"] += pairs
            self.moe["moe_fill_rows"] += rows
            self._moe_fill = self._moe_fill_zero

    def _bucket_fill(self, size: int):
        """Compiled bucketed fill for a DECLARED chunk size: AOT-loaded
        when the engine warm-started, else jitted once per bucket (the
        key set is the fixed declared-bucket set, so this cache is
        bounded by construction)."""
        fn = self._bucket_fills.get(size)
        if fn is None:
            fn = jax.jit(self._build_chunk_fill(size),
                         donate_argnums=self._donate)
            self._bucket_fills[size] = fn
        return fn

    def _fill_prompt_bucketed(self, slot: int, req: "GenRequest",
                              start: int) -> np.ndarray:
        """Run the prompt suffix (``start`` = cached-prefix tokens)
        through declared-bucket chunk fills; returns the logits at the
        prompt's final token (from the last chunk's ``valid - 1``
        row)."""
        suffix = req.prompt[start:]
        bt_row = jnp.asarray(self.block_table[slot])
        pos, off = start, 0
        logits = None
        tl = self._tl
        for size, valid in self._buckets.plan_chunks(len(suffix)):
            sp = tl and tl.enter("prefill_chunk", size=size, valid=valid)
            toks = np.zeros((size,), np.int32)
            toks[:valid] = suffix[off:off + valid]
            fill = self._bucket_fill(size)
            logits = self._run_fill(fill, slot, bt_row, jnp.int32(pos),
                                    jnp.asarray(toks), jnp.int32(valid))
            self.prefill_chunks += 1
            self.prefill_tokens_dispatched += size
            if tl:
                tl.leave(sp)
            pos += valid
            off += valid
        return logits

    # ------------------------------------------------------------------
    # host-side scheduler
    # ------------------------------------------------------------------
    def add_request(self, prompt_ids, max_new_tokens: int,
                    eos_token_id: Optional[int] = None, *,
                    temperature: float = 0.0,
                    top_k: Optional[int] = None,
                    top_p: Optional[float] = None,
                    seed: int = 0, priority: int = 0) -> int:
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if len(prompt) < 1:
            raise ValueError("prompt must contain at least one token "
                             "(an empty prompt has no last position for "
                             "the prefill to sample from)")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1 (the prefill "
                             "argmax is already one generated token)")
        total = len(prompt) + max_new_tokens
        if total > self.MB * self.BS:
            raise ValueError(f"request needs {total} tokens, engine caps "
                             f"at {self.MB * self.BS} per sequence")
        if self._blocks_needed(total) > self.alloc.num_blocks:
            raise ValueError(
                f"request needs {self._blocks_needed(total)} pages, the "
                f"whole pool has {self.alloc.num_blocks} — it could never "
                f"admit (raise num_blocks or shrink the request)")
        if total > self.cfg.max_position_embeddings:
            raise ValueError("request exceeds max_position_embeddings")
        req = GenRequest(self._next_id, prompt, max_new_tokens,
                         eos_token_id, temperature=temperature,
                         top_k=top_k, top_p=top_p, seed=seed,
                         priority=int(priority))
        self._next_id += 1
        self.queue.append(req)
        # request tracing (ISSUE 20): adopt the ambient trace the
        # frontend/supervisor activated around this call; the queue
        # mark becomes the queue_wait span at admission
        from ..observability.tracing import TRACER
        if TRACER.enabled:
            tr = TRACER.current()
            if tr is not None:
                req.trace = tr
                tr.mark("enqueued")
        return req.req_id

    @staticmethod
    def _trace_of(req: "GenRequest"):
        """The request's live trace, or None (tracing disabled, or the
        request was submitted with no trace active)."""
        from ..observability.tracing import TRACER
        if not TRACER.enabled:
            return None
        return getattr(req, "trace", None)

    def _pick_token(self, req: GenRequest, logits: np.ndarray,
                    position: int) -> int:
        """Greedy, or sample on the request's own PRNG stream folded by
        ABSOLUTE position — reproducible per (seed, content), independent
        of batch composition and admission timing."""
        if req.temperature is None or req.temperature <= 0.0:
            return int(logits.argmax())
        return int(self._sample_rows([req], np.asarray(logits)[None],
                                     [position])[0])

    def _sampler(self):
        """The compiled fixed-width sampler: AOT-loaded when the engine
        warm-started, else jitted once."""
        if self._sampler_fn is None:
            self._sampler_fn = jax.jit(build_sampler())
        return self._sampler_fn

    def _sample_rows(self, reqs: List[GenRequest], logits_rows,
                     positions) -> np.ndarray:
        """Sample one token per request (rows aligned with ``reqs``).

        Rows are PADDED to the full decode width ``max_batch`` so every
        call — any sampled sub-batch size AND the single-row admission
        path — runs ONE compiled program instead of one per distinct
        width.  That one program is what ``aot/serve.py`` serializes, so
        warm-started engines sample with zero backend compiles.  Each
        row is computed independently (vmap), so padding cannot change
        a real row's token."""
        n = len(reqs)
        lg = np.zeros((self.B, logits_rows.shape[-1]), np.float32)
        lg[:n] = logits_rows
        seeds = np.zeros((self.B,), np.int32)
        pos = np.zeros((self.B,), np.int32)
        temps = np.ones((self.B,), np.float32)   # pad rows: no div-by-0
        topk = np.zeros((self.B,), np.int32)
        topp = np.zeros((self.B,), np.float32)
        pos[:n] = np.asarray(positions, np.int32)
        for i, r in enumerate(reqs):
            seeds[i] = r.seed
            temps[i] = r.temperature
            topk[i] = r.top_k or 0
            topp[i] = r.top_p or 0.0
        toks = self._sampler()(jnp.asarray(lg), jnp.asarray(seeds),
                               jnp.asarray(pos), jnp.asarray(temps),
                               jnp.asarray(topk), jnp.asarray(topp))
        return np.asarray(toks)[:n]

    def _blocks_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.BS)

    @property
    def prefix_index(self) -> "collections.OrderedDict[bytes, int]":
        """Compatibility view of the HBM-resident tier of the prefix
        cache: ``{chained block digest: phys page}``, LRU order — the
        leak report and the pool-invariant tests read this; the live
        structure is the radix tree (``self.prefix_cache``)."""
        return collections.OrderedDict(self.prefix_cache.resident_items())

    def _cached_prefix(self, prompt: np.ndarray):
        """Longest cached block-aligned prefix: ``(resident_blocks,
        resident_pages, offloaded_nodes)``.  Resident pages are claimed
        via ``_RefPool.share``; offloaded nodes restore by exact-byte
        scatter into freshly acquired pages (``_restore_offloaded``).
        When the prompt is an exact multiple of BS, at least one block
        is left uncached so the suffix prefill has >= 1 token to
        produce next-token logits."""
        if not self.enable_prefix_caching:
            return 0, [], []
        full = len(prompt) // self.BS
        lookup = full - 1 if len(prompt) % self.BS == 0 else full
        pages, off = self.prefix_cache.walk(
            self._block_keys(prompt, lookup))
        return len(pages), pages, off

    def _block_keys(self, prompt: np.ndarray, n: int) -> List[bytes]:
        """Chained per-block digests — ONE definition shared with the
        fleet router's affinity summaries
        (``serving.prefix_cache.block_keys``)."""
        return self.prefix_cache.keys_for(prompt, n)

    def prefix_match_blocks(self, keys: List[bytes]) -> int:
        """Longest cached chain prefix for a precomputed key list,
        WITHOUT touching cache recency or refcounts — the read-only
        summary ``EngineRouter`` consults for prefix-affinity
        placement."""
        if not self.enable_prefix_caching:
            return 0
        return self.prefix_cache.match_blocks(keys)

    def _acquire_with_eviction(self, n: int) -> Optional[List[int]]:
        """Acquire pages, LRU-evicting prefix-cache blocks on pressure
        (leaf-first, so surviving chains stay walkable).  Only blocks
        whose page is held SOLELY by the cache (ref == 1) are evicted —
        evicting a shared block frees nothing and would throw away
        prefixes other requests still hit.  With an offload budget the
        victim's exact page bytes park in the host-RAM tier before the
        page is released (restored by scatter on the next hit).
        Callers must take their own reference on reused pages BEFORE
        acquiring, or an evicted twin of a 'shared' page could be
        handed back as private and the chunk fill would overwrite
        cached prefix KV."""
        while True:
            got = self.alloc.acquire(n)
            if got is not None:
                self.stats["pages_allocated"] += n
                return got
            node = self.prefix_cache.evictable(
                lambda p: self.alloc.ref.get(p, 0))
            if node is None:
                return None
            self._evict_prefix_block(node)

    def _evict_prefix_block(self, node) -> None:
        """Evict one resident cache block: offload its exact page bytes
        to the bounded host tier when configured (host-side gather —
        the same zero-compile convention as ``snapshot_slot``), then
        release the cache's pool reference."""
        cache = self.prefix_cache
        if cache.wants_offload:
            if self._kv_quant:
                # int8 pages travel with their per-(token, head) fp32
                # scales — both CRC-stamped, both restored by scatter
                k = np.asarray(self.pool_k.data)[:, node.phys].copy()
                v = np.asarray(self.pool_v.data)[:, node.phys].copy()
                ks = np.asarray(self.pool_k.scale)[:, node.phys].copy()
                vs = np.asarray(self.pool_v.scale)[:, node.phys].copy()
                phys = cache.evict(node, k, v, ks, vs)
            else:
                k = np.asarray(self.pool_k)[:, node.phys].copy()
                v = np.asarray(self.pool_v)[:, node.phys].copy()
                phys = cache.evict(node, k, v)
        else:
            phys = cache.evict(node)
        self.alloc.release([phys])
        from ..observability import REGISTRY
        if REGISTRY.enabled:
            REGISTRY.counter("serve.prefix.evictions_total").inc()
            if cache.wants_offload:
                REGISTRY.counter("serve.prefix.offloads_total").inc()
                REGISTRY.gauge("serve.prefix.offloaded_bytes").set(
                    cache.host_bytes)

    def _restore_offloaded(self, off, priv: List[int]) -> int:
        """Scatter offloaded prefix blocks' exact bytes into the first
        ``len(off)`` freshly acquired private pages, promoting each back
        to the resident tier (the cache takes a reference, exactly as
        if the block had never left HBM).  A CRC failure stops the
        restore at that block — typed event, ``restore_failures``
        counter — and the caller recomputes the remaining suffix by
        ordinary prefill: bit-rot costs FLOPs, never tokens.  Returns
        the number of blocks restored.  One host round trip total; the
        device copy runs through the pool-shaped op pre-warmed at
        construction (zero backend compiles, the ``serve_prefix_warm``
        budget row)."""
        if not off:
            return 0
        from ..observability import REGISTRY
        from ..serving.resilience import SpillCorruptError
        pk = pv = pks = pvs = None
        restored = 0
        for j, node in enumerate(off):
            try:
                node.verify()
                if (node.k_scale is not None) != self._kv_quant:
                    # an offloaded block whose quantization disagrees
                    # with the pool (e.g. restored cache state from a
                    # differently-configured engine) can never scatter
                    # — same typed demotion as bit-rot: recompute the
                    # suffix, never corrupt the pool
                    raise SpillCorruptError(
                        f"offloaded prefix block {node.key.hex()[:12]} "
                        "quantization does not match this engine's KV "
                        "pool — demoting to suffix recompute")
            except SpillCorruptError as e:
                self.prefix_cache.drop_host(node)
                if REGISTRY.enabled:
                    REGISTRY.counter(
                        "serve.prefix.restore_failures_total").inc()
                    REGISTRY.event("serve", action="prefix_bitrot",
                                   depth=int(node.depth),
                                   error=str(e)[:200])
                break
            if pk is None:
                if self._kv_quant:
                    pk = np.asarray(self.pool_k.data).copy()
                    pv = np.asarray(self.pool_v.data).copy()
                    pks = np.asarray(self.pool_k.scale).copy()
                    pvs = np.asarray(self.pool_v.scale).copy()
                else:
                    pk = np.asarray(self.pool_k).copy()
                    pv = np.asarray(self.pool_v).copy()
            pk[:, priv[j]] = node.k_bytes
            pv[:, priv[j]] = node.v_bytes
            if self._kv_quant:
                pks[:, priv[j]] = node.k_scale
                pvs[:, priv[j]] = node.v_scale
            self.prefix_cache.promote(node, priv[j])
            self.alloc.share([priv[j]])
            restored += 1
        if pk is not None:
            # owned copies, never aliases: the decode step donates the
            # pools (see restore_into_slot for the full rationale)
            if self._kv_quant:
                from ..ops.paged_kv import QuantizedKVPool
                self.pool_k = QuantizedKVPool(jnp.array(pk),
                                              jnp.array(pks))
                self.pool_v = QuantizedKVPool(jnp.array(pv),
                                              jnp.array(pvs))
            else:
                self.pool_k = jnp.array(pk)
                self.pool_v = jnp.array(pv)
        if restored and REGISTRY.enabled:
            REGISTRY.counter("serve.prefix.restores_total").inc(restored)
            REGISTRY.gauge("serve.prefix.offloaded_bytes").set(
                self.prefix_cache.host_bytes)
        return restored

    def _note_prefix_lookup(self, hit_blocks: int) -> None:
        """Account one admission-time cache consultation (miss or
        hit).  ``hit_blocks`` counts resident + restored blocks whose
        compute the suffix prefill will skip."""
        s = self.prefix_cache.stats
        s["lookups"] += 1
        from ..observability import REGISTRY
        if REGISTRY.enabled:
            REGISTRY.counter("serve.prefix.lookups_total").inc()
        if hit_blocks:
            s["hits"] += 1
            s["hit_blocks"] += hit_blocks
            s["hit_tokens"] += hit_blocks * self.BS
            if REGISTRY.enabled:
                REGISTRY.counter("serve.prefix.hits_total").inc()
                REGISTRY.counter("serve.prefix.hit_tokens_total").inc(
                    hit_blocks * self.BS)

    def _register_prefix(self, prompt: np.ndarray,
                         table: List[int]) -> None:
        """Insert every read-only (full, decode-untouched) prompt block
        into the radix tree — the cache parks one pool reference per
        new block, so retirement releases only the slot's references
        and the prefix outlives the request.  Decode writes start at
        position len(prompt), so all ``full`` blocks are immutable for
        the sequence's lifetime."""
        if not self.enable_prefix_caching:
            return
        full = len(prompt) // self.BS
        took = self.prefix_cache.insert(self._block_keys(prompt, full),
                                        table[:full])
        if took:
            self.alloc.share(took)
            self.stats["prefix_blocks_registered"] += len(took)
            from ..observability import REGISTRY
            if REGISTRY.enabled:
                REGISTRY.counter("serve.prefix.inserts_total").inc(
                    len(took))

    def _best_waiting_index(self) -> Optional[int]:
        """Queue index of the next request to admit: highest priority
        wins; FIFO within a priority class (queue position is arrival
        order — a preempted request re-enters at the FRONT, so it
        resumes before later arrivals of its own class)."""
        best, best_key = None, None
        for i, r in enumerate(self.queue):
            key = (-r.priority, i)
            if best_key is None or key < best_key:
                best, best_key = i, key
        return best

    def _releasable_pages(self, slot: int) -> int:
        """Pages preempting ``slot`` would actually free: pages whose
        only live reference is the slot's (prefix-shared pages survive
        in the index and free nothing)."""
        return sum(1 for p in self.slot_pages[slot]
                   if self.alloc.ref.get(p) == 1)

    def _preempt_for_priority(self) -> None:
        """Evict lowest-priority RUNNING work for a strictly-higher-
        priority waiter when the batch/pool is saturated (ROADMAP
        2(c)).  One victim per pass, bounded by the batch width; a
        victim is only taken when eviction can actually make the
        waiter admissible (a slot opens, and the victims' private
        pages can close the page shortfall), so low-priority work is
        never spilled pointlessly."""
        for _ in range(self.B):
            idx = self._best_waiting_index()
            if idx is None:
                return
            cand = self.queue[idx]
            # nobody to evict (every class equal: the usual case) ends
            # the matter before the waiter's prefix is hashed and the
            # prefix index walked, which cost per cached page, every
            # iteration
            victims = [s for s in range(self.B)
                       if self.slots[s] is not None
                       and self.slots[s].priority < cand.priority]
            if not victims:
                return
            snap = self._spill.get(cand.req_id)
            if snap is not None:
                need, shared = snap.num_blocks, ()
            else:
                # admission reuses the waiter's cached prefix pages and
                # acquires only the remainder — the shortfall tests
                # must see the same need, or a saturated pool would
                # spill a low-priority tenant for a waiter that was
                # already admissible via shared prefix pages (offloaded
                # blocks still consume fresh pages, so they stay in
                # ``need``)
                L, shared, _off = self._cached_prefix(cand.prompt)
                need = self._blocks_needed(
                    len(cand.prompt) + cand.max_new_tokens) - L
            shared_set = set(shared)
            # the waiter's own prefix pages are counted in ``need``
            # already, and admission pins them before acquiring — they
            # are not evictable headroom on top of that
            evictable = sum(1 for p in self.prefix_index.values()
                            if self.alloc.ref.get(p) == 1
                            and p not in shared_set)
            have_slot = any(s is None for s in self.slots)
            if have_slot and self.alloc.free_blocks + evictable >= need:
                return                 # admissible without eviction
            releasable = sum(self._releasable_pages(s) for s in victims)
            if (self.alloc.free_blocks + evictable + releasable) < need:
                return                 # eviction could never admit cand
            # cheapest spill first: lowest priority, then fewest
            # committed KV positions, then slot index (deterministic)
            victims.sort(key=lambda s: (self.slots[s].priority,
                                        int(self.lengths[s]), s))
            # the pages' way out, on the timeline under ``admit``
            tl = self._tl
            sp = tl and tl.enter("kv_snapshot", slot=victims[0])
            rid = self.preempt(victims[0])
            if tl:
                # what went with the pages (a tier at its cap may have
                # dropped the snapshot again already)
                snap = self._spill.get(rid)
                tl.leave(sp, state_bytes=snap.state_nbytes if snap else 0)

    def preempt(self, slot: int) -> int:
        """Evict the RUNNING request in ``slot`` for later resumption:
        snapshot its committed KV pages + decode cursor to the host-RAM
        spill tier (CRC-checked — ``serving/resilience.py``), release
        its pool references through the ordinary ``_free_slot`` path,
        and requeue it at the FRONT of the waiting queue.  The resumed
        stream is bit-identical to an unpreempted run: restore puts the
        exact page bytes into fresh blocks and the sampler is keyed by
        (seed, absolute position), so neither eviction nor re-admission
        can change a token (pinned by tests/test_serving_resilience.py).
        Returns the preempted request id."""
        req = self.slots[slot]
        if req is None:
            raise ValueError(f"slot {slot} is not running a request")
        # the snapshot reads the slot's pages, state and cursor as the
        # step in flight leaves them
        self._drain()
        import time
        from ..serving.resilience import snapshot_slot
        tr = self._trace_of(req)
        t_sp = tr.now() if tr is not None else 0.0
        t0 = time.perf_counter()
        snap = snapshot_slot(self, slot)
        self._spill_put(req.req_id, snap)
        self._free_slot(slot)
        self.queue.appendleft(req)
        dt = time.perf_counter() - t0
        if tr is not None:
            tr.add("preempt_spill", t_sp, tr.now(),
                   committed=int(snap.length), priority=req.priority)
            tr.mark("enqueued")    # queue_wait resumes until re-admission
        self.resilience["preemptions"] += 1
        self.resilience["spill_save_secs"] += dt
        from ..observability import REGISTRY
        if REGISTRY.enabled:
            REGISTRY.counter("serve.resilience.preemptions_total").inc()
            REGISTRY.gauge("serve.resilience.spilled_bytes").set(
                self.spilled_bytes)
            REGISTRY.histogram("serve.resilience.preempt_save_secs",
                               unit="s").record(dt)
            REGISTRY.event("serve", action="preempt", req_id=req.req_id,
                           priority=req.priority,
                           committed=int(snap.length))
        return req.req_id

    def _spill_put(self, req_id: int, snap) -> None:
        """Insert a snapshot into the (possibly capacity-bounded) spill
        tier.  Snapshots evicted to honor the cap DEMOTE their request
        to replay-from-prefix: the request keeps waiting in the queue
        with its committed tokens, and admission recomputes its KV from
        that prefix (``_replay_into_slot``) — a typed event plus the
        ``serve.resilience.spill_evictions_total`` counter per victim,
        never silent host-memory growth."""
        from ..observability import REGISTRY
        for rid in self._spill.put(req_id, snap):
            self.resilience["spill_evictions"] += 1
            if REGISTRY.enabled:
                REGISTRY.counter(
                    "serve.resilience.spill_evictions_total").inc()
                REGISTRY.event("serve", action="spill_evict", req_id=rid,
                               tier_bytes=self._spill.nbytes,
                               cap_bytes=self._spill.capacity_bytes)

    def spill_compatible(self, snap) -> bool:
        """Whether a KV snapshot from another engine can restore into
        THIS pool: identical page geometry (layers, block size, kv
        heads, head dim, dtype) and a table wide enough to hold it —
        the precondition for cross-replica snapshot transplant
        (``serving/fleet.py``).  Quantized pools additionally require
        the snapshot to carry per-page scales (and vice versa) — an
        int8 snapshot can never scatter into a bf16 pool."""
        if (getattr(snap, "k_scale", None) is not None) != \
                self._kv_quant:
            return False
        state = getattr(snap, "ssm_state", None)
        if (state is not None) != self._hybrid or (
                self._hybrid and any(
                    row.shape != full.shape[:1] + full.shape[2:]
                    for row, full in ((state, self.ssm_state),
                                      (snap.conv_state,
                                       self.conv_state)))):
            return False
        ref = self.pool_k.data if self._kv_quant else self.pool_k
        if (snap.v_pages.shape[-1] == 0) != self._latent:
            return False           # a latent snapshot has no value pages
        return (snap.k_pages.shape[0] == ref.shape[0]
                and snap.k_pages.shape[2:] == ref.shape[2:]
                and snap.k_pages.dtype == ref.dtype
                and snap.num_blocks <= self.MB)

    def adopt_preempted(self, req: GenRequest, snap) -> None:
        """Transplant a preempted request (committed tokens + spilled
        KV snapshot) extracted from ANOTHER engine of identical
        geometry: the snapshot enters this engine's spill tier and the
        request joins the FRONT of the queue, so admission restores the
        exact page bytes into fresh local blocks — same path as a local
        preemption, bit-identical resumption."""
        if not self.spill_compatible(snap):
            pshape = (self.pool_k.data if self._kv_quant
                      else self.pool_k).shape
            raise ValueError(
                "KV snapshot geometry does not match this engine's pool "
                f"(snapshot pages {snap.k_pages.shape}, pool {pshape})")
        if req.req_id in self._spill:
            raise ValueError(f"request {req.req_id} already spilled here")
        self.queue.appendleft(req)
        self._spill_put(req.req_id, snap)

    def _restore_preempted(self, slot: int, req: GenRequest, idx: int,
                           snap) -> bool:
        """Re-admit a preempted request: fresh blocks, spilled KV bytes
        scattered back, decode cursor restored — no recompute, no new
        first token.  False when the pool cannot host it yet."""
        import time
        from ..serving.resilience import restore_into_slot
        priv = self._acquire_with_eviction(snap.num_blocks)
        if priv is None:
            return False
        self._drain()
        del self.queue[idx]
        tr = self._trace_of(req)
        if tr is not None:
            t_rs = tr.now()
            tq = tr.take_mark("enqueued")
            if tq is not None:
                tr.add("queue_wait", tq, t_rs)
        self.block_table[slot, :] = -1
        self.block_table[slot, :snap.num_blocks] = priv
        self.slot_pages[slot] = priv
        t0 = time.perf_counter()
        tl = self._tl
        sp = tl and tl.enter("kv_restore", blocks=snap.num_blocks)
        try:
            restore_into_slot(self, slot, snap)
            if tl:
                tl.leave(sp, state_bytes=snap.state_nbytes)
        except BaseException:
            # exactly-once release; the snapshot is unusable, so the
            # request is DROPPED from this engine (a supervising
            # wrapper replays it from its committed prefix instead)
            self.alloc.release(self.slot_pages[slot])
            self.slot_pages[slot] = []
            self.block_table[slot, :] = -1
            del self._spill[req.req_id]
            raise
        del self._spill[req.req_id]
        self.slots[slot] = req
        self.lengths[slot] = snap.length
        self.tokens[slot] = snap.next_token
        dt = time.perf_counter() - t0
        self.resilience["restores"] += 1
        self.resilience["spill_restore_secs"] += dt
        from ..observability import REGISTRY
        if REGISTRY.enabled:
            REGISTRY.counter("serve.resilience.restores_total").inc()
            REGISTRY.gauge("serve.resilience.spilled_bytes").set(
                self.spilled_bytes)
            REGISTRY.histogram("serve.resilience.preempt_restore_secs",
                               unit="s").record(dt)
            REGISTRY.event("serve", action="restore", req_id=req.req_id,
                           priority=req.priority,
                           committed=int(snap.length))
        if tr is not None:
            tr.add("preempt_restore", t_rs, tr.now(),
                   committed=int(snap.length))
        return True

    def _replay_into_slot(self, slot: int, req: GenRequest,
                          idx: int) -> bool:
        """Re-admit a preempted request whose KV snapshot is GONE (the
        bounded spill tier evicted it): recompute the committed KV by
        prefilling the committed token prefix ``prompt + out[:-1]`` and
        resume the decode cursor at the pending token ``out[-1]``.

        Prefill-computed KV is bit-identical to decode-computed KV (the
        foundation of prefix caching and crash replay, pinned since
        ISSUE 11), so demotion costs prefill FLOPs, never tokens.  The
        final-position logits are discarded — they would only
        re-produce ``out[-1]``, which is already committed.  False when
        the pool cannot host the request yet."""
        committed = np.concatenate(
            [req.prompt, np.asarray(req.out[:-1], np.int32)]) \
            if len(req.out) > 1 else req.prompt
        need = self._blocks_needed(len(req.prompt) + req.max_new_tokens)
        L, shared, off = self._cached_prefix(committed)
        self.alloc.share(shared)
        priv = self._acquire_with_eviction(need - L)
        if priv is None:
            self.alloc.release(shared)
            return False
        restored = self._restore_offloaded(off, priv)
        self._note_prefix_lookup(L + restored)
        self.stats["prefix_blocks_reused"] += L + restored
        del self.queue[idx]
        tr = self._trace_of(req)
        if tr is not None:
            t_rp = tr.now()
            tq = tr.take_mark("enqueued")
            if tq is not None:
                tr.add("queue_wait", tq, t_rp)
        table = shared + priv
        self.block_table[slot, :] = -1
        self.block_table[slot, :need] = table
        self.slot_pages[slot] = table
        shadow = GenRequest(req.req_id, committed, 1, None)
        try:
            self._prefill_into_slot(slot, shadow, L + restored)
            self._register_prefix(req.prompt, table)
        except BaseException:
            # exactly-once release, same contract as the fresh path
            self.alloc.release(self.slot_pages[slot])
            self.slot_pages[slot] = []
            self.block_table[slot, :] = -1
            self.queue.appendleft(req)
            raise
        self.slots[slot] = req
        self.lengths[slot] = len(committed)
        self.tokens[slot] = req.out[-1]
        self.resilience["prefix_replays"] += 1
        from ..observability import REGISTRY
        if REGISTRY.enabled:
            REGISTRY.counter(
                "serve.resilience.prefix_replays_total").inc()
            REGISTRY.event("serve", action="prefix_replay",
                           req_id=req.req_id, committed=len(committed))
        if tr is not None:
            tr.add("prefix_replay", t_rp, tr.now(),
                   committed=len(committed),
                   cached_blocks=L + restored)
        return True

    def _prefill_into_slot(self, slot: int, req: GenRequest,
                           L: int) -> np.ndarray:
        """Run the prompt into the slot's (already mapped) pages and
        return next-token logits — the three prefill tiers the admission
        path chooses between.  Extracted so the fault-injection harness
        (tests/faults.py) has one seam for crash-mid-prefill."""
        from ..models.generation import build_llama_decoder
        T0 = len(req.prompt)
        # the honest prefill-cost meter the cache A/B bench reads:
        # tokens whose KV this admission actually computes (cache hits
        # and offload restores shrink it; padding never counts)
        self.stats["prefill_tokens_computed"] += T0 - L * self.BS
        table = self.slot_pages[slot]
        tl = self._tl
        if self._buckets is not None:
            # declared-bucket prefill (cold prompts AND cache-hit
            # suffixes): fixed chunk programs, no per-length jit
            return self._fill_prompt_bucketed(slot, req, L * self.BS)
        if L or self.quant_config is not None or self._hybrid \
                or self._latent:
            # suffix-only prefill against the cached pages (a hybrid's
            # or a latent model's whole prompt, start=0: the chunk fill
            # is its one prefill program; a hybrid's hand-over of the
            # state lives there).  Quantized
            # engines route COLD prompts here too (start=0): the dense
            # tier below computes full-width KV and scatters it into
            # the pool raw, which would skip both the quantized matmul
            # path and the pool's code+scale layout — one prefill tier
            # for every quant admission keeps greedy output
            # bit-identical across cold/hit/replay paths
            suffix = req.prompt[L * self.BS:]
            sp = tl and tl.enter("prefill_chunk", size=len(suffix),
                                valid=len(suffix))
            fill = self._chunk_fill(len(suffix))
            logits = self._run_fill(
                fill, slot, jnp.asarray(self.block_table[slot]),
                jnp.int32(L * self.BS), jnp.asarray(suffix))
            self.prefill_chunks += 1
            self.prefill_tokens_dispatched += len(suffix)
            if tl:
                tl.leave(sp)
            return logits
        # dense prefill, jitted once per distinct prompt length: one
        # unpadded chunk on the timeline
        sp = tl and tl.enter("prefill_chunk", size=T0, valid=T0)
        jprefill = self._prefill_cache.get(T0)
        if jprefill is None:
            prefill, _ = build_llama_decoder(self.cfg, T0,
                                             use_pallas=False)
            jprefill = jax.jit(prefill)
            self._prefill_cache.put(T0, jprefill)
        cache, logits = jprefill(self.params, req.prompt[None, :])
        # move prompt KV into the pool pages ON DEVICE with ONE
        # scatter per pool; the padded tail of the last page
        # holds zeros, masked by lengths
        nb = self._blocks_needed(T0)
        pad = nb * self.BS - T0
        kc, vc = cache["k"][:, 0], cache["v"][:, 0]
        pages = np.asarray(table[:nb])

        def paged_view(x):             # [L, nb, BS, Hkv, D]
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
            return x.reshape(x.shape[0], nb, self.BS,
                             *x.shape[2:])

        self.pool_k = self.pool_k.at[:, pages].set(
            paged_view(kc).astype(self.pool_k.dtype))
        self.pool_v = self.pool_v.at[:, pages].set(
            paged_view(vc).astype(self.pool_v.dtype))
        self.prefill_chunks += 1
        self.prefill_tokens_dispatched += T0
        if tl:
            tl.leave(sp)
        return logits

    def _admit(self) -> None:
        """Admit waiting requests into free slots while pages allow,
        highest priority first (FIFO within a class).  On a
        prefix-cache hit the shared pages are reused and only the
        SUFFIX runs (paged chunk fill); cold prompts prefill densely
        and their KV moves into the pool pages; a PREEMPTED request
        restores its spilled KV into fresh blocks instead of
        recomputing.  Under saturation, strictly-lower-priority running
        requests are evicted for higher-priority waiters
        (``_preempt_for_priority``)."""
        tl = self._tl
        running = self.active_requests
        adm0, chunks0 = self.admissions, self.prefill_chunks
        sp_admit = tl and tl.enter("admit", running=running)
        if self.enable_preemption:
            self._preempt_for_priority()
        for slot in range(self.B):
            if self.slots[slot] is not None:
                continue
            idx = self._best_waiting_index()
            if idx is None:
                break
            req = self.queue[idx]
            snap = self._spill.get(req.req_id)
            if snap is not None:
                if not self._restore_preempted(slot, req, idx, snap):
                    break              # head-of-line waits for pages
                self.admissions += 1
                continue
            if req.out:
                # preempted, but the bounded spill tier evicted the
                # snapshot: demoted to replay-from-prefix
                if not self._replay_into_slot(slot, req, idx):
                    break              # head-of-line waits for pages
                self.admissions += 1
                continue
            T0 = len(req.prompt)
            total = T0 + req.max_new_tokens
            need = self._blocks_needed(total)
            L, shared, off = self._cached_prefix(req.prompt)
            # take the slot's reference FIRST: eviction under pressure
            # must never free (and re-hand-out) a page we are reusing
            self.alloc.share(shared)
            priv = self._acquire_with_eviction(need - L)
            if priv is None:
                self.alloc.release(shared)
                break                      # head-of-line waits for pages
            tr = self._trace_of(req)
            t_rs = tr.now() if tr is not None else 0.0
            # offloaded continuation: exact bytes scatter into the
            # leading private pages (no recompute); a CRC failure
            # cleanly demotes the rest to ordinary suffix prefill
            restored = self._restore_offloaded(off, priv)
            self._note_prefix_lookup(L + restored)
            self.stats["prefix_blocks_reused"] += L + restored
            del self.queue[idx]
            if tr is not None:
                tq = tr.take_mark("enqueued")
                if tq is not None:
                    tr.add("queue_wait", tq, t_rs)
                if off:
                    tr.add("prefix_restore", t_rs, tr.now(),
                           blocks=restored)
                if L + restored:
                    tr.event("prefix_hit", cached_blocks=L,
                             restored_blocks=restored,
                             tokens_skipped=(L + restored) * self.BS)
                t_pf = tr.now()
            # the same interval on the timeline, joined by rid (the
            # trace's outermost id where a router or supervisor renumbers)
            sp_pf = tl and tl.enter(
                "prefill", tokens=T0,
                rid=req.req_id if tr is None or tr.rid is None else tr.rid,
                cached_tokens=(L + restored) * self.BS)
            chunks_pf = self.prefill_chunks
            table = shared + priv
            self.block_table[slot, :] = -1
            self.block_table[slot, :need] = table
            self.slot_pages[slot] = table
            try:
                logits = self._prefill_into_slot(slot, req, L + restored)
                self._register_prefix(req.prompt, table)
                sp = tl and tl.enter("first_token_fetch")
                first = self._pick_token(req, np.asarray(logits)[0],
                                         position=T0)
                self._take_fill_counts()
                if tl:
                    tl.leave(sp)
            except BaseException:
                # exactly-once page release (ISSUE 11 hardening): the
                # slot never went live, so neither cancel() nor a later
                # drain can see these references — drop them here, and
                # keep the request WAITING so a retrying caller (or a
                # supervisor replay) still owns it
                self.alloc.release(self.slot_pages[slot])
                self.slot_pages[slot] = []
                self.block_table[slot, :] = -1
                self.queue.appendleft(req)
                if tl:
                    tl.leave(sp_pf, error=True)
                if tr is not None:
                    tr.add("prefill", t_pf, tr.now(), tokens=T0,
                           error=True)
                    tr.mark("enqueued")   # still waiting (retry/replay)
                raise
            if tl:
                tl.leave(sp_pf, chunks=self.prefill_chunks - chunks_pf)
            if tr is not None:
                tr.add("prefill", t_pf, tr.now(), tokens=T0,
                       cached_tokens=(L + restored) * self.BS)
            self._append_tok(req, first)
            self.slots[slot] = req
            self.lengths[slot] = T0
            self.tokens[slot] = first
            self.admissions += 1
        if self.prefill_chunks > chunks0:
            # every live stream waited through this admission's prefill
            self.stalled_slot_iterations += running
        if tl:
            tl.leave(sp_admit, admitted=self.admissions - adm0)

    @staticmethod
    def _append_tok(req: GenRequest, tok: int) -> None:
        req.out.append(tok)
        if req.eos_token_id is not None and req.eos_pos is None \
                and tok == req.eos_token_id:
            req.eos_pos = len(req.out) - 1

    def _retire_done(self) -> None:
        tl = self._tl
        sp = tl and tl.enter("retire")
        n0 = len(self.finished)
        for s in range(self.B):
            req = self.slots[s]
            if req is not None and (len(req.out) >= req.max_new_tokens
                                    or req.eos_pos is not None):
                # truncate anything after the first eos
                if req.eos_pos is not None:
                    req.out = req.out[:req.eos_pos + 1]
                self._retire(s)
        if tl:
            tl.leave(sp, retired=len(self.finished) - n0)

    def _free_slot(self, slot: int) -> None:
        self.alloc.release(self.slot_pages[slot])
        self.slot_pages[slot] = []
        self.block_table[slot, :] = -1
        self.lengths[slot] = 0
        self.tokens[slot] = 0
        self.slots[slot] = None

    def _retire(self, slot: int) -> None:
        req = self.slots[slot]
        self.finished[req.req_id] = np.concatenate(
            [req.prompt, np.asarray(req.out, np.int32)])
        self._free_slot(slot)

    def cancel(self, req_id: int) -> bool:
        """Abort a queued or in-flight request.  Its pages free
        immediately; no result is reported.  Returns False when the id
        is unknown or already finished.

        Accounting contract (regression-pinned by
        test_serving_engine.py::test_cancel_accounting_*): a WAITING
        request holds no page references, so removal from the queue is
        the whole operation; a SCHEDULED request holds exactly one
        reference per page in its table (including prefix-shared pages,
        whose extra references live in the prefix index) and
        ``_free_slot`` releases each exactly once — the ``_RefPool``
        raises on any double free, so a drift here fails loudly instead
        of corrupting another request's KV."""
        for i, req in enumerate(self.queue):
            if req.req_id == req_id:
                del self.queue[i]
                # a preempted waiter holds no pool references, but its
                # spilled host-RAM snapshot must not outlive it
                self._spill.pop(req_id, None)
                return True
        for slot in range(self.B):
            req = self.slots[slot]
            if req is not None and req.req_id == req_id:
                self._drain()
                self._free_slot(slot)
                return True
        return False

    def _stamp(self, active: List[int], name: str, m0: float, m1: float,
               pre: Optional[Dict[int, int]] = None) -> None:
        """Timeline on: the step ``[m0, m1]`` written into every live
        request's trace as a span ``name`` (with ``pre``, the slots'
        output lengths before a speculative step, also what each
        ``committed``).  The tracer's own work, a ``Trace.add`` under a
        lock a slot: the ``stamp`` span, so that a reader of the time
        between two steps can leave it out."""
        tl = self._tl
        sp = tl.enter("stamp")
        n = 0
        for s in active:
            r = self.slots[s]
            tr = self._trace_of(r) if r is not None else None
            if tr is not None:
                more = {} if pre is None \
                    else {"committed": len(r.out) - pre[s]}
                tr.add(name, m0 - tr.mono_t0, m1 - tr.mono_t0,
                       batch=len(active), **more)
                n += 1
        tl.leave(sp, traces=n)

    #: the fleet router numbers its replicas' engines; a solo engine
    #: has none (the timeline's ``engine_step`` carries it)
    replica: Optional[int] = None

    def step(self) -> Dict[int, np.ndarray]:
        """One scheduler iteration: admit, decode every active slot,
        collect tokens, retire finished.  Returns newly finished
        {req_id: full ids} (empty dict when idle)."""
        from ..observability.tracing import TRACER
        # the ONE tracer read of an iteration: None when it is off, and
        # then no site below stamps a time or opens an annotation (a
        # drain outside step() — cancel, preempt — opens none either)
        tl = self._tl = TRACER.timeline()
        try:
            if tl is None:
                return self._iterate()
            sp = tl.enter("engine_step") if self.replica is None \
                else tl.enter("engine_step", replica=self.replica)
            try:
                return self._iterate()
            finally:
                tl.leave(sp)    # and whatever a raise left open below
        finally:
            self._tl = None

    def _iterate(self) -> Dict[int, np.ndarray]:
        """Retire, admit, then hand the next decode step over.  The step
        picks its greedy row on the device; where no row samples, step
        n+1 is launched on step n's greedy row BEFORE step n is fetched,
        so that the host's seam and the round trip run while the device
        computes: launch the next, consume the previous.  Otherwise:
        launch, consume."""
        tl = self._tl
        # retire first so freed slots/pages admit this very iteration;
        # then AGAIN after admission — the prefill's first token can
        # already satisfy the budget (max_new_tokens=1) or hit eos, and
        # such a request must not enter the decode batch
        self._retire_done()
        self._admit()
        self._retire_done()
        ahead = self._runs_ahead()
        if not ahead:
            self._drain()
        # what the host settles before it hands a step over: who is
        # live, how far the walk goes
        sp = tl and tl.enter("plan")
        prev = self._inflight
        # the request each slot runs in this step: a row whose budget
        # ends with the step in flight has no step left and goes in as a
        # free slot does (no page past those reserved at admission is
        # written)
        reqs = [r if r is not None and r.eos_pos is None and len(r.out) + (
                    prev is not None and prev.reqs[s] is r)
                < r.max_new_tokens else None
                for s, r in enumerate(self.slots)]
        rows = [s for s, r in enumerate(reqs) if r is not None]
        if not rows:
            if tl:
                tl.leave(sp, batch=0)
            if prev is None:
                self.last_logits = None  # nothing decoded this iteration
            self._drain()
            return self._take_finished()
        if self._spec is not None and self._spec.config.enabled:
            # speculative decode: draft K, verify K+1 in one dispatch,
            # commit the accepted prefix (spec_decode/runner.py) —
            # greedy output is bit-identical to the baseline branch
            pre = sum(len(self.slots[s].out) for s in rows)
            pre_by_slot = {s: len(self.slots[s].out) for s in rows} \
                if tl else None
            if tl:
                tl.leave(sp, batch=len(rows))
            m0 = time.monotonic() if tl else 0.0
            sp = tl and tl.enter("spec_decode", batch=len(rows))
            self._spec.run_decode(rows)
            if tl:
                tl.leave(sp, committed=sum(
                    len(self.slots[s].out) for s in rows) - pre)
                m1 = time.monotonic()
                self._stamp(rows, "spec_decode_step", m0, m1,
                            pre_by_slot)
            self.decode_steps += 1
            self.decode_slot_steps += len(rows)
            self.decode_tokens += \
                sum(len(self.slots[s].out) for s in rows) - pre
            return self._take_finished()
        # what the step is handed is a copy: an upload may share the
        # host's memory, and the host moves on before the step ends
        bt, lengths = self.block_table.copy(), self.lengths.copy()
        idle = [s for s, r in enumerate(reqs)
                if r is None and self.slots[s] is not None]
        bt[idle], lengths[idle] = -1, 0
        # the attention of this dispatch sees each row's stored tokens
        # plus the one it appends
        seen = lengths + 1
        trips, chunk_pages = decode_walk(seen, self.MB, self.BS,
                                         self._walk_positions)
        if tl:
            tl.leave(sp, batch=len(rows))
        d = self._launch(reqs, bt, lengths, trips * chunk_pages * self.B,
                         int(np.sum(-(-seen[rows] // self.BS))))
        if ahead:
            self._inflight = d
            if prev is not None:
                self._consume(prev)
        else:
            self._consume(d)
        return self._take_finished()

    def _runs_ahead(self) -> bool:
        """Whether the next decode step may go to the device before the
        one in flight is fetched: not while a live row samples (its
        token is drawn on the host from the fetched logits), nor under
        speculation (its runner reads and writes the host's cursors)."""
        if self._spec is not None and self._spec.config.enabled:
            return False
        return not any(r is not None and (r.temperature or 0.0) > 0.0
                       for r in self.slots)

    def _take_finished(self) -> Dict[int, np.ndarray]:
        out, self.finished = self.finished, {}
        return out

    def _launch(self, reqs: List[Optional[GenRequest]], bt: np.ndarray,
                lengths: np.ndarray, pages_walked: int,
                pages_live: int) -> _Dispatch:
        """Hand the decode step of the slots that run ``reqs`` to the
        device; the host's cursors advance with it.  A row of the step
        in flight is fed that step's greedy row where it lies, on the
        device."""
        tl = self._tl
        prev = self._inflight
        m0 = time.monotonic() if tl else 0.0
        sp = tl and tl.enter("decode_dispatch",
                             batch=sum(r is not None for r in reqs))
        sc = tl and tl.enter(
            "upload", bytes=bt.nbytes + lengths.nbytes + self.tokens.nbytes)
        place = self._row_place
        up = jnp.asarray if place is None \
            else (lambda a: jax.device_put(a, place))
        if prev is None:
            tokens = up(self.tokens.copy())
        else:
            merge = self._ahead_fn or _ahead_tokens
            tokens = merge(up(np.where(
                [prev.reqs[s] is self.slots[s] for s in range(self.B)], -1,
                self.tokens).astype(np.int32)), prev.extra[-1])
        args = (*self._carried(), up(bt), up(lengths), tokens)
        if tl:
            tl.leave(sc)
        # the step returns every row's first choice (after its expert
        # layers' counts where the model routes); when `launch` ends the
        # step is the runtime's, and what the host will fetch of it is
        # queued to come back the moment it is done
        sc = tl and tl.enter("launch")
        ahead = prev is not None and not prev.extra[-1].is_ready()
        # the last consumed step's logits are let go before this step's
        # are made: two sets alive (the one in flight), not three
        self.last_logits = None
        logits, *extra = self._keep(self._step(self.params, *args))
        for a in extra:
            a.copy_to_host_async()
        if tl:
            tl.leave(sc)
            tl.leave(sp)
        # the fed token's KV is stored
        self.lengths += [r is not None for r in reqs]
        return _Dispatch(logits, extra, reqs, pages_walked, pages_live,
                         ahead, m0)

    def _drain(self) -> None:
        """Take the tokens of the step in flight, if one is."""
        d, self._inflight = self._inflight, None
        if d is not None:
            self._consume(d)

    def _consume(self, d: _Dispatch) -> None:
        """The ONE consumer of a dispatched decode step, in either
        order: wait for it, bring its results to the host, count it,
        and append its tokens to the rows whose slot still holds the
        request it ran (a slot retired or refilled since drops its
        row's token)."""
        tl = self._tl
        logits, extra = d.logits, d.extra
        sp = tl and tl.enter("logits_fetch")     # waits for the device
        if tl:
            # the timeline alone splits the wait from the copy: when
            # `device_wait` ends the device is done, on the host's clock
            sc = tl.enter("device_wait")
            jax.block_until_ready((logits, extra))
            tl.leave(sc)
            sc = tl.enter("copy")
        n = sum(r is not None for r in d.reqs)
        # small arrays come to the host (the greedy row, and the expert
        # layers' counts where the model routes); the logits ([B, V]
        # float32: 25.7 MB a step at 64 slots and a 100k vocabulary)
        # stay on the device unless a row samples or a test looks
        *counts, greedy = (np.asarray(a) for a in extra)
        self.last_logits = logits
        fetched = sum(a.nbytes for a in extra)
        if counts:
            self._count_experts(n, counts[0])
        self.stats["decode_fetch_bytes"] += fetched
        if tl:
            tl.leave(sc, bytes=fetched)
            tl.leave(sp, bytes=fetched)
        sp = tl and tl.enter("pick")
        live = [s for s, r in enumerate(d.reqs)
                if r is not None and self.slots[s] is r]
        sampled = [s for s in live
                   if (self.slots[s].temperature or 0.0) > 0.0]
        picks = greedy
        if sampled:
            # ONE dispatch + sync for the whole sampled sub-batch
            picks = greedy.copy()
            picks[sampled] = self._sample_rows(
                [self.slots[s] for s in sampled],
                self.last_logits[sampled],
                [int(self.lengths[s]) for s in sampled])
        for s in live:
            tok = int(picks[s])
            self._append_tok(self.slots[s], tok)
            self.tokens[s] = tok
        if tl:
            tl.leave(sp, sampled=len(sampled))
            self._stamp(live, "decode_step", d.t0, time.monotonic())
        self.decode_steps += 1
        self.decode_slot_steps += n
        self.decode_tokens += n
        self.decode_pages_walked += d.pages_walked
        self.decode_pages_live += d.pages_live
        if self._hybrid:
            self.state_slot_steps += n * self.ssm_state.shape[0]
        self.stats["decode_ahead_steps"] += d.ahead

    def _count_experts(self, n: int, counts: np.ndarray) -> None:
        """Add a decode step's expert counts (``n`` live rows) to the
        ``moe_*`` sums."""
        local, hit, *latent = (int(c) for c in counts)
        cfg, m = self.cfg, self.moe
        layers = getattr(cfg, "num_expert_layers", cfg.num_layers)
        m["moe_assignments_local"] += local
        m["moe_experts_hit"] += hit
        m["moe_assignments_total"] += n * cfg.num_experts_per_tok * layers
        m["moe_expert_slots"] += cfg.experts_held * layers
        if latent:
            m["moe_peak_load"] += latent[0]
            m["moe_step_rows"] += latent[1]

    def run_to_completion(self) -> Dict[int, np.ndarray]:
        """Drive steps until queue and batch drain; returns all results.
        ``self.finished`` is part of the liveness condition: a step that
        raised AFTER retiring a request (e.g. a typed spill-restore
        failure during admission) strands that result in ``finished``,
        and a later drain must still deliver it."""
        results: Dict[int, np.ndarray] = {}
        while self.queue or self.finished \
                or any(s is not None for s in self.slots):
            results.update(self.step())
        return results

    # ------------------------------------------------------------------
    # serve-path introspection (paddle_tpu/serving front-end + telemetry)
    # ------------------------------------------------------------------
    @property
    def queue_depth(self) -> int:
        """Requests admitted into the engine but not yet scheduled."""
        return len(self.queue)

    @property
    def active_requests(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    def batch_occupancy(self) -> float:
        """Fraction of decode-batch slots currently running a request."""
        return self.active_requests / float(self.B)

    def kv_utilization(self) -> float:
        """Fraction of KV pool blocks holding live references (slots or
        prefix index)."""
        return 1.0 - self.alloc.free_blocks / float(self.alloc.num_blocks)

    def kv_leak_report(self) -> Dict[str, int]:
        """Cross-check the refcount pool against the structures that are
        supposed to hold its references (slot tables + prefix index).

        ``leaked`` counts blocks whose refcount disagrees with the
        holders, plus holder entries with no refcount; ``unaccounted``
        counts blocks that are neither free nor referenced.  Both must
        be zero after any drain — asserted by the loadgen smoke and the
        cancellation regression tests."""
        held: Dict[int, int] = {}
        for pages in self.slot_pages:
            for p in pages:
                held[p] = held.get(p, 0) + 1
        for p in self.prefix_index.values():
            held[p] = held.get(p, 0) + 1
        leaked = sum(1 for p, r in self.alloc.ref.items()
                     if held.get(p, 0) != r)
        leaked += sum(1 for p in held if p not in self.alloc.ref)
        report = {
            "free_blocks": self.alloc.free_blocks,
            "index_blocks": len(self.prefix_index),
            "slot_blocks": sum(len(p) for p in self.slot_pages),
            "leaked": leaked,
            "unaccounted": (self.alloc.num_blocks - self.alloc.free_blocks
                            - len(self.alloc.ref)),
        }
        if self._hybrid:
            # a slot's state rows belong to the request in the slot and
            # to nothing else: rows in use = slots running (a drained
            # engine reads 0; a row cannot outlive its slot)
            report["state_rows"] = self.active_requests
        return report

    @property
    def spilled_bytes(self) -> int:
        """Host-RAM bytes currently held by preempted-request KV
        snapshots (the spill tier)."""
        return sum(s.nbytes for s in self._spill.values())

    def resilience_stats(self) -> Dict[str, object]:
        """Preemption-side resilience counters for bench rows / serve
        telemetry (the supervisor adds the crash-recovery side)."""
        s: Dict[str, object] = dict(self.resilience)
        s["spilled_requests"] = len(self._spill)
        s["spilled_bytes"] = self.spilled_bytes
        return s

    def prefix_stats(self) -> Dict[str, object]:
        """Cross-request prefix-cache counters and point-in-time state
        for bench rows / the ``serve.prefix.*`` gauges
        (``ServeMetrics.publish_engine``)."""
        s: Dict[str, object] = dict(self.prefix_cache.stats)
        s["enabled"] = self.enable_prefix_caching
        s["cached_blocks"] = self.prefix_cache.resident_blocks
        s["offloaded_blocks"] = self.prefix_cache.offloaded_blocks
        s["offloaded_bytes"] = self.prefix_cache.host_bytes
        s["prefill_tokens_computed"] = \
            self.stats["prefill_tokens_computed"]
        lk = s["lookups"]
        s["hit_rate"] = (s["hits"] / lk) if lk else None
        return s

    def scheduler_stats(self) -> Dict[str, object]:
        """Admission/prefill accounting for the ``serve.sched.*`` gauges
        (``ServeMetrics.publish_engine``): ``bucket_fill`` is useful
        over dispatched (padded) prefill tokens, ``stalled_share`` the
        share of per-slot decode steps that began behind a prefill —
        the streams whose token gap paid for somebody's prompt;
        ``kv_walk_share`` the part of the page table the decode
        attention gathered (1.0 = its whole width every step),
        ``kv_walk_fill`` the part of what it gathered that was live.
        Both count plain decode dispatches: a speculative dispatch
        (K+1 inner steps of the same program) is left out of the
        walked pages and of the table they are divided by."""
        plain = self.decode_steps - (
            self._spec.stats["spec_steps"] if self._spec else 0)
        s: Dict[str, object] = {
            "decode_pages_walked": self.decode_pages_walked,
            "decode_pages_live": self.decode_pages_live,
            "decode_pages_table": plain * self.B * self.MB,
            "admissions": self.admissions,
            "prefill_chunks": self.prefill_chunks,
            "prefill_tokens_dispatched": self.prefill_tokens_dispatched,
            "prefill_tokens_computed":
                self.stats["prefill_tokens_computed"],
            "stalled_slot_iterations": self.stalled_slot_iterations,
            "decode_slot_steps": self.decode_slot_steps}
        if self._hybrid:
            s["state_slot_steps"] = self.state_slot_steps
        if self._routed:
            s.update(self.moe)
        return sched_ratios(s)

    def spec_stats(self) -> Optional[Dict[str, object]]:
        """Speculation counters for bench rows / serve telemetry, or
        None when the engine decodes baseline (no ``spec_config``).
        ``engine_steps_per_token`` counts per-slot decode iterations
        per decode token, so baseline decode measures exactly 1.0 at
        any batch size — < 1.0 is accepted speculation, nothing else."""
        if self._spec is None:
            return None
        s: Dict[str, object] = dict(self._spec.stats)
        s["enabled"] = self._spec.config.enabled
        s["k"] = self._spec.config.k
        s["acceptance_rate"] = self._spec.acceptance_rate
        s["engine_steps_per_token"] = (
            self.decode_slot_steps / self.decode_tokens
            if self.decode_tokens else None)
        return s

    def kernel_tiers(self) -> Dict[str, Dict[str, Optional[str]]]:
        """The kernel choices the code still makes for this engine, from
        the SAME function the dispatch reads, so a printed tier is the
        one that ran: ``{"ssm_state_update": {"tier": "pallas" | "xla",
        "reason": why the kernel stood down, or None}}`` for a model
        with per-slot state (its family's ``kernel_tiers``: a delta-rule
        model's key is ``kda_state_update``); nothing for the Llama
        family, whose layers have one implementation
        (``ops/decode_block.py``)."""
        tiers = getattr(_model_module(self.cfg), "kernel_tiers", None)
        if tiers is None or not self._hybrid:
            return {}
        return tiers(self.cfg, self.ssm_state.shape)

    def aot_stats(self) -> Dict[str, object]:
        """Warm-start observability for bench rows/telemetry: whether
        artifacts loaded (and why not), plus declared-bucket hit/miss
        counts."""
        s: Dict[str, object] = {"aot_loaded": self.aot_loaded}
        if self.aot_error is not None:
            s["aot_error"] = self.aot_error
        if self._buckets is not None:
            s.update(self._buckets.stats())
        return s
