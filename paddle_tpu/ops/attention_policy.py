"""Attention backend selection: XLA dense fused attention vs Pallas flash.

Mirror of the reference's per-shape scaled-dot-product backend dispatch
(/root/reference/python/paddle/nn/functional/flash_attention.py:976 picks
flash / mem-efficient / math per shape+dtype support), grounded in this
repo's v5e measurements (round-4 v5e sweep, record removed):

* XLA's fused dense attention is 15-47% FASTER than the in-tree flash
  kernel whenever its softmax residuals fit in HBM (56.9k vs 48.0k tok/s
  at GPT-125M b8 s1024; 11.4k vs 7.8k tok/s at h2048 s2048 remat).
* The dense path OOMs once the saved [L, B, H, Sq, Sk] f32 logits outgrow
  HBM (observed at b>=16 GPT-125M s1024 without remat: ~19 GB at b32).

So flash is the memory-ENABLING path and dense the speed path until the
flash kernel itself beats XLA (block tuning is ongoing): ``prefer_flash``
returns True only when the dense residual footprint would crowd HBM.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

# Share of the HBM the train state leaves free that dense residuals may
# take; the rest is for activations, temporaries and fragmentation.
_DENSE_BUDGET_FRAC = 0.35


@functools.lru_cache(maxsize=1)
def hbm_bytes_per_device() -> float:
    """Per-device HBM capacity as the TPU's allocator reports it
    (``bytes_limit``; a TPU whose stats cannot be read is an error, not
    a guessed size); 'unbounded' (so dense always wins) on CPU hosts.
    Cached: capacity is fixed for the process lifetime and prefer_flash
    sits on hot paths."""
    import jax
    from ..core.device import on_tpu
    if not on_tpu():
        return float("inf")
    return float(jax.local_devices()[0].memory_stats()["bytes_limit"])


def dense_residual_bytes(q_shape: Sequence[int], k_shape: Sequence[int],
                         layers_live: int) -> float:
    """HBM the dense path pins for backward: f32 logits/probs of every
    live layer ([B, Hq, Sq, Sk] per layer; XLA saves them at f32 — the
    b32 OOM measured 19 GB, exactly L*B*H*S*S*4)."""
    b, sq, hq = q_shape[0], q_shape[1], q_shape[2]
    sk = k_shape[1]
    return 4.0 * b * hq * sq * sk * max(1, layers_live)


def prefer_flash(q_shape: Sequence[int], k_shape: Sequence[int],
                 num_layers: int, remat: bool = False,
                 hbm_bytes: Optional[float] = None,
                 budget_frac: float = _DENSE_BUDGET_FRAC,
                 state_bytes: float = 0.0) -> bool:
    """Decide the attention backend for a training step.

    ``q_shape``/``k_shape``: [B, S, H, D] (device-LOCAL shapes — call
    inside shard_map so dp/mp/sep sharding is already applied).
    ``num_layers``: layers resident on this device (num_layers / pp).
    ``remat``: under rematerialization only ~2 layers of residuals are
    live at once (the recomputed layer + the one being differentiated).
    ``state_bytes``: per-device bytes the step's state pins (params,
    grads, optimizer moments); the budget is a share of what is left.
    Without it a 7B-width 4-layer step (10 GB of state on a 16 GB chip)
    picked dense and was refused by the TPU compiler for HBM.
    """
    live = 2 if remat else num_layers
    hbm = hbm_bytes if hbm_bytes is not None else hbm_bytes_per_device()
    return dense_residual_bytes(q_shape, k_shape, live) \
        > budget_frac * max(hbm - state_bytes, 0.0)


def make_auto_attn(num_layers: int, pp_degree: int, num_microbatches: int,
                   schedule: str, remat: bool, remat_policy,
                   flash_fn: Callable, dense_fn: Callable,
                   state_bytes: Optional[Callable[[], float]] = None
                   ) -> Callable:
    """Build the shared ``attn(q, k, v)`` auto-backend closure for the
    model train-step builders (gpt.py / llama.py — single source so the
    residency model cannot diverge between them).

    Residency model: residuals live per stage = resident layers x
    in-flight microbatches (1F1B keeps up to ``pp_degree`` in flight,
    GPipe all of them).  A remat_policy that SAVES batched-dot outputs
    ("dots_saveable"/"everything", or any unknown callable — assumed
    saving) pins the dense logits despite remat, so it is treated as
    remat=False for the decision.

    ``state_bytes``: zero-argument callable giving the per-device bytes
    the step's state pins (``parallel.manual.train_state_bytes``), read
    at trace time.
    """
    in_flight = num_microbatches if schedule == "gpipe" \
        else min(num_microbatches, pp_degree)
    live = (num_layers // max(1, pp_degree)) * max(1, in_flight)
    saves_logits = callable(remat_policy) or \
        remat_policy in ("dots_saveable", "everything")
    eff_remat = remat and not saves_logits

    def attn(q, k, v):
        pinned = state_bytes() if state_bytes is not None else 0.0
        if prefer_flash(q.shape, k.shape, live, eff_remat,
                        state_bytes=pinned):
            return flash_fn(q, k, v)
        return dense_fn(q, k, v)

    return attn
