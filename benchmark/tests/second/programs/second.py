"""A second program module, for ``test_harness.py``: found as a file by
the ``program`` key of a configuration, and unlike ``llama`` wherever
the harness might still assume ``llama``:

* its parameter tree is ``{"outer": {...}, "layers": {name: [L, ...]}}``
  (no ``blocks``, no leading axis): only ``build_engine`` knows how the
  engine wants it;
* its work goes under names of its own (``mixer_flops``,
  ``mixer_bytes``), and it offers no ``decode_flops`` / ``decode_bytes``;
* its engine counts ``state_syncs`` and records a ``state_sync`` span
  on the timeline: names the harness holds nowhere."""

from __future__ import annotations

from typing import Any, Dict, Iterable

from benchmark.programs import llama


def program_config(config: Dict[str, Any]):
    return llama.program_config(config)


def make_params(config: Dict[str, Any], seed: int):
    """The same draw as ``llama``'s (the equations are the same), laid
    out otherwise."""
    p = llama.make_params(config, seed)
    return {"outer": {k: v for k, v in p.items() if k != "blocks"},
            "layers": {k: v[0] for k, v in p["blocks"].items()}}


def build_engine(cfg, params, engine: Dict[str, Any]):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.observability.tracing import TRACER

    class Engine(ContinuousBatchingEngine):
        state_syncs = 0

        def step(self):
            tl = TRACER.timeline()
            sp = tl and tl.enter("state_sync", slots=self.active_requests)
            self.state_syncs += 1
            if tl:
                tl.leave(sp)
            return super().step()

        def scheduler_stats(self):
            return dict(super().scheduler_stats(),
                        state_syncs=self.state_syncs)

    tree = dict(params["outer"],
                blocks={k: v[None] for k, v in params["layers"].items()})
    return Engine(cfg, tree, max_batch=engine["max_batch"],
                  block_size=engine["block_size"],
                  num_blocks=engine["num_blocks"],
                  max_blocks_per_seq=engine["max_blocks_per_seq"],
                  prefill_buckets=tuple(engine["prefill_buckets"]))


def request_work(cfg: Dict, prompt_len: int, new_tokens: int
                 ) -> Dict[str, float]:
    return {"flops": 7 * (prompt_len + new_tokens)}


def decode_step_work(cfg: Dict, contexts: Iterable[int]) -> Dict[str, float]:
    n = len(list(contexts))
    return {"mixer_flops": 2 * 2048 ** 3 * n, "mixer_bytes": 3 * n}
