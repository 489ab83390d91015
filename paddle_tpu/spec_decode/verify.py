"""The verify program: K+1 target-model decode positions, one dispatch.

Bit-identity is the whole design.  The pinned acceptance criterion is
that greedy speculative decode emits EXACTLY the baseline greedy stream,
and the only way to guarantee that on every backend is to make the
verify program compute the SAME floating-point operations as the
baseline decode step — so ``build_verify_program`` takes the engine's
own ``_build_step`` closure and runs it K+1 times under ``lax.scan``
inside one jitted program.  Each scan iteration appends one token's KV
through ``paged_append`` and produces the decode-step logits for the
next position; the per-iteration HLO is the decode step's, so logits
and pool contents match sequential baseline decode bit-for-bit
(asserted by tests/test_spec_decode.py).

What this buys: one host dispatch + one device sync per K+1 positions
instead of per token, and one SCHEDULER iteration per accepted run —
engine-steps-per-token drops below 1.0 (the serve bench's extra.spec
row).  What it does not buy: intra-verify parallelism across the K+1
positions — that is the block-fusion work of ROADMAP item 2 (a chunked
parallel verify must share the fused block kernel's numerics story to
keep the bit-identity pin; until then, sequential-in-program is the
honest CPU-tier shape).

Rollback contract: the program ALWAYS writes K+1 positions of KV per
slot (fixed width); the host commits only the accepted prefix by
advancing ``lengths`` that far.  Rejected-tail writes land at positions
>= the committed length, which every subsequent attention masks out and
the next append overwrites — the pages themselves stay owned by the
slot (the engine maps a request's full page budget at admission), so
rollback never touches the refcount pool.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["build_verify_program"]


def build_verify_program(step_fn):
    """Wrap a decode-step closure (``ContinuousBatchingEngine.
    _build_step()``'s return) into ``verify(params, pool_k, pool_v,
    block_table, lengths, tokens [B, K+1]) -> (pool_k, pool_v,
    logits [B, K+1, V])``.

    ``tokens[:, 0]`` is each slot's fed token (the engine's
    ``self.tokens``), columns 1..K the draft proposals; ``logits[:, i]``
    is the target's next-token distribution after consuming
    ``tokens[:, :i+1]`` — exactly what ``step_fn`` would have returned
    on the i-th sequential call.  The K+1 width is baked at trace time
    (the jitted program is specialized per (max_batch, k) geometry,
    which the AOT manifest records)."""

    def verify(params, pool_k, pool_v, block_table, lengths, tokens):
        def body(carry, tok):
            pk, pv, ln = carry
            # the step's greedy row, after its logits, is not read here
            pk, pv, logits = step_fn(params, pk, pv, block_table, ln,
                                     tok)[:3]
            return (pk, pv, ln + 1), logits

        (pk, pv, _), logits = jax.lax.scan(
            body, (pool_k, pool_v, lengths),
            jnp.swapaxes(tokens, 0, 1))
        return pk, pv, jnp.swapaxes(logits, 0, 1)

    return verify
