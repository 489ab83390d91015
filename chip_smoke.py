#!/usr/bin/env python3
"""The quickest proof that paddle_tpu still starts on the chip.

One process, the entry points a user would call, one supported model at
its published widths (llama_7b: H 4096, F 11008, 32 x 128 heads,
V 32000; depth cut to fit one 16 GB chip, weights random from --seed):

* serve: the HTTP CLI's own server (``serving.http.parse_args`` +
  ``build_frontend`` -> ``ContinuousBatchingEngine`` -> ``ServingFrontend``
  -> ``HttpServingServer`` on port 0) answers concurrent
  ``POST /v1/generate`` requests over real localhost sockets; one greedy
  stream must equal ``engine.run_to_completion`` for the same prompt on
  a fresh engine, and shutdown must report zero leaked KV blocks;
* train: ``build_llama_train_step`` at b4 x s2048 takes a few steps on a
  fixed seeded batch; the loss must stay finite and fall.

``--chips 4`` runs ONLY the sharded trainer (mp=2 x sharding=2 over four
chips) and the one-device step it is compared with.

Without a TPU the script exits non-zero before any phase (``--tiny``
relaxes that for a CPU rehearsal at llama_tiny shapes and reports the
platform it really ran on).  Any phase that raises, or any check that
fails, makes the exit code non-zero.  The last line of stdout is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import gc
import http.client
import json
import sys
import threading
import time

import numpy as np

# Depth and pool size are what the v5e compiler's memory_analysis() of the
# real programs leaves room for on a 15.75 GiB chip (PR 22, CHANGES.md):
# serve, 16 layers = 6.52 GiB of bf16 weights; the decode step holds the
# scanned KV pool twice and re-lays-out weights, so a 16k-token pool
# (num_blocks 1024: 4 GiB, peak 17.0 GiB) is refused and an 8k-token pool
# (512: 2 GiB) peaks at 13.8 GiB.  Train, b4 x s2048 with fp32 Adam
# moments: 4 layers peak at 15.47 GiB, 3 layers at 12.5 GiB.
SERVE_LAYERS = 16
SERVE_NUM_BLOCKS = 512     # x block_size 16 = 8k tokens
TRAIN_LAYERS = 3
NEW_TOKENS = 32
# one-device vs four-device first-step loss: both run bf16 matmuls, and
# mp=2 sums each row-parallel product in two halves, so the logits
# differ in their last bf16 digits; the mean NLL over 8192 tokens moves
# far less than this
LOSS_TOL = 2e-2


def say(**record) -> None:
    print(json.dumps(record, default=lambda o: o.item()), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: CHECK FAILED: {what}")


def memory(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {k: stats.get(k) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


COMPILE_KEYS = ("n_compiles", "compile_secs", "cache_hits", "cache_misses")


def compile_report(monitor, since: dict) -> dict:
    now = monitor.summary()
    return {k: round(now[k] - since.get(k, 0), 3) for k in COMPILE_KEYS}


# ---------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------
def generate(port: int, body: dict, out: dict, key: str) -> None:
    """One POST /v1/generate over a real socket; SSE unless the body
    says ``"stream": false``.  Leaves the terminal payload in
    ``out[key]`` (a raised exception leaves nothing, which fails the
    caller's check)."""
    from paddle_tpu.serving.http import iter_sse
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    try:
        conn.request("POST", "/v1/generate", body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if body.get("stream", True):
            streamed, final = [], None
            for event, payload in iter_sse(resp):
                if event == "token":
                    streamed.append(payload["t"])
                else:
                    final = dict(payload, event=event)
            out[key] = dict(final or {}, status=resp.status,
                            streamed=streamed)
        else:
            out[key] = dict(json.loads(resp.read()), status=resp.status)
    finally:
        conn.close()


def serve_phase(tiny: bool, seed: int, monitor) -> None:
    import jax
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.serving import HttpServingServer
    from paddle_tpu.serving.http import build_frontend, parse_args

    t0, c0 = time.perf_counter(), monitor.summary()
    if tiny:
        cli = ["--model", "llama_tiny", "--num-blocks", "64",
               "--prefill-buckets", "8", "32"]
        lens = (6, 40)
    else:
        cli = ["--model", "llama_7b", "--dtype", "bfloat16",
               "--num-layers", str(SERVE_LAYERS),
               "--num-blocks", str(SERVE_NUM_BLOCKS),
               "--prefill-buckets", "32", "128"]
        lens = (24, 150)
    args = parse_args(cli + ["--port", "0", "--max-batch", "4",
                             "--seed", str(seed)])
    fe = build_frontend(args)
    eng = fe.engine
    cfg = eng.cfg
    geometry = dict(max_batch=eng.B, block_size=eng.BS,
                    num_blocks=eng.alloc.num_blocks,
                    prefill_buckets=tuple(args.prefill_buckets))
    built_s = time.perf_counter() - t0
    mem_built = memory(jax.devices()[0])

    rng = np.random.default_rng(seed)
    prompts = {name: rng.integers(0, cfg.vocab_size, (n,)).tolist()
               for name, n in (("long", lens[1]), ("short", lens[0]),
                               ("long_json", lens[1]),
                               ("short_b", lens[0]))}
    srv = HttpServingServer(fe, host=args.host, port=args.port).start()
    answers: dict = {}
    threads = [threading.Thread(
        target=generate, name=f"client-{name}",
        args=(srv.port, {"prompt_ids": ids, "max_new_tokens": NEW_TOKENS,
                         "stream": name != "long_json"}, answers, name))
        for name, ids in prompts.items()]
    t1 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    check(not any(t.is_alive() for t in threads),
          "every HTTP client returned within 900 s")
    served_s = time.perf_counter() - t1
    report = srv.close()

    for name in prompts:
        a = answers.get(name)
        check(a is not None, f"request {name!r} got an answer")
        check(a["status"] == 200 and a.get("state") == "FINISHED",
              f"request {name!r} FINISHED with 200, got {a}")
        toks = a["tokens"]
        check(len(toks) == NEW_TOKENS, f"{name!r}: {NEW_TOKENS} new tokens")
        check(all(0 <= t < cfg.vocab_size for t in toks),
              f"{name!r}: token ids in [0, {cfg.vocab_size})")
        if "streamed" in a:
            check(a["streamed"] == toks,
                  f"{name!r}: SSE token events equal the done payload")
    check(eng.decode_slot_steps > eng.decode_steps,
          "some decode step ran a batch > 1")
    check(report["kv_leaked_blocks"] == 0,
          f"zero leaked KV blocks at shutdown, got {report}")
    decode = dict(steps=eng.decode_steps, slot_steps=eng.decode_slot_steps)
    stats = dict(eng.stats, **eng.aot_stats())
    mem_served = memory(jax.devices()[0])

    # the reference: the same weights in a FRESH engine of the same
    # geometry (no prefix cache to hit), driven directly
    params = eng.params
    del srv, fe, eng
    gc.collect()
    ref_eng = ContinuousBatchingEngine(cfg, params, **geometry)
    rid = ref_eng.add_request(np.asarray(prompts["long"], np.int32),
                              NEW_TOKENS)
    ref = ref_eng.run_to_completion()[rid]
    ref_new = [int(t) for t in ref[-NEW_TOKENS:]]
    check(ref_new == answers["long"]["streamed"],
          "the greedy HTTP stream equals engine.run_to_completion on a "
          f"fresh engine: {answers['long']['streamed']} vs {ref_new}")
    check(ref_eng.kv_leak_report()["leaked"] == 0,
          "the reference engine leaked no KV blocks")
    del ref_eng, params
    gc.collect()

    say(phase="serve", model=args.model, dtype=cfg.dtype,
        num_layers=cfg.num_layers,
        depth_cut=None if tiny else f"32 -> {cfg.num_layers} layers "
        "(published widths kept; weights + KV pool must fit one chip)",
        hidden=cfg.hidden_size, ffn=cfg.intermediate_size,
        heads=cfg.num_heads, vocab=cfg.vocab_size, **geometry,
        kv_pool_tokens=geometry["num_blocks"] * geometry["block_size"],
        requests=len(prompts), prompt_lens=lens, new_tokens=NEW_TOKENS,
        decode=decode, engine_stats=stats, shutdown=report,
        build_secs=round(built_s, 2), serve_secs=round(served_s, 2),
        memory_after_build=mem_built, memory_after_serving=mem_served,
        compiles=compile_report(monitor, c0))


# ---------------------------------------------------------------------
# train
# ---------------------------------------------------------------------
def train_tiers(cfg, topo, b: int, s: int) -> dict:
    """Which tier attention and the CE head run on in the train step on
    ``topo``, from the functions (and the inputs) the step's own
    dispatch reads at trace time."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.device import on_tpu
    from paddle_tpu.models.llama import init_llama_params, llama_param_specs
    from paddle_tpu.ops.attention_policy import (hbm_bytes_per_device,
                                                 prefer_flash)
    from paddle_tpu.ops.fused_cross_entropy import pallas_unsupported_reason
    from paddle_tpu.parallel.manual import train_state_bytes
    mp = topo.get_model_parallel_world_size()
    data_ways = topo.axis_size("dp") * topo.axis_size("sharding")
    q = (b // data_ways, s, cfg.num_heads // mp, cfg.head_dim)
    k = (b // data_ways, s, cfg.kv_heads // mp, cfg.head_dim)
    state = train_state_bytes(
        jax.eval_shape(lambda: init_llama_params(cfg, topo)),
        llama_param_specs(cfg, topo)[0], topo)
    if jax.default_backend() == "cpu":
        attn = {"tier": "xla", "reason": "CPU backend: dense attention"}
    elif prefer_flash(q, k, cfg.num_layers, remat=True, state_bytes=state):
        attn = {"tier": "pallas", "reason": None}
    else:
        attn = {"tier": "xla", "reason":
                "attention_policy: dense residuals fit 0.35 x what "
                f"{state} B of train state leave of "
                f"{int(hbm_bytes_per_device())} B of HBM"}
    dt = jnp.dtype(cfg.dtype)
    reason = pallas_unsupported_reason(
        jax.ShapeDtypeStruct((b * s, cfg.hidden_size), dt),
        jax.ShapeDtypeStruct((cfg.hidden_size, cfg.vocab_size), dt),
        "mp" if mp > 1 else None)
    if reason is None and not on_tpu():
        reason = "not on a TPU"
    head = {"tier": "pallas" if reason is None else "xla", "reason": reason}
    return {"attention": attn, "ce_head": head}


def run_trainer(cfg, devices, steps: int, b: int, s: int, seed: int,
                **degrees):
    """Build the hybrid train step on ``devices`` and take ``steps``
    steps on one fixed seeded batch.  Returns (losses, per-device
    memory while the state is alive, build+first-step seconds, the
    kernel tiers the step ran on)."""
    import jax
    from paddle_tpu import parallel as dist
    from paddle_tpu.models.llama import build_llama_train_step

    t0 = time.perf_counter()
    topo = dist.init_topology(devices=devices, **degrees)
    step_fn, init_fn = build_llama_train_step(
        cfg, topo, num_microbatches=1, remat=True, sharding_stage=2)
    state = init_fn(seed)
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size, (b, s)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)
    losses, first_s = [], None
    for _ in range(steps):
        state, loss = step_fn(state, ids, labels)
        losses.append(float(loss))
        if first_s is None:
            first_s = time.perf_counter() - t0
    jax.block_until_ready(state)
    mem = [memory(d) for d in devices]
    del state
    gc.collect()
    return losses, mem, first_s, train_tiers(cfg, topo, b, s)


def check_losses(losses, what: str) -> None:
    check(all(np.isfinite(x) for x in losses), f"{what}: finite loss "
          f"at every step, got {losses}")
    check(losses[-1] < losses[0],
          f"{what}: loss lower at the last step than the first, "
          f"got {losses}")


def train_config(tiny: bool):
    from paddle_tpu.models.llama import llama_7b, llama_tiny
    if tiny:
        return llama_tiny(dtype="bfloat16"), 4, 64
    return llama_7b(dtype="bfloat16", num_layers=TRAIN_LAYERS), 4, 2048


def train_phase(tiny: bool, seed: int, monitor) -> None:
    import jax
    c0 = monitor.summary()
    cfg, b, s = train_config(tiny)
    losses, mem, first_s, tiers = run_trainer(cfg, jax.devices()[:1], 5,
                                              b, s, seed)
    check_losses(losses, "train")
    say(phase="train", model="llama_tiny" if tiny else "llama_7b",
        dtype=cfg.dtype, num_layers=cfg.num_layers,
        depth_cut=None if tiny else f"32 -> {cfg.num_layers} layers "
        "(published widths kept; fp32 Adam moments must fit one chip)",
        batch=b, seq=s, steps=len(losses), losses=losses,
        tiers=tiers,
        build_and_first_step_secs=round(first_s, 2), memory=mem[0],
        compiles=compile_report(monitor, c0))


def sharded_train_phase(tiny: bool, seed: int, monitor) -> None:
    """Only the four-chip path and what it is compared with."""
    import jax
    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs four devices, jax found "
          f"{len(devices)}")
    c0 = monitor.summary()
    cfg, b, s = train_config(tiny)
    one, _, one_s, one_tiers = run_trainer(cfg, devices[:1], 3, b, s,
                                           seed)
    four, mem, four_s, four_tiers = run_trainer(cfg, devices[:4], 3, b, s,
                                                seed, mp=2, sharding=2)
    check_losses(one, "one device")
    check_losses(four, "mp=2 x sharding=2")
    check(abs(one[0] - four[0]) <= LOSS_TOL,
          f"first-step loss agrees within {LOSS_TOL}: one device "
          f"{one[0]} vs four {four[0]}")
    in_use = [m["bytes_in_use"] for m in mem]
    if None not in in_use:         # the CPU backend reports no stats
        check(min(in_use) >= 0.5 * max(in_use) > 0,
              f"the state is spread over four devices, not parked on "
              f"one: bytes_in_use {in_use}")
    say(phase="train_4chip", topology="mp=2 x sharding=2 (ZeRO stage 2)",
        model="llama_tiny" if tiny else "llama_7b", dtype=cfg.dtype,
        num_layers=cfg.num_layers, batch=b, seq=s,
        losses_one_device=one, losses_four_devices=four,
        first_step_abs_diff=abs(one[0] - four[0]), tolerance=LOSS_TOL,
        bytes_in_use_per_device=in_use, memory_per_device=mem,
        tiers={"one_device": one_tiers, "four_devices": four_tiers},
        build_and_first_step_secs={"one": round(one_s, 2),
                                   "four": round(four_s, 2)},
        compiles=compile_report(monitor, c0))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded trainer and its "
                         "one-device comparison")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal: llama_tiny shapes, device "
                         "check relaxed")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.tiny:
        print(f"chip_smoke: needs a TPU; jax found "
              f"{len(devices)} x {devices[0].platform!r} "
              "(--tiny rehearses on the CPU)", file=sys.stderr)
        return 1

    import paddle_tpu
    from paddle_tpu import native
    from paddle_tpu.core.device import enable_compile_cache
    from paddle_tpu.observability import CompileMonitor
    cache_dir = enable_compile_cache()
    monitor = CompileMonitor().install()
    say(phase="start", paddle_tpu=paddle_tpu.__version__,
        jax=jax.__version__, platform=devices[0].platform,
        device_kind=devices[0].device_kind, device_count=len(devices),
        compile_cache_dir=cache_dir,
        native_available=native.available(),
        native_unavailable_reason=native.unavailable_reason(),
        tiny=args.tiny, chips=args.chips, seed=args.seed)
    if args.chips == 4:
        sharded_train_phase(args.tiny, args.seed, monitor)
    else:
        serve_phase(args.tiny, args.seed, monitor)
        train_phase(args.tiny, args.seed, monitor)
    say(phase="compile_cache", dir=cache_dir,
        **compile_report(monitor, {}))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
