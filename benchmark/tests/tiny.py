"""A second root of data files, made from the real ones at a size a CPU
test can hold (llama_tiny widths, short lengths).  It is written into a
temporary directory: that the harness runs from it is also the proof
that cells, configurations, mixes and metrics are found as files, by
name, with no edit to a file that exists."""

from __future__ import annotations

import glob
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_WIDTHS = {"hidden_size": 64, "intermediate_size": 128,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "vocab_size": 256, "max_position_embeddings": 256,
               "num_hidden_layers": 2}
CELLS = {"mistral7b-train": "tiny-train", "mistral7b-batch": "tiny-batch",
         "mistral7b-chat": "tiny-chat"}


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def make_root(dst: str, limits=None) -> str:
    for src in glob.glob(os.path.join(HERE, "configs", "*.json")):
        cfg = dict(_load(src), **TINY_WIDTHS)
        if "engine" in cfg["assumed"]:
            cfg["assumed"]["engine"] = {
                "max_batch": 4, "block_size": 8, "num_blocks": 64,
                "max_blocks_per_seq": 16, "prefill_buckets": [8, 32]}
        _dump(os.path.join(dst, "configs", os.path.basename(src)), cfg)
    for src in glob.glob(os.path.join(HERE, "traffic", "*.json")):
        t = _load(src)
        if t["kind"] == "train_stream":
            t.update(batch=4, seq_len=32, document_tokens=dict(
                t["document_tokens"], median=12, min=2, max=64))
        else:
            t.update(prompt_tokens=dict(t["prompt_tokens"], min=4, max=40),
                     output_tokens=dict(t["output_tokens"], min=4, max=12),
                     plan_requests=4096, check_requests=3)
            if t["arrivals"]["process"] == "poisson":
                t["arrivals"]["rate_rps"] = 20.0
            else:
                t["arrivals"]["min_waiting"] = 8
        t["trace_seconds"] = 0.5
        _dump(os.path.join(dst, "traffic", os.path.basename(src)), t)
    for src in glob.glob(os.path.join(HERE, "workloads", "*.json")):
        name = os.path.basename(src)[:-5]
        w = _load(src)
        if limits and name in limits:
            w["limits"] = dict(w["limits"], **limits[name])
        if "min_tokens_compared" in w["limits"]:
            w["limits"]["min_tokens_compared"] = 8
        _dump(os.path.join(dst, "workloads", CELLS[name] + ".json"), w)
    for src in glob.glob(os.path.join(HERE, "metrics", "*.json")):
        m = _load(src)
        m["workloads"] = [CELLS[c] for c in m["workloads"]]
        _dump(os.path.join(dst, "metrics", os.path.basename(src)), m)
    return dst
