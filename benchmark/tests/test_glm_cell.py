"""The latent-attention family's files: the configuration against the
catalog's row, its work counts (``programs/glm_moe_lite.py``) against
counts made by hand from the published sizes of GLM-4.7-Flash, and its
cell's tiny twin through the whole harness on the CPU (the program
module, the reference, the counters the new metric files read)."""

import json
import os

import numpy as np
import pytest

from benchmark.lib import model, peaks, xplane
from benchmark.tests import tiny
from benchmark.tests.test_harness import FIXTURE, run

CONFIG = model.load_json("configs", "glm-4.7-flash-serve")
work = model.program_module(CONFIG)

# by hand, per layer (H 2048; 20 heads of 192 | 64 | 256; ranks 768 / 512)
MLA = 2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448 \
    + 20 * 256 * 2048                                     # 21,757,952
EXPERT = 3 * 2048 * 1536                                  # 9,437,184
SHARED = EXPERT
ROUTER = 2048 * 64                                        # 131,072
DENSE = 3 * 2048 * 10240                                  # 62,914,560
HEAD = 154880 * 2048                                      # 317,194,240
NORMS = 2 * 2048 + 768 + 512                              # a layer's gains

PUBLISHED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "data", "glm-4.7-flash.published.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def published():
    with open(PUBLISHED) as f:
        return json.load(f)


def test_the_configuration_is_the_published_one_but_for_the_cut():
    row = published()
    cut = set(CONFIG["published"])
    assert cut == {"num_hidden_layers", "num_nextn_predict_layers"}
    for k, v in row["config"].items():
        if k not in cut:
            assert CONFIG[k] == v, k
    assert CONFIG["published"] == {
        k: row["config"][k] for k in sorted(cut)}
    # the cut keeps the leading dense layer and six expert layers
    assert (CONFIG["num_hidden_layers"], CONFIG["first_k_dense_replace"],
            CONFIG["num_nextn_predict_layers"]) == (7, 1, 0)
    with open(os.path.join(os.path.dirname(model.HERE),
                           "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "glm-4.7-flash-serve")
    assert set(entry["reduced"]) == cut
    assert entry["source"] == row["source_url"]
    for key in ("rope_interleave", "router_bias_range", "latent_norm_eps",
                "engine"):
        assert key in CONFIG["assumed"], key


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the model-configs catalog is not mounted")
def test_the_published_keys_are_the_catalog_row():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "GLM-4.7-Flash")
    mine = published()
    assert mine["config"] == row["config"]
    assert mine["source_url"] == row["source_url"]


def test_matmul_params_and_reference_leaves():
    assert (MLA, EXPERT, DENSE) == (21757952, 9437184, 62914560)
    assert work.mla_params(CONFIG) == MLA
    assert work.expert_params(CONFIG) == EXPERT
    assert work.dense_mlp_params(CONFIG) == DENSE
    assert work.expert_layer_fixed_params(CONFIG) == ROUTER + SHARED
    per_token = 7 * MLA + DENSE + 6 * (ROUTER + SHARED + 4 * EXPERT) + HEAD
    assert work.matmul_params_per_token(CONFIG) == per_token
    # what the chip HOLDS, by the reference's leaves: every expert, the
    # table beside the head, the gains and the routers' biases
    ref = model.reference_module(CONFIG)
    held = 7 * (MLA + NORMS) + DENSE \
        + 6 * (ROUTER + 64 + SHARED + 64 * EXPERT) + 2 * HEAD + 2048
    assert ref.param_count(CONFIG) == held == 4530936960
    # a pair of query and cached token, one layer: the two forms
    assert work.pair_flops(CONFIG, "expanded") == 2 * 20 * (192 + 64 + 256)
    assert work.pair_flops(CONFIG, "absorbed") == 2 * 20 * (512 + 576) \
        == 43520


def test_decode_step_work():
    ctx = [4096] * 64
    got = work.decode_step_work(CONFIG, ctx)
    share = 1 - (1 - 4 / 64) ** 64                         # 98.4% hit
    weights = 2 * (7 * MLA + DENSE + 6 * (ROUTER + SHARED) + HEAD
                   + 6 * 64 * EXPERT * share)
    latent = 7 * 576 * 2 * 64 * 4096
    assert got["decode_bytes"] == pytest.approx(weights + latent)
    assert got["mla_decode_bytes"] == latent
    assert 10.0e9 < got["decode_bytes"] < 11.0e9
    per_token = 2 * work.matmul_params_per_token(CONFIG)
    attn = 7 * 43520 * 64 * 4096
    assert got["decode_flops"] == pytest.approx(64 * per_token + attn)
    assert got["mla_decode_flops"] == attn


def test_request_work():
    got = work.request_work(CONFIG, 100, 3)["flops"]
    per_token = 2 * work.matmul_params_per_token(CONFIG)
    # the prompt's pairs in the expanded form, the two decode steps'
    # in the absorbed form
    attn = 7 * (20480 * (100 * 101 // 2) + 43520 * (101 + 102))
    assert got == pytest.approx(102 * per_token + attn)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("root")))


def test_the_cell_runs_through_the_harness(root, monkeypatch):
    """The tiny twin of ``glm47flash-long``: ``correct``, no page
    leaked, and the traced line holds every metric of the cell that a
    CPU can read."""
    monkeypatch.setattr(xplane, "find_xplane", lambda d: FIXTURE)
    monkeypatch.setitem(peaks.PEAKS, "cpu",
                        {"flops": 197e12, "bytes_per_s": 819e9})
    rc, lines, err = run(root, "tiny-glm47flash", 2 ** 31 + 5, seconds=2.0,
                         trace=True)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True, err
    assert last["failed"] == 0 and last["checks"]["kv_leaked_blocks"][
        "value"] == 0
    got = {k: v["value"] for k, v in last["metrics"].items()}
    for name in ("serve_mfu", "decode_step_ms", "iter_host_ms",
                 "decode_batch", "device_idle", "kv_walk_share",
                 "kv_walk_fill", "experts_hit_share", "prefill_share",
                 "bucket_fill", "expert_load_peak"):
        assert got[name + ".glm47flash"] > 0, name
    assert got["experts_hit_share.glm47flash"] <= 100
    # 8 experts, 2 a token: an even share is 12.5% of a layer's pairs,
    # and one expert can hold at most one pair a row (50%)
    assert 12.5 <= got["expert_load_peak.glm47flash"] <= 50
    assert got["decode_batch.glm47flash"] <= 4


def test_the_control_and_the_planted_fault(root):
    """``served_gaps`` with the float8 control compares the same
    positions and ranks by its own choice; and the planted fault (the
    router's bias in the weights too) changes the gate's weights and not
    its choice."""
    import jax.numpy as jnp
    from paddle_tpu.parallel import moe
    cfg = model.load_json("configs", "glm-4.7-flash-serve", root)
    ref = model.reference_module(cfg)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, cfg["vocab_size"], n) for n in (30, 44)]
    rand = ref.served_gaps(cfg, 9, seqs, [10, 20], 64, "float32")
    low = ref.served_gaps(cfg, 9, seqs, [10, 20], 64, "float32",
                          control="fp8")
    assert low["tokens"] == rand["tokens"] == 20 + 24
    assert 0 <= low["mean_gap"] < rand["mean_gap"]
    assert low["agree"] > rand["agree"]
    sound = moe.route_sigmoid
    try:
        work.planted_fault().wrap_engine(None)
        logits = jnp.asarray(rng.normal(size=(50, 8)), jnp.float32)
        bias = jnp.asarray(rng.uniform(-0.1, 0.1, 8), jnp.float32)
        w0, i0 = sound(logits, bias, 2, scale=1.8)
        w1, i1 = moe.route_sigmoid(logits, bias, 2, scale=1.8)
    finally:
        moe.route_sigmoid = sound
    np.testing.assert_array_equal(i0, i1)
    assert float(jnp.abs(w0 - w1).max()) > 0.01
    np.testing.assert_allclose(w1.sum(-1), 1.8, rtol=1e-6)
