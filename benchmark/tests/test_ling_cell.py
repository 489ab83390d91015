"""The linear-attention family's files: the configuration against the
catalog's row, its work counts (``programs/ling_linear.py``) against
counts made by hand from the published sizes of Ling-3.0-flash, and its
cell's tiny twin through the whole harness on the CPU (the program
module, the reference, the counters the new metric files read)."""

import json
import os

import numpy as np
import pytest

from benchmark.lib import model, peaks, xplane
from benchmark.tests import tiny
from benchmark.tests.test_harness import FIXTURE, run

CONFIG = model.load_json("configs", "ling-3.0-flash-serve")
work = model.program_module(CONFIG)

# by hand, per layer (H 2560; 32 heads; KDA keys and values 128; latent
# heads of 128 | 64 | 128 over a 512-wide latent)
KDA = 4 * 2560 * 4096 + 4096 * 2560 + 2 * 2560 * 32       # 52,592,640
LATENT = 2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 \
    + 32 * 128 * 2560 + 2560 * 32                         # 31,965,184
EXPERT = 3 * 2560 * 768                                   # 5,898,240
SHARED = EXPERT
ROUTER = 2560 * 512                                       # 1,310,720
DENSE = 3 * 2560 * 6144                                   # 47,185,920
HEAD = 39296 * 2560                                       # 100,597,760
STATE = 32 * 128 * 128                                    # a slot a layer
# the small leaves: a KDA layer's convs, dt_bias, A_log, output norm; a
# latent layer's latent norm
KDA_SMALL = 3 * 4096 * 4 + 4096 + 32 + 4096
LATENT_SMALL = 512

PUBLISHED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "data", "ling-3.0-flash-vl.published.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def published():
    with open(PUBLISHED) as f:
        return json.load(f)


def test_the_configuration_is_the_published_one_but_for_the_cut():
    row = published()
    cut = set(CONFIG["published"])
    assert cut == {"num_hidden_layers", "first_k_dense_replace",
                   "num_experts", "vocab_size"}
    for k, v in row["config"].items():
        if k not in cut:
            assert CONFIG[k] == v, k
    assert CONFIG["published"] == {
        k: row["config"][k] for k in sorted(cut)}
    # layer 0 (dense) and six expert layers: one whole period, its
    # latent layer the sixth; 128 of the 512 experts, a quarter of the
    # vocabulary; the router whole
    assert (CONFIG["num_hidden_layers"], CONFIG["first_k_dense_replace"],
            CONFIG["num_experts"], CONFIG["router_num_experts"],
            CONFIG["vocab_size"]) == (7, 1, 128, 512, 157184 // 4)
    z = model.reference_module(CONFIG).sizes(CONFIG)
    assert z["mixers"] == ("kda",) * 5 + ("attention", "kda")
    assert z["types"][0] == "kda_dense" and z["types"][5] == \
        "attention_expert"
    with open(os.path.join(os.path.dirname(model.HERE),
                           "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "ling-3.0-flash-serve")
    assert set(entry["reduced"]) == cut
    assert entry["source"] == row["source_url"]
    for key in ("layer_types", "kda", "kda_safe_gate", "use_qk_norm",
                "rope_interleave", "kda_init", "router_bias_range",
                "swiglu_limits", "not_held", "engine"):
        assert key in CONFIG["assumed"], key
    assert "4 chips a stage" in CONFIG["deployment"]


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the model-configs catalog is not mounted")
def test_the_published_keys_are_the_catalog_row():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash-VL")
    mine = published()
    assert mine["config"] == row["config"]
    assert mine["source_url"] == row["source_url"]


def test_matmul_params_and_reference_leaves():
    assert (KDA, LATENT, EXPERT, DENSE) == (52592640, 31965184, 5898240,
                                            47185920)
    assert work.mix_params(CONFIG, "kda") == KDA
    assert work.mix_params(CONFIG, "attention") == LATENT
    assert work.expert_params(CONFIG) == EXPERT
    assert work.dense_mlp_params(CONFIG) == DENSE
    assert work.expert_layer_fixed_params(CONFIG) == ROUTER + SHARED
    assert work.experts_per_token(CONFIG) == 2.0          # 8 x 128 / 512
    per_token = 6 * KDA + LATENT + DENSE \
        + 6 * (ROUTER + SHARED + 2 * EXPERT) + HEAD
    assert work.matmul_params_per_token(CONFIG) == per_token
    # what the chip HOLDS, by the reference's leaves: the 128 held
    # experts, the table beside the head, the gains, the routers' biases
    ref = model.reference_module(CONFIG)
    held = 6 * (KDA + KDA_SMALL) + LATENT + LATENT_SMALL + 7 * 2 * 2560 \
        + DENSE + 6 * (ROUTER + 512 + SHARED + 128 * EXPERT) \
        + 2 * HEAD + 2560
    assert ref.param_count(CONFIG) == held == 5169390784
    assert work.pair_flops(CONFIG, "expanded") == 2 * 32 * (128 + 64 + 128)
    assert work.pair_flops(CONFIG, "absorbed") == 2 * 32 * (1024 + 64)


def test_decode_step_work():
    ctx = [1300] * 128
    got = work.decode_step_work(CONFIG, ctx)
    share = 1 - (1 - 8 / 512) ** 128                       # 86.7% hit
    assert 0.866 < share < 0.868
    weights = 2 * (6 * KDA + LATENT + DENSE + 6 * (ROUTER + SHARED) + HEAD
                   + 6 * 128 * EXPERT * share)
    state = 2 * 6 * 128 * STATE * 4
    tails = 2 * 6 * 128 * 3 * 3 * 4096 * 2
    latent = 576 * 2 * 128 * 1300
    assert got["decode_bytes"] == pytest.approx(
        weights + state + tails + latent)
    assert got["kda_state_update_bytes"] == state == 3221225472
    assert got["kda_state_update_flops"] == 7 * 6 * 128 * STATE
    # the issue's reckoning: ~12.4 GB a step, the state and the held
    # experts 90% of it, the latent under 2%
    assert 12.2e9 < got["decode_bytes"] < 12.6e9
    assert latent < 0.02 * got["decode_bytes"]
    per_token = 2 * work.matmul_params_per_token(CONFIG) \
        + 6 * (7 * STATE + 2 * 3 * 4096 * 4)
    attn = 2 * 32 * 1088 * 128 * 1300
    assert got["decode_flops"] == pytest.approx(128 * per_token + attn)


def test_request_work():
    got = work.request_work(CONFIG, 100, 3)["flops"]
    per_token = 2 * work.matmul_params_per_token(CONFIG) \
        + work.kda_flops_per_token(CONFIG)
    # ONE latent layer: the prompt's pairs in the expanded form, the two
    # decode steps' in the absorbed form
    attn = 20480 * (100 * 101 // 2) + 69632 * (101 + 102)
    assert got == pytest.approx(102 * per_token + attn)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("root")))


def test_the_cell_runs_through_the_harness(root, monkeypatch):
    """The tiny twin of ``ling3flash-reason``: ``correct``, no page
    leaked, and the traced line holds every metric of the cell that a
    CPU can read."""
    monkeypatch.setattr(xplane, "find_xplane", lambda d: FIXTURE)
    monkeypatch.setitem(peaks.PEAKS, "cpu",
                        {"flops": 197e12, "bytes_per_s": 819e9})
    rc, lines, err = run(root, "tiny-ling3flash", 2 ** 31 + 5, seconds=2.0,
                         trace=True)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True, err
    assert last["failed"] == 0 and last["checks"]["kv_leaked_blocks"][
        "value"] == 0
    got = {k: v["value"] for k, v in last["metrics"].items()}
    for name in ("serve_mfu", "decode_step_ms", "iter_host_ms",
                 "decode_batch", "device_idle", "kv_walk_share",
                 "kv_walk_fill", "experts_hit_share", "moe_local_share",
                 "prefill_share", "bucket_fill", "expert_load_peak",
                 "moe_fill_slack"):
        assert got[name + ".ling3flash"] > 0, name
    assert got["experts_hit_share.ling3flash"] <= 100
    # 8 of the router's 16 experts held, 2 a token
    assert 20 <= got["moe_local_share.ling3flash"] <= 80
    assert got["decode_batch.ling3flash"] <= 4
    # the masked form multiplies every row by every held expert
    assert got["moe_fill_slack.ling3flash"] >= 1


def test_the_control_and_the_planted_fault(root):
    """``served_gaps`` with the float8 control compares the same
    positions and ranks by its own choice; and the planted fault (the
    decay after the update) is in place of both ops."""
    from paddle_tpu.ops import kda
    cfg = model.load_json("configs", "ling-3.0-flash-serve", root)
    ref = model.reference_module(cfg)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, cfg["vocab_size"], n) for n in (30, 44)]
    rand = ref.served_gaps(cfg, 9, seqs, [10, 20], 64, "float32")
    low = ref.served_gaps(cfg, 9, seqs, [10, 20], 64, "float32",
                          control="fp8")
    assert low["tokens"] == rand["tokens"] == 20 + 24
    assert 0 <= low["mean_gap"] < rand["mean_gap"]
    assert low["agree"] > rand["agree"]
    sound = (kda.kda_chunk_scan, kda.kda_state_update_row)
    fault = work.planted_fault()
    try:
        fault.wrap_engine(None)
        assert kda.kda_chunk_scan is not sound[0]
        assert kda.kda_state_update_row is not sound[1]
    finally:
        fault.unwrap()
    assert (kda.kda_chunk_scan, kda.kda_state_update_row) == sound
