"""The hybrid family's files: its work counts (``programs/
granite_hybrid.py``) against counts made by hand from the published
sizes of granite-4.0-h-small, and its cell's tiny twin through the whole
harness on the CPU (the program module, the reference, the counters the
new metric files read)."""

import json
import os

import numpy as np
import pytest

from benchmark.lib import model, peaks, xplane
from benchmark.tests import tiny
from benchmark.tests.test_harness import FIXTURE, run

CONFIG = model.load_json("configs", "granite-4.0-h-small-serve")
work = model.program_module(CONFIG)

# by hand, bf16, per layer (H 4096; Mamba: 128 heads x 64, state 128,
# d_inner 8192, conv over 8192 + 2 x 128 = 8448 channels, width 4)
IN_PROJ = 4096 * (8192 + 8448 + 128)                      # 68,681,728
OUT_PROJ = 8192 * 4096                                    # 33,554,432
MAMBA = IN_PROJ + OUT_PROJ                                # 102,236,160
ATTN = 2 * 4096 * 4096 + 2 * 4096 * 1024                  # 41,943,040
EXPERT = 3 * 4096 * 768                                   # 9,437,184
SHARED = 3 * 4096 * 1536                                  # 18,874,368
ROUTER = 4096 * 72                                        # 294,912
TABLE = 100352 * 4096                                     # 411,041,792


PUBLISHED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "data", "granite-4.0-h-small.published.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def published():
    with open(PUBLISHED) as f:
        return json.load(f)


def test_the_configuration_is_the_published_one_but_for_the_cut():
    """Against the published keys, kept beside the tests as data."""
    row = published()
    cut = set(CONFIG["published"])
    assert cut == {"num_hidden_layers", "layer_types", "num_local_experts"}
    for k, v in row["config"].items():
        if k not in cut:
            assert CONFIG[k] == v, k
    assert CONFIG["layer_types"] == row["config"]["layer_types"][:10]
    assert CONFIG["router_num_experts"] == \
        row["config"]["num_local_experts"] == 72


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the model-configs catalog is not mounted")
def test_the_published_keys_are_the_catalog_row():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-small")
    mine = published()
    assert mine["config"] == row["config"]
    assert mine["source_url"] == row["source_url"]


def test_matmul_params_and_reference_leaves():
    assert (MAMBA, ATTN, EXPERT) == (102236160, 41943040, 9437184)
    assert work.mix_params(CONFIG, "mamba") == MAMBA
    assert work.mix_params(CONFIG, "attention") == ATTN
    assert work.expert_params(CONFIG) == EXPERT
    assert work.dense_ffn_params(CONFIG) == ROUTER + SHARED
    assert work.experts_per_token(CONFIG) == 5.0          # 10 x 36 / 72
    per_token = 9 * MAMBA + ATTN + 10 * (ROUTER + SHARED + 5 * EXPERT) \
        + TABLE
    assert work.matmul_params_per_token(CONFIG) == per_token
    # what the chip HOLDS, counted by the reference's leaves: the
    # matrices above with all 36 experts, and the small leaves (two norm
    # gains a layer, per Mamba layer the conv 8448 x 4 + 8448, dt_bias,
    # A_log, D 3 x 128 and the gated norm's 8192, the last norm)
    ref = model.reference_module(CONFIG)
    small = 10 * 2 * 4096 + 9 * (8448 * 5 + 3 * 128 + 8192) + 4096
    held = 9 * MAMBA + ATTN + 10 * (ROUTER + SHARED + 36 * EXPERT) + TABLE
    assert ref.param_count(CONFIG) == held + small
    assert 9.92e9 < 2 * ref.param_count(CONFIG) < 9.94e9   # 9.93 GB


def test_decode_step_work():
    ctx = [200] * 64
    got = work.decode_step_work(CONFIG, ctx)
    share = 1 - (1 - 10 / 72) ** 64
    weights = 2 * (9 * MAMBA + ATTN + 10 * (ROUTER + SHARED) + TABLE
                   + 10 * 36 * EXPERT * share)
    state = 2 * 9 * 64 * (128 * 64 * 128 * 4 + 8448 * 3 * 2)
    kv = 2 * 8 * 128 * 1 * 2 * 64 * 200
    assert got["decode_bytes"] == pytest.approx(weights + state + kv)
    assert 14.5e9 < got["decode_bytes"] < 15.5e9
    per_token = 2 * work.matmul_params_per_token(CONFIG) \
        + 9 * (5 * 128 * 64 * 128 + 2 * 8448 * 4)
    attn = 4 * 32 * 128 * 1 * 64 * 200
    assert got["decode_flops"] == pytest.approx(64 * per_token + attn)
    # the kernel's own: the float32 state once in, once out; 5 FLOPs an
    # element (decay, dt x B^T and the sum: 3; S C: 2)
    assert got["ssm_state_update_bytes"] == 2 * 9 * 64 * 128 * 64 * 128 * 4
    assert got["ssm_state_update_flops"] == 5 * 9 * 64 * 128 * 64 * 128


def test_request_work():
    got = work.request_work(CONFIG, 100, 3)["flops"]
    per_token = 2 * work.matmul_params_per_token(CONFIG) \
        + work.ssm_flops_per_token(CONFIG)
    attn = 4 * 32 * 128 * (100 * 101 // 2 + 101 + 102)
    assert got == pytest.approx(102 * per_token + attn)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("root")))


def test_the_cell_runs_through_the_harness(root, monkeypatch):
    """The tiny twin of ``granite4h-batch`` (4 of 8 experts held):
    ``correct``, no page leaked, and the traced line holds every metric
    of the cell that a CPU can read."""
    monkeypatch.setattr(xplane, "find_xplane", lambda d: FIXTURE)
    monkeypatch.setitem(peaks.PEAKS, "cpu",
                        {"flops": 197e12, "bytes_per_s": 819e9})
    rc, lines, err = run(root, "tiny-granite4h", 2 ** 31 + 5, seconds=2.0,
                         trace=True)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True, err
    assert last["failed"] == 0 and last["checks"]["kv_leaked_blocks"][
        "value"] == 0
    got = {k: v["value"] for k, v in last["metrics"].items()}
    for name in ("serve_mfu", "decode_step_ms", "iter_host_ms",
                 "decode_batch", "device_idle", "moe_local_share",
                 "experts_hit_share", "kv_walk_share", "kv_walk_fill"):
        assert got[name + ".granite4h"] > 0, name
    assert 35 < got["moe_local_share.granite4h"] < 65
    assert got["experts_hit_share.granite4h"] <= 100
    assert got["decode_batch.granite4h"] <= 4


def test_the_control_ranks_by_its_own_choice(root):
    """``served_gaps`` with the float8 control: the same positions are
    compared, and the control's own first choice lies nearer the
    reference's best than a random token does (what the limits between
    program and control are on the chip is PERF.md's matter)."""
    cfg = model.load_json("configs", "granite-4.0-h-small-serve", root)
    ref = model.reference_module(cfg)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, cfg["vocab_size"], n) for n in (30, 44)]
    rand = ref.served_gaps(cfg, 9, seqs, [10, 20], 64, "float32")
    low = ref.served_gaps(cfg, 9, seqs, [10, 20], 64, "float32",
                          control="fp8")
    assert low["tokens"] == rand["tokens"] == 20 + 24
    assert 0 <= low["mean_gap"] < rand["mean_gap"]
    assert low["agree"] > rand["agree"]
