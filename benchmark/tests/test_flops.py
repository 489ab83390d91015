"""The dense GQA family's FLOP and byte functions (``programs/llama.py``,
found as the harness finds them: through the configuration's
``program`` key) against counts made by hand from the published sizes
of Mistral-7B-v0.3 (H 4096, F 14336, 32 x 128 query heads, 8 KV heads,
V 32768)."""

import pytest

from benchmark.lib import model

flops = model.program_module(
    model.load_json("configs", "mistral-7b-v0.3-serve"))

# by hand, per layer: q and o 4096 x 4096 each, k and v 4096 x 1024 each,
# gate, up and down 4096 x 14336 each
LAYER = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336   # 218,103,808
HEAD = 4096 * 32768                                             # 134,217,728


@pytest.mark.parametrize("name,layers", [("mistral-7b-v0.3-serve", 16),
                                         ("mistral-7b-v0.3-train", 3)])
def test_matmul_params(name, layers):
    cfg = model.load_json("configs", name)
    assert LAYER == 218103808
    assert flops.matmul_params(cfg) == layers * LAYER + HEAD
    # the reference counts the same leaves (plus norm gains, embedding)
    ref = model.reference_module(cfg)
    assert ref.param_count(cfg, with_embedding=False) == \
        flops.matmul_params(cfg) + (2 * layers + 1) * 4096


def test_train_flops_per_token():
    cfg = model.load_json("configs", "mistral-7b-v0.3-train")
    n = 3 * LAYER + HEAD                                  # 788,529,152
    # attention, forward, one 2048-token row: QK^T and PV are each
    # 2 * 128 FLOPs a (query, key) pair and head; causal pairs
    # 2048 * 2049 / 2; 32 heads; 3 layers
    attn = 4 * 32 * 128 * 3 * (2048 * 2049 // 2)
    want = 3 * (2 * n * 2048 + attn) / 2048
    assert flops.train_flops_per_token(cfg, 2048) == pytest.approx(want)
    assert want == pytest.approx(4.8824e9, rel=1e-3)
    # at PR 24's 14,951 tokens/s that is 37.1% of 197e12: a share of
    # the peak that cannot pass 100 until tokens/s pass 40,350
    assert 100 * want * 14951 / 197e12 == pytest.approx(37.05, abs=0.1)
    assert flops.train_attention_flops(cfg, 4, 2048) == 3 * 4 * attn
    # what the harness puts into readings["work"] of one step
    assert flops.train_step_work(cfg, 4, 2048) == {
        "flops": pytest.approx(want * 4 * 2048), "attn_flops": 3 * 4 * attn}


def test_decode_step_flops_and_bytes():
    cfg = model.load_json("configs", "mistral-7b-v0.3-serve")
    n = 16 * LAYER + HEAD
    ctx = [300, 500]
    assert flops.decode_flops(cfg, ctx) == \
        2 * n * 2 + 4 * 32 * 128 * 16 * 800
    # K and V of a cached token: 8 heads x 128 x 2 bytes, twice, 16 layers
    assert flops.kv_bytes(cfg, 1) == 2 * 8 * 128 * 2 * 16 == 65536
    assert flops.decode_bytes(cfg, ctx) == 2 * n + 65536 * 800
    assert flops.weight_bytes(cfg) == pytest.approx(7.25e9, rel=2e-3)
    assert flops.prefill_flops(cfg, 512) == \
        2 * n * 512 + 4 * 32 * 128 * 16 * (512 * 513 // 2)
    # what the harness puts into readings["work"]: a decode step, and a
    # request that got 3 tokens out (the first comes from the prefill)
    assert flops.decode_step_work(cfg, ctx) == {
        "decode_flops": flops.decode_flops(cfg, ctx),
        "decode_bytes": flops.decode_bytes(cfg, ctx)}
    assert flops.request_work(cfg, 512, 3) == {
        "flops": flops.prefill_flops(cfg, 512)
        + flops.decode_flops(cfg, [513, 514])}


def test_unknown_device_kind_is_an_error():
    from benchmark.lib.peaks import peaks_of
    assert peaks_of("TPU v5 lite")["flops"] == 197e12
    assert peaks_of("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_of("cpu")
