"""The shared VMEM cost model (ISSUE 10): static estimate ==
interpret-mode-measured kernel allocation, and the runtime gates route
through it.

The measurement: ``pl.pallas_call`` is wrapped so each invocation
records what the kernel actually DECLARES — every in/out BlockSpec's
block shape at the argument's runtime dtype plus every VMEM
scratch_shapes entry — which is exactly the per-grid-step VMEM
residency Mosaic will allocate (modulo tile padding, absorbed by
``cost.SAFETY_FRACTION``).  The pin: ``cost.decode_block_vmem`` /
``cost.linear_ce_vmem`` match that measurement within
``cost.MODEL_TOLERANCE`` for the decode-block megakernel and the fused
CE head.  If someone adds a scratch buffer to a kernel and forgets the
cost model (or vice versa), this fails.

Also the ISSUE 10 acceptance grep: no second hardcoded VMEM constant
exists outside ``analysis/kernel/cost.py`` — the runtime fusion
fallback (``unsupported_reason``) and the autotune validity filters
read the one budget table.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from paddle_tpu.analysis.kernel import cost
from paddle_tpu.core.flags import FLAGS, set_flags

rng = np.random.default_rng(3)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _interpret():
    old = FLAGS.pallas_interpret
    set_flags({"pallas_interpret": True})
    yield
    set_flags({"pallas_interpret": old})


class _Capture:
    """Record (in_specs, out_specs, scratch, arg/out dtypes) per
    pallas_call invocation; pass everything through untouched."""

    def __init__(self):
        self.calls = []

    def install(self, monkeypatch):
        real = pl.pallas_call

        def wrapper(kernel, **kw):
            inner = real(kernel, **kw)

            def runner(*args):
                self.calls.append((kw, [getattr(a, "dtype", None)
                                        for a in args]))
                return inner(*args)
            return runner

        monkeypatch.setattr(pl, "pallas_call", wrapper)

    @staticmethod
    def _block_bytes(spec, dtype):
        shape = getattr(spec, "block_shape", None)
        if shape is None or dtype is None:
            return 0                      # SMEM / ANY / whole-array refs
        n = 1
        for d in shape:
            n *= 1 if d is None else int(d)
        return n * jnp.dtype(dtype).itemsize

    def measured_bytes(self, call_index=0):
        """Declared per-grid-step VMEM bytes of one recorded call."""
        kw, arg_dtypes = self.calls[call_index]
        total = 0
        in_specs = kw.get("in_specs") or []
        for spec, dt in zip(in_specs, arg_dtypes):
            total += self._block_bytes(spec, dt)
        out_specs = kw.get("out_specs")
        out_shape = kw.get("out_shape")
        out_specs = out_specs if isinstance(out_specs, (list, tuple)) \
            else [out_specs]
        out_shape = out_shape if isinstance(out_shape, (list, tuple)) \
            else [out_shape]
        for spec, sds in zip(out_specs, out_shape):
            total += self._block_bytes(spec, getattr(sds, "dtype", None))
        for scr in kw.get("scratch_shapes") or []:
            dt = getattr(scr, "dtype", None)
            if dt is None or "sem" in str(dt):
                continue                  # semaphores occupy no VMEM data
            n = math.prod(getattr(scr, "shape", ()) or ())
            total += n * jnp.dtype(dt).itemsize
        return total


def _rel_diff(a, b):
    return abs(a - b) / max(a, b, 1)


# ---------------------------------------------------------------------------
# decode_block: static estimate vs captured kernel declaration
# ---------------------------------------------------------------------------
def _decode_case(dtype=np.float32):
    from paddle_tpu.ops.decode_block import DecodeBlockSpec
    H, Hq, Hkv, D, F, BS = 32, 4, 2, 8, 48, 4
    spec = DecodeBlockSpec(hidden=H, num_heads=Hq, kv_heads=Hkv,
                           head_dim=D, block_size=BS, norm="rms",
                           activation="swiglu", eps=1e-5, rope=True)

    def w(*shape):
        return jnp.asarray(
            rng.standard_normal(shape).astype(np.float32) * 0.1, dtype)

    lp = {"ln1_w": w(H) + 1.0, "q_w": w(H, Hq * D), "k_w": w(H, Hkv * D),
          "v_w": w(H, Hkv * D), "o_w": w(Hq * D, H), "ln2_w": w(H) + 1.0,
          "gate_w": w(H, F), "up_w": w(H, F), "down_w": w(F, H)}
    B, NB = 2, 16
    pool_k, pool_v = w(NB, BS, Hkv, D), w(NB, BS, Hkv, D)
    bt = jnp.asarray(np.array([[2, 5, -1, -1, -1, -1],
                               [1, 4, -1, -1, -1, -1]], np.int32))
    lengths = jnp.asarray(np.array([5, 3], np.int32))
    x = w(B, H)
    cos, sin = w(B, D), w(B, D)
    return spec, lp, x, pool_k, pool_v, bt, lengths, cos, sin


@pytest.mark.parametrize("pages", [1, 2])
def test_decode_block_static_estimate_matches_measured(monkeypatch,
                                                       pages):
    from paddle_tpu.ops.pallas.decode_block import (_weight_names,
                                                    decode_block_pallas)
    spec, lp, x, pk, pv, bt, ln, cos, sin = _decode_case()
    cap = _Capture()
    cap.install(monkeypatch)
    out, _, _ = decode_block_pallas(x, lp, pk, pv, bt, ln, cos, sin,
                                    spec=spec, pages=pages)
    assert np.isfinite(np.asarray(out)).all()
    assert len(cap.calls) == 1
    measured = cap.measured_bytes(0)
    wbytes = sum(lp[n].size * lp[n].dtype.itemsize
                 for n in _weight_names(spec))
    est = cost.decode_block_vmem(
        hidden=spec.hidden, num_heads=spec.num_heads,
        kv_heads=spec.kv_heads, head_dim=spec.head_dim,
        block_size=spec.block_size, pages=pages, weight_bytes=wbytes,
        pool_itemsize=pk.dtype.itemsize, x_itemsize=x.dtype.itemsize)
    assert _rel_diff(est["total"], measured) <= cost.MODEL_TOLERANCE, (
        f"static {est} vs measured {measured}")


def test_decode_block_bf16_pools_shrink_staging(monkeypatch):
    """The model tracks dtypes: bf16 pools halve the staging bytes and
    the measured capture agrees."""
    from paddle_tpu.ops.pallas.decode_block import (_weight_names,
                                                    decode_block_pallas)
    spec, lp, x, pk, pv, bt, ln, cos, sin = _decode_case(jnp.bfloat16)
    cap = _Capture()
    cap.install(monkeypatch)
    decode_block_pallas(x, lp, pk, pv, bt, ln, cos, sin, spec=spec,
                        pages=2)
    measured = cap.measured_bytes(0)
    wbytes = sum(lp[n].size * lp[n].dtype.itemsize
                 for n in _weight_names(spec))
    est = cost.decode_block_vmem(
        hidden=spec.hidden, num_heads=spec.num_heads,
        kv_heads=spec.kv_heads, head_dim=spec.head_dim,
        block_size=spec.block_size, pages=2, weight_bytes=wbytes,
        pool_itemsize=2, x_itemsize=2)
    assert _rel_diff(est["total"], measured) <= cost.MODEL_TOLERANCE


# ---------------------------------------------------------------------------
# quantized decode_block (ISSUE 16): the dtype-aware model vs capture
# ---------------------------------------------------------------------------
def _quantize_case(qc, kv_quant=False):
    from paddle_tpu.ops.decode_block import DecodeBlockSpec
    from paddle_tpu.ops.paged_kv import QuantizedKVPool, quantize_kv
    from paddle_tpu.ops.pallas.decode_block import _MATMUL_NAMES
    from paddle_tpu.quantization.serve import _quantize_matrix
    spec, lp, x, pk, pv, bt, ln, cos, sin = _decode_case()
    spec = DecodeBlockSpec(
        hidden=spec.hidden, num_heads=spec.num_heads,
        kv_heads=spec.kv_heads, head_dim=spec.head_dim,
        block_size=spec.block_size, norm="rms", activation="swiglu",
        eps=1e-5, rope=True, weight_dtype=qc.weight_dtype,
        group_size=qc.group_size)
    qlp = {}
    for n, v in lp.items():
        if n in _MATMUL_NAMES:
            q, s = _quantize_matrix(np.asarray(v, np.float32), qc)
            qlp[n + "__q"] = jnp.asarray(q)
            qlp[n + "__s"] = jnp.asarray(s)
        else:
            qlp[n] = v
    if kv_quant:
        pk = QuantizedKVPool(*quantize_kv(pk))
        pv = QuantizedKVPool(*quantize_kv(pv))
    return spec, qlp, x, pk, pv, bt, ln, cos, sin


@pytest.mark.parametrize("wdt,gs", [("int8", -1), ("int8", 64),
                                    ("int4", 64)])
def test_decode_block_quant_weights_estimate_matches_measured(
        monkeypatch, wdt, gs):
    """Static ``decode_block_vmem`` with quantized weight bytes ==
    the interpret-captured declaration: int8 codes stream at 1 B,
    int4 at half rows, scales ride along fp32 — within
    MODEL_TOLERANCE.  (The test geometry's K=32/48 rows round up to
    one 64-group, so gs=64 exercises the grouped layout.)"""
    from paddle_tpu.ops.pallas.decode_block import (_param_keys,
                                                    decode_block_pallas)
    from paddle_tpu.quantization import ServeQuantConfig
    qc = ServeQuantConfig(weight_dtype=wdt, group_size=gs)
    spec, qlp, x, pk, pv, bt, ln, cos, sin = _quantize_case(qc)
    cap = _Capture()
    cap.install(monkeypatch)
    out, _, _ = decode_block_pallas(x, qlp, pk, pv, bt, ln, cos, sin,
                                    spec=spec, pages=2)
    assert np.isfinite(np.asarray(out)).all()
    measured = cap.measured_bytes(0)
    wbytes = sum(qlp[n].size * qlp[n].dtype.itemsize
                 for n in _param_keys(spec))
    est = cost.decode_block_vmem(
        hidden=spec.hidden, num_heads=spec.num_heads,
        kv_heads=spec.kv_heads, head_dim=spec.head_dim,
        block_size=spec.block_size, pages=2, weight_bytes=wbytes,
        pool_itemsize=4, x_itemsize=4)
    assert _rel_diff(est["total"], measured) <= cost.MODEL_TOLERANCE, (
        f"static {est} vs measured {measured}")
    # and the closed-form weight-bytes model matches the actual leaves
    F = qlp["gate_w__q"].shape[-1]
    assert cost.decode_block_weight_bytes(
        hidden=spec.hidden, num_heads=spec.num_heads,
        kv_heads=spec.kv_heads, head_dim=spec.head_dim, ffn_hidden=F,
        weight_dtype=wdt, group_size=gs, itemsize_=4) == wbytes


def test_decode_block_kv_quant_estimate_matches_measured(monkeypatch):
    """int8 KV pools: codes stage at 1 B/elt plus fp32 scale rows per
    page, and the new-token k/v io rows stay fp32 — the model tracks
    the 4-buffer DMA within MODEL_TOLERANCE."""
    from paddle_tpu.ops.pallas.decode_block import (_param_keys,
                                                    decode_block_pallas)
    from paddle_tpu.quantization import ServeQuantConfig
    qc = ServeQuantConfig(weight_dtype="int8", kv_dtype="int8")
    spec, qlp, x, pk, pv, bt, ln, cos, sin = _quantize_case(
        qc, kv_quant=True)
    cap = _Capture()
    cap.install(monkeypatch)
    decode_block_pallas(x, qlp, pk, pv, bt, ln, cos, sin, spec=spec,
                        pages=2)
    measured = cap.measured_bytes(0)
    wbytes = sum(qlp[n].size * qlp[n].dtype.itemsize
                 for n in _param_keys(spec))
    est = cost.decode_block_vmem(
        hidden=spec.hidden, num_heads=spec.num_heads,
        kv_heads=spec.kv_heads, head_dim=spec.head_dim,
        block_size=spec.block_size, pages=2, weight_bytes=wbytes,
        pool_itemsize=1, x_itemsize=4, kv_quant=True)
    assert _rel_diff(est["total"], measured) <= cost.MODEL_TOLERANCE, (
        f"static {est} vs measured {measured}")
    # the scale staging is real: the kv_quant estimate exceeds the
    # same geometry priced without it at int8 pool itemsize
    plain = cost.decode_block_vmem(
        hidden=spec.hidden, num_heads=spec.num_heads,
        kv_heads=spec.kv_heads, head_dim=spec.head_dim,
        block_size=spec.block_size, pages=2, weight_bytes=wbytes,
        pool_itemsize=1, x_itemsize=4)
    assert est["staging"] > plain["staging"]


def test_autotune_candidates_use_dtype_aware_model():
    """The pages-candidate filter prices quantized weights through the
    dtype-aware model: a llama-7B-width layer admits NO candidates at
    bf16 but a non-empty set under int8 weight storage."""
    from paddle_tpu.ops.decode_block import DecodeBlockSpec
    from paddle_tpu.ops.pallas.decode_block import (VMEM_BUDGET_BYTES,
                                                    _fitting_candidates,
                                                    _vmem_total)
    W = dict(hidden=896, num_heads=14, kv_heads=2, head_dim=64)
    bf16 = DecodeBlockSpec(block_size=4, norm="rms",
                           activation="swiglu", eps=1e-5, rope=True,
                           **W)
    wb_bf16 = cost.decode_block_weight_bytes(
        ffn_hidden=2432, itemsize_=2, **W)
    wb_int8 = cost.decode_block_weight_bytes(
        ffn_hidden=2432, weight_dtype="int8", itemsize_=2, **W)
    # bf16: NOTHING fits (the (1,) return is the filter's floor, and
    # even that candidate prices over budget — dispatch falls back
    # before the tuner ever runs it)
    assert _fitting_candidates(bf16, 8, 2, wb_bf16, 2) == (1,)
    assert _vmem_total(bf16, 1, wb_bf16, 2, 2) > VMEM_BUDGET_BYTES
    int8 = DecodeBlockSpec(block_size=4, norm="rms",
                           activation="swiglu", eps=1e-5, rope=True,
                           weight_dtype="int8", **W)
    cands = _fitting_candidates(int8, 8, 2, wb_int8, 2)
    assert len(cands) >= 2, cands      # real fits, not the floor
    assert all(_vmem_total(int8, p, wb_int8, 2, 2)
               <= VMEM_BUDGET_BYTES for p in cands)


# ---------------------------------------------------------------------------
# linear_ce: static estimate vs captured kernel declaration
# ---------------------------------------------------------------------------
def test_linear_ce_static_estimate_matches_measured(monkeypatch):
    from paddle_tpu.ops.pallas.linear_ce import (
        linear_cross_entropy_pallas)
    T, H, V = 16, 32, 50
    x = jnp.asarray(rng.standard_normal((2, 8, H)).astype(np.float32))
    w = jnp.asarray(
        rng.standard_normal((V, H)).astype(np.float32) * 0.1)
    lab = jnp.asarray(rng.integers(0, V, (2, 8)).astype(np.int32))
    cap = _Capture()
    cap.install(monkeypatch)
    nll = linear_cross_entropy_pallas(x, w, lab, block_rows=16, chunk=32)
    assert np.isfinite(np.asarray(nll)).all()
    assert len(cap.calls) == 1                 # forward kernel only
    measured = cap.measured_bytes(0)
    est = cost.linear_ce_vmem(block_rows=16, chunk=32, hidden=H,
                              x_itemsize=4, w_itemsize=4)
    assert _rel_diff(est["total"], measured) <= cost.MODEL_TOLERANCE, (
        f"static {est} vs measured {measured}")


# ---------------------------------------------------------------------------
# the runtime gates route through the cost model
# ---------------------------------------------------------------------------
def test_budget_single_source_of_truth():
    """The decode-block module attrs ARE the cost model's numbers (the
    12 MB v4 figure comes from the table, not a local literal), and
    the per-generation table behaves."""
    from paddle_tpu.ops.pallas import decode_block as pdb
    assert pdb.VMEM_BUDGET_BYTES == cost.budget_bytes() == 12 * 2 ** 20
    assert pdb.MAX_HEAD_DIM == cost.MAX_HEAD_DIM
    assert cost.budget_bytes("v6e") == 2 * cost.budget_bytes("v4")
    assert cost.generation_from_device_kind("TPU v5 lite") == "v5e" or \
        cost.generation_from_device_kind("TPU v5e") == "v5e"
    with pytest.raises(KeyError):
        cost.budget_bytes("v99")


def test_unsupported_reason_uses_cost_model():
    """`unsupported_reason` (the DecodeBlockUnsupportedError signal) is
    the cost model's verdict: its threshold moves exactly with the
    estimate's total."""
    from paddle_tpu.ops.pallas.decode_block import (_weight_names,
                                                    unsupported_reason)
    spec, lp, x, pk, pv, bt, ln, cos, sin = _decode_case()
    assert unsupported_reason(spec, lp, pk) is None
    wbytes = sum(lp[n].size * lp[n].dtype.itemsize
                 for n in _weight_names(spec))
    est = cost.decode_block_vmem(
        hidden=spec.hidden, num_heads=spec.num_heads,
        kv_heads=spec.kv_heads, head_dim=spec.head_dim,
        block_size=spec.block_size, pages=1, weight_bytes=wbytes,
        pool_itemsize=4, x_itemsize=4)
    # a budget one byte under the estimate must flip the verdict
    reason = cost.decode_block_unsupported_reason(
        hidden=spec.hidden, num_heads=spec.num_heads,
        kv_heads=spec.kv_heads, head_dim=spec.head_dim,
        block_size=spec.block_size, rope=spec.rope,
        weight_bytes=wbytes, pool_itemsize=4, budget=est["total"] - 1)
    assert reason is not None and "VMEM" in reason
    assert cost.decode_block_unsupported_reason(
        hidden=spec.hidden, num_heads=spec.num_heads,
        kv_heads=spec.kv_heads, head_dim=spec.head_dim,
        block_size=spec.block_size, rope=spec.rope,
        weight_bytes=wbytes, pool_itemsize=4,
        budget=est["total"]) is None


def test_autotune_validity_routes_through_cost(tmp_path):
    """`pick(valid=...)`: candidates the cost model rejects are never
    timed (KL005's runtime half)."""
    from paddle_tpu.ops.pallas import autotune
    set_flags({"use_autotune": True})
    timed = []

    def run(cand):
        def fn(*args):
            timed.append(cand)
            return np.zeros(())
        return fn

    try:
        autotune.clear_cache()
        got = autotune.pick(
            "cost_gate_test", ("k",), [1, 2, 4, 8], run, (), 1,
            valid=lambda c: c <= 2)
        assert got in (1, 2)
        assert set(timed) <= {1, 2}, timed
    finally:
        set_flags({"use_autotune": False})
        autotune.clear_cache()


def test_linear_ce_candidate_filter_uses_cost():
    """At a huge hidden size every big candidate overflows; the filter
    keeps only configs linear_ce_fits approves."""
    assert cost.linear_ce_fits(128, 512, 256)
    # (512, 2048) blocks at H=8192 fp32: (512+2048)*8192*4 ≈ 80 MB
    assert not cost.linear_ce_fits(512, 2048, 8192)


def test_linear_ce_backward_tile_fits_its_own_working_set():
    """The backward holds an fp32 accumulator and an output block beside
    the forward's blocks, all double-buffered: at the forward's
    (256, 512) tile and H 4096 bf16 the closed form gives the 20 MiB the
    v5e compiler reported when it refused the kernel (PR 22), so the
    backward halves its tile until it fits the budget."""
    need = cost.linear_ce_bwd_vmem(block_rows=256, chunk=512, hidden=4096,
                                   x_itemsize=2, w_itemsize=2)
    assert need == 28 * 2 ** 20            # dw; dx alone is 20 MiB
    br, c = cost.linear_ce_bwd_blocks(256, 512, 4096, 2, 2)
    assert (br, c) == (128, 128)
    assert cost.fits(cost.linear_ce_bwd_vmem(
        block_rows=br, chunk=c, hidden=4096, x_itemsize=2, w_itemsize=2))
    # a tile that already fits is left alone
    assert cost.linear_ce_bwd_blocks(128, 128, 256, 4, 4) == (128, 128)
    # past the width whose smallest tile fits, the reason names the cause
    assert cost.linear_ce_unsupported_reason(4096, 2, 2) is None
    reason = cost.linear_ce_unsupported_reason(16384, 4, 4)
    assert "VMEM" in reason and "hidden=16384" in reason


# ---------------------------------------------------------------------------
# acceptance grep: no second hardcoded VMEM constant
# ---------------------------------------------------------------------------
def test_no_second_hardcoded_vmem_constant():
    """ISSUE 10 acceptance: ops/ carries no VMEM byte literal — the
    budget exists exactly once, in analysis/kernel/cost.py."""
    pat = re.compile(r"\d+\s*\*\s*2\s*\*\*\s*20|<<\s*20|0x[cC]00000")
    offenders = []
    ops_root = os.path.join(REPO, "paddle_tpu", "ops")
    for root, dirs, names in os.walk(ops_root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for n in names:
            if not n.endswith(".py"):
                continue
            p = os.path.join(root, n)
            with open(p, encoding="utf-8") as f:
                for i, line in enumerate(f, 1):
                    if pat.search(line):
                        offenders.append(f"{p}:{i}: {line.strip()}")
    assert offenders == [], (
        "hardcoded VMEM-scale constants outside analysis/kernel/cost.py:"
        "\n" + "\n".join(offenders))
    # and the one true table does live in cost.py
    assert cost.VMEM_BYTES_PER_CORE["v4"] == 16 * 2 ** 20


# ---------------------------------------------------------------------------
# prefill_block (ISSUE 18): static estimate vs captured declaration
# ---------------------------------------------------------------------------
def _prefill_case(dtype=np.float32, Ts=7, start=5):
    from paddle_tpu.ops.decode_block import DecodeBlockSpec
    H, Hq, Hkv, D, F, BS, MB, NB = 32, 4, 2, 8, 48, 4, 6, 16
    spec = DecodeBlockSpec(hidden=H, num_heads=Hq, kv_heads=Hkv,
                           head_dim=D, block_size=BS, norm="rms",
                           activation="swiglu", eps=1e-5, rope=True)

    def w(*shape):
        return jnp.asarray(
            rng.standard_normal(shape).astype(np.float32) * 0.1, dtype)

    lp = {"ln1_w": w(H) + 1.0, "q_w": w(H, Hq * D), "k_w": w(H, Hkv * D),
          "v_w": w(H, Hkv * D), "o_w": w(Hq * D, H), "ln2_w": w(H) + 1.0,
          "gate_w": w(H, F), "up_w": w(H, F), "down_w": w(F, H)}
    pool_k, pool_v = w(NB, BS, Hkv, D), w(NB, BS, Hkv, D)
    bt_row = jnp.asarray(np.array([2, 5, 7, -1, -1, -1], np.int32))
    pos = start + jnp.arange(Ts)
    blk = jnp.take(jnp.maximum(bt_row, 0), pos // BS)
    off = pos % BS
    mask = jnp.arange(MB * BS)[None, None, None, :] \
        <= pos[None, None, :, None]
    x = w(1, Ts, H)
    cos, sin = w(Ts, D), w(Ts, D)
    return spec, lp, x, pool_k, pool_v, blk, off, bt_row, mask, cos, sin


@pytest.mark.parametrize("pages", [1, 2])
def test_prefill_block_static_estimate_matches_measured(monkeypatch,
                                                        pages):
    from paddle_tpu.ops.pallas.decode_block import _weight_names
    from paddle_tpu.ops.pallas.prefill_block import prefill_block_pallas
    spec, lp, x, pk, pv, blk, off, bt, mask, cos, sin = _prefill_case()
    cap = _Capture()
    cap.install(monkeypatch)
    out, _, _ = prefill_block_pallas(x, lp, pk, pv, blk, off, bt, mask,
                                     cos, sin, spec=spec, start=5,
                                     pages=pages)
    assert np.isfinite(np.asarray(out)).all()
    assert len(cap.calls) == 1
    measured = cap.measured_bytes(0)
    wbytes = sum(lp[n].size * lp[n].dtype.itemsize
                 for n in _weight_names(spec))
    est = cost.prefill_block_vmem(
        hidden=spec.hidden, num_heads=spec.num_heads,
        kv_heads=spec.kv_heads, head_dim=spec.head_dim,
        block_size=spec.block_size, pages=pages, chunk=x.shape[1],
        weight_bytes=wbytes, pool_itemsize=pk.dtype.itemsize,
        x_itemsize=x.dtype.itemsize)
    assert _rel_diff(est["total"], measured) <= cost.MODEL_TOLERANCE, (
        f"static {est} vs measured {measured}")
    # the staging term is double-buffered: DMA_STAGING_SLOTS revolving
    # copies of the page-chunk live in VMEM at once
    per_chunk = 2 * pages * spec.block_size * spec.kv_heads \
        * spec.head_dim * pk.dtype.itemsize
    assert est["staging"] == cost.DMA_STAGING_SLOTS * per_chunk


def test_prefill_block_kv_quant_estimate_matches_measured(monkeypatch):
    from paddle_tpu.ops.paged_kv import QuantizedKVPool, quantize_kv
    from paddle_tpu.ops.pallas.decode_block import _weight_names
    from paddle_tpu.ops.pallas.prefill_block import prefill_block_pallas
    spec, lp, x, pk, pv, blk, off, bt, mask, cos, sin = _prefill_case()
    pk = QuantizedKVPool(*quantize_kv(pk))
    pv = QuantizedKVPool(*quantize_kv(pv))
    cap = _Capture()
    cap.install(monkeypatch)
    prefill_block_pallas(x, lp, pk, pv, blk, off, bt, mask, cos, sin,
                         spec=spec, start=5, pages=2)
    measured = cap.measured_bytes(0)
    wbytes = sum(lp[n].size * lp[n].dtype.itemsize
                 for n in _weight_names(spec))
    est = cost.prefill_block_vmem(
        hidden=spec.hidden, num_heads=spec.num_heads,
        kv_heads=spec.kv_heads, head_dim=spec.head_dim,
        block_size=spec.block_size, pages=2, chunk=x.shape[1],
        weight_bytes=wbytes, pool_itemsize=1, x_itemsize=4,
        kv_quant=True)
    assert _rel_diff(est["total"], measured) <= cost.MODEL_TOLERANCE, (
        f"static {est} vs measured {measured}")


def test_prefill_unsupported_reason_uses_cost_model():
    """The PrefillBlockUnsupportedError signal is the cost model's
    verdict: the threshold moves exactly with the estimate's total,
    and the pinned llama-7B-width layer (H=896/F=2432 bf16) is over
    budget on weights alone."""
    from paddle_tpu.ops.pallas.decode_block import _weight_names
    from paddle_tpu.ops.pallas.prefill_block import unsupported_reason
    spec, lp, x, pk, pv, blk, off, bt, mask, cos, sin = _prefill_case()
    assert unsupported_reason(spec, lp, pk, x.shape[1]) is None
    wbytes = sum(lp[n].size * lp[n].dtype.itemsize
                 for n in _weight_names(spec))
    kw = dict(hidden=spec.hidden, num_heads=spec.num_heads,
              kv_heads=spec.kv_heads, head_dim=spec.head_dim,
              block_size=spec.block_size, chunk=x.shape[1],
              rope=spec.rope, weight_bytes=wbytes, pool_itemsize=4,
              x_itemsize=4)
    est = cost.prefill_block_vmem(
        hidden=spec.hidden, num_heads=spec.num_heads,
        kv_heads=spec.kv_heads, head_dim=spec.head_dim,
        block_size=spec.block_size, pages=1, chunk=x.shape[1],
        weight_bytes=wbytes, pool_itemsize=4, x_itemsize=4)
    reason = cost.prefill_block_unsupported_reason(
        budget=est["total"] - 1, **kw)
    assert reason is not None and "VMEM" in reason
    assert cost.prefill_block_unsupported_reason(
        budget=est["total"], **kw) is None
    # the pinned serve width: bf16 weights alone blow the real budget
    W = dict(hidden=896, num_heads=14, kv_heads=2, head_dim=64)
    wb_bf16 = cost.decode_block_weight_bytes(
        ffn_hidden=2432, itemsize_=2, **W)
    reason = cost.prefill_block_unsupported_reason(
        block_size=8, chunk=64, rope=True, weight_bytes=wb_bf16,
        pool_itemsize=2, x_itemsize=2, **W)
    assert reason is not None and "VMEM" in reason


def test_prefill_autotune_candidates_use_dtype_aware_model():
    """The prefill pages-candidate filter prices through the same
    dtype-aware model AND shares the decode kernel's floor convention
    (ONE `_floor_candidates`, not a second copy)."""
    from paddle_tpu.ops.decode_block import DecodeBlockSpec
    from paddle_tpu.ops.pallas import decode_block as pdb
    from paddle_tpu.ops.pallas import prefill_block as ppf
    assert ppf._floor_candidates is pdb._floor_candidates
    W = dict(hidden=896, num_heads=14, kv_heads=2, head_dim=64)
    bf16 = DecodeBlockSpec(block_size=8, norm="rms",
                           activation="swiglu", eps=1e-5, rope=True,
                           **W)
    wb_bf16 = cost.decode_block_weight_bytes(
        ffn_hidden=2432, itemsize_=2, **W)
    wb_int8 = cost.decode_block_weight_bytes(
        ffn_hidden=2432, weight_dtype="int8", itemsize_=2, **W)
    # bf16: nothing fits — the (1,) return is the shared floor, and
    # even that candidate prices over budget (dispatch falls back
    # before the tuner ever runs it)
    assert ppf._fitting_candidates(bf16, 64, 8, 2, wb_bf16, 2) == (1,)
    assert ppf._vmem_total(bf16, 1, 64, wb_bf16, 2, 2) \
        > pdb.VMEM_BUDGET_BYTES
    int8 = DecodeBlockSpec(block_size=8, norm="rms",
                           activation="swiglu", eps=1e-5, rope=True,
                           weight_dtype="int8", **W)
    cands = ppf._fitting_candidates(int8, 64, 8, 2, wb_int8, 2)
    assert len(cands) >= 2, cands      # real fits, not the floor
    assert all(ppf._vmem_total(int8, p, 64, wb_int8, 2, 2)
               <= pdb.VMEM_BUDGET_BYTES for p in cands)
    # longer chunks shrink what fits: the model is chunk-aware
    assert len(ppf._fitting_candidates(int8, 2048, 8, 2, wb_int8, 2)) \
        <= len(cands)
