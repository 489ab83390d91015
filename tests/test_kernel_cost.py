"""The shared VMEM cost model: static estimate ==
interpret-mode-measured kernel allocation, and the runtime gates route
through it.

The measurement: ``pl.pallas_call`` is wrapped so each invocation
records what the kernel actually DECLARES — every in/out BlockSpec's
block shape at the argument's runtime dtype plus every VMEM
scratch_shapes entry — which is exactly the per-grid-step VMEM
residency Mosaic will allocate (modulo tile padding, absorbed by
``cost.SAFETY_FRACTION``).  The pin: ``cost.linear_ce_vmem`` matches
that measurement within ``cost.MODEL_TOLERANCE`` for the fused CE head.
If someone adds a scratch buffer to the kernel and forgets the cost
model (or vice versa), this fails.

Also the acceptance grep: no second hardcoded VMEM constant exists
outside ``analysis/kernel/cost.py`` — the autotune validity filters and
the kernels' block sizing read the one budget table.
"""

import math
import os
import re

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
    """A kernel's block budget IS the cost model's number (the 12 MB v4
    figure comes from the table, not a local literal), and the
    per-generation table behaves."""
    from paddle_tpu.ops.pallas import ssm
    assert cost.budget_bytes() == 12 * 2 ** 20
    assert ssm.BLOCK_BYTES == cost.budget_bytes() // 12
    assert cost.budget_bytes("v6e") == 2 * cost.budget_bytes("v4")
    assert cost.generation_from_device_kind("TPU v5 lite") == "v5e" or \
        cost.generation_from_device_kind("TPU v5e") == "v5e"
    with pytest.raises(KeyError):
        cost.budget_bytes("v99")


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
    """ops/ carries no VMEM byte literal — the
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
