"""kernellint unit tests: the cost model, the pallas_call extractor,
per-rule fixtures, suppressions, and the CLI lane.

Fixture files under tests/kernellint_fixtures/ are ANALYZED, never
imported (the KL006 pair lives under an ops/pallas/ subpath because
that rule is scoped to kernel modules).  CPU-only, no jax execution.
"""

import os
import subprocess
import sys
import textwrap

import pytest

from paddle_tpu.analysis import core
from paddle_tpu.analysis.kernel import cost
from paddle_tpu.analysis.kernel.extract import extract_sites

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "kernellint_fixtures")
REPO = os.path.dirname(HERE)

KL_IDS = ("KL001", "KL002", "KL003", "KL004", "KL005", "KL006")

_FIXTURE_PATHS = {
    "KL006": os.path.join("ops", "pallas"),
}


def fixture_path(rid, kind):
    sub = _FIXTURE_PATHS.get(rid, "")
    return os.path.join(FIXTURES, sub, f"{rid.lower()}_{kind}.py")


def run_fixture(rid, kind):
    return core.run([fixture_path(rid, kind)], select={rid})


# -- registry -----------------------------------------------------------

def test_kl_rules_registered_with_metadata():
    ids = [r.id for r in core.all_rules()]
    for rid in KL_IDS:
        assert rid in ids
    for rule in core.all_rules():
        if rule.id.startswith("KL"):
            assert rule.severity in core.SEVERITIES
            assert rule.doc and rule.hint and rule.name


# -- cost model ---------------------------------------------------------

def test_itemsize_accepts_strings_and_reprs():
    assert cost.itemsize("float32") == 4
    assert cost.itemsize("bfloat16") == 2
    assert cost.itemsize("int8") == 1
    with pytest.raises(ValueError):
        cost.itemsize("not_a_dtype")


def test_budget_reproduces_hand_constant():
    # 0.75 * 16 MB
    assert cost.budget_bytes() == 12 * 2 ** 20
    assert cost.fits(12 * 2 ** 20)
    assert not cost.fits(12 * 2 ** 20 + 1)


def test_linear_ce_vmem_scales_with_blocks():
    small = cost.linear_ce_vmem(block_rows=128, chunk=512, hidden=256)
    big = cost.linear_ce_vmem(block_rows=512, chunk=2048, hidden=256)
    assert big["total"] > small["total"]
    assert cost.linear_ce_fits(128, 512, 256)
    assert not cost.linear_ce_fits(512, 2048, 8192)


# -- extractor ----------------------------------------------------------

def test_extractor_models_real_kernels():
    mod = core.load_module(os.path.join(
        REPO, "paddle_tpu", "ops", "pallas", "linear_ce.py"))
    sites = extract_sites(mod)
    assert len(sites) == 3                      # fwd, dx, dw
    fwd = sites[0]
    assert fwd.grid_rank == 2
    assert fwd.grid_has_cdiv                    # nv = pl.cdiv(V, C)
    assert fwd.kernel_name == "_fwd_kernel"
    assert len(fwd.in_specs) == 3 and fwd.in_specs_complete
    assert [s.index_map_arity for s in fwd.in_specs] == [2, 2, 2]
    assert len(fwd.scratch) == 4                # [VMEM(...)] * 4 folds
    assert all(s.kind == "vmem" and s.dtype == "float32"
               for s in fwd.scratch)


def test_extractor_handles_paged_gather_kernels():
    """What a kernel that gathers pages itself declares: tables in
    SMEM, pools left in ANY space, a splat of per-weight specs, DMA
    semaphores beside the VMEM scratch, a ceil-divided grid axis in the
    ``-(-a // b)`` idiom.  The serving path's own attention kernel
    (``ops/pallas/decode_attention.py``) reads lengths from SMEM."""
    import ast
    src = textwrap.dedent("""
        def _call(x, ws, pool_k, pool_v, table, lengths, pages):
            B, H = x.shape
            nt = -(-table.shape[1] // pages)

            def wspec(w):
                return pl.BlockSpec(w.shape, lambda b, j: (0, 0))

            in_specs = [
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((None, 1, H), lambda b, j: (b, 0, 0)),
                *[wspec(w) for w in ws],
                pl.BlockSpec(memory_space=pltpu.ANY),
                pl.BlockSpec(memory_space=pltpu.ANY),
            ]
            return pl.pallas_call(
                _kernel,
                grid=(B, nt),
                in_specs=in_specs,
                out_specs=pl.BlockSpec((None, 1, H),
                                       lambda b, j: (b, 0, 0)),
                out_shape=jax.ShapeDtypeStruct((B, 1, H), x.dtype),
                scratch_shapes=[pltpu.VMEM((2, pages, 16, 128),
                                           jnp.float32),
                                pltpu.SemaphoreType.DMA((2, pages))],
            )(table, lengths, x[:, None], *ws, pool_k, pool_v)
    """)
    mod = core.Module("paged.py", "paged.py", src, ast.parse(src))
    sites = extract_sites(mod)
    assert len(sites) == 1
    site = sites[0]
    assert site.grid_rank == 2
    assert site.grid_has_cdiv                   # nt = -(-mb // pages)
    assert site.kernel_name == "_kernel"
    assert not site.in_specs_complete           # *[wspec(...)] splat
    smem = [s for s in site.in_specs if s.memory_space == "smem"]
    anys = [s for s in site.in_specs if s.memory_space == "any"]
    assert len(smem) == 2 and len(anys) == 2    # tables + pools
    assert any(s.kind == "sem" for s in site.scratch)

    real = extract_sites(core.load_module(os.path.join(
        REPO, "paddle_tpu", "ops", "pallas", "decode_attention.py")))
    assert len(real) == 1 and real[0].kernel_name == "_decode_kernel"
    assert real[0].grid_rank == 3 and real[0].in_specs_complete
    assert [s.memory_space for s in real[0].in_specs] \
        == ["smem", "vmem", "vmem", "vmem"]
    assert [s.kind for s in real[0].scratch] == ["vmem"] * 3


def test_const_env_folds_module_and_local_names():
    import ast
    from paddle_tpu.analysis.kernel.extract import ConstEnv
    src = textwrap.dedent("""
        BM, BK = 256, 512
        TWO = 2
        def f(M):
            bm = min(BM, max(8, M))
            bk = BK // TWO
            pair = (bm, bk)
    """)
    mod = core.Module("x.py", "x.py", src, ast.parse(src))
    env = ConstEnv(mod, mod.functions["f"])
    assert env.lookup("bk") == 256
    assert env.lookup("bm") is None             # M is runtime -> unproven
    assert env.lookup("BM") == 256


# -- per-rule fixtures --------------------------------------------------

@pytest.mark.parametrize("rid", KL_IDS)
def test_rule_fires_on_positive_fixture(rid):
    findings = run_fixture(rid, "pos")
    assert findings, f"{rid} found nothing in its positive fixture"
    assert {f.rule for f in findings} == {rid}


@pytest.mark.parametrize("rid", KL_IDS)
def test_rule_quiet_on_negative_fixture(rid):
    findings = run_fixture(rid, "neg")
    assert not findings, [f.format() for f in findings]


def test_kl001_message_names_the_bound():
    findings = run_fixture("KL001", "pos")
    assert any("MB" in f.message and "budget" in f.message
               for f in findings)
    assert all(f.severity == "error" for f in findings)


def test_kl002_catches_all_three_shapes():
    findings = run_fixture("KL002", "pos")
    msgs = " ".join(f.message for f in findings)
    assert "arg(s) but the grid has rank" in msgs
    assert "coordinate(s) for a rank-" in msgs
    assert "program_id(2)" in msgs
    assert len(findings) == 3


def test_kl005_key_drift(tmp_path):
    drift = tmp_path / "drifting.py"
    drift.write_text(textwrap.dedent("""
        from paddle_tpu.ops.pallas.autotune import lookup, pick
        def tune(key, cands, run, args):
            return pick("flash_fwd2", key, cands, run, args, cands[0])
        def traced(key):
            return lookup("flash_fwd", key, None)
    """))
    findings = core.run([str(drift)], select={"KL005"})
    assert len(findings) == 1
    assert "key drift" in findings[0].message


def test_kernellint_suppression_alias(tmp_path):
    bad = tmp_path / "suppressed.py"
    bad.write_text(textwrap.dedent("""
        from jax.experimental import pallas as pl
        import jax.numpy as jnp
        import jax

        def _kernel(x_ref, o_ref):
            o_ref[:] = x_ref[:]

        def f(x):
            # tile overhang folds into a copy, reviewed: harmless here
            return pl.pallas_call(  # kernellint: disable=KL003
                _kernel,
                grid=(pl.cdiv(x.shape[0], 8),),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            )(x)
    """))
    assert core.run([str(bad)], select={"KL003"}) == []


# -- the CLI lane -------------------------------------------------------

def test_cli_select_kl_prefix_expands():
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", "--select", "KL",
         "--no-baseline", "--json",
         os.path.join(FIXTURES, "ops", "pallas", "kl006_pos.py")],
        capture_output=True, text=True, cwd=REPO)
    import json
    payload = json.loads(proc.stdout)
    assert proc.returncode == 1
    assert set(payload["counts"]) == {"KL006"}


def test_cli_kl_lane_clean_on_ops_pallas():
    """The ISSUE 10 acceptance command: `python -m paddle_tpu.analysis
    --select KL ops/pallas/` runs clean against the committed (empty)
    KERNELLINT.md ledger."""
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", "--select", "KL",
         os.path.join("paddle_tpu", "ops", "pallas")],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 above baseline" in proc.stdout
