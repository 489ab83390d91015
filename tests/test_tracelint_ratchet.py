"""tracelint ratchet: the real package versus the committed baseline.

Tier-1 and CPU-only: pure AST analysis, no jax execution.  The ratchet
fails when any (rule, file) finding count exceeds TRACELINT.md — the
same comparison `python tools/tracelint_baseline.py --check` runs
standalone (pre-commit style).
"""

import functools
import os
import subprocess
import sys

from paddle_tpu.analysis import baseline as baseline_mod
from paddle_tpu.analysis import core
from paddle_tpu.analysis.cli import default_paths

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CORE_TREES = ("paddle_tpu/checkpoint/", "paddle_tpu/io/",
              "paddle_tpu/optimizer/", "paddle_tpu/parallel/")


@functools.lru_cache(maxsize=1)
def _scan_once():
    # the committed tree is immutable for the lifetime of the test run;
    # one full scan serves every ratchet assertion below
    return tuple(core.run(default_paths()))


def _current_findings():
    return list(_scan_once())


def test_package_at_or_below_baseline():
    findings = _current_findings()
    base = baseline_mod.load()
    regressions = baseline_mod.compare(baseline_mod.counts(findings),
                                       base)
    assert regressions == [], (
        "tracelint findings grew beyond TRACELINT.md:\n  "
        + "\n  ".join(regressions)
        + "\nfix or suppress (with justification), or regenerate the "
          "baseline via `python tools/tracelint_baseline.py` with "
          "reviewer sign-off")


def test_observability_has_zero_tl001_tl006():
    """ISSUE 5 contract: the telemetry package records HOST-side only —
    no host-sync in traced code (TL001; a metrics call inside jit is
    that hazard by construction) and no silent broad excepts (TL006) —
    live scan AND committed ledger."""
    tree = "paddle_tpu/observability/"
    live = [f for f in _current_findings()
            if f.rule in ("TL001", "TL006") and f.path.startswith(tree)]
    assert live == [], [f.format() for f in live]
    for (rule, path), n in baseline_mod.load().items():
        if rule in ("TL001", "TL006") and path.startswith(tree):
            assert n == 0, f"baseline carries {rule} debt in {path}"


def test_aot_has_zero_tl001_tl006():
    """ISSUE 6 contract: the AOT subsystem is host-side plumbing around
    traced programs — no host-sync in traced code (TL001) and no silent
    broad excepts (TL006; a swallowed artifact error would turn a warm
    start into a silent cold start) — live scan AND committed ledger."""
    tree = "paddle_tpu/aot/"
    live = [f for f in _current_findings()
            if f.rule in ("TL001", "TL006") and f.path.startswith(tree)]
    assert live == [], [f.format() for f in live]
    for (rule, path), n in baseline_mod.load().items():
        if rule in ("TL001", "TL006") and path.startswith(tree):
            assert n == 0, f"baseline carries {rule} debt in {path}"


def test_serving_has_zero_tl001_tl006():
    """ISSUE 7 contract: the streaming front-end is host-side scheduler
    code — no host-sync in traced code (TL001) and no silent broad
    excepts (TL006; a swallowed delivery/cancel error would strand a
    consumer or leak KV pages) — live scan AND committed ledger."""
    tree = "paddle_tpu/serving/"
    live = [f for f in _current_findings()
            if f.rule in ("TL001", "TL006") and f.path.startswith(tree)]
    assert live == [], [f.format() for f in live]
    for (rule, path), n in baseline_mod.load().items():
        if rule in ("TL001", "TL006") and path.startswith(tree):
            assert n == 0, f"baseline carries {rule} debt in {path}"


def test_serving_http_has_zero_tl001_tl006():
    """ISSUE 13 contract: the HTTP/SSE front door is pure host-side
    connection plumbing over the frontend — no host-sync in traced
    code (TL001) and no silent broad excepts (TL006; a swallowed
    disconnect/stall/shutdown error would leak the very KV pages the
    wire layer exists to free) — live scan AND committed ledger."""
    files = ("paddle_tpu/serving/http.py",)
    live = [f for f in _current_findings()
            if f.rule in ("TL001", "TL006") and f.path.endswith(files)]
    assert live == [], [f.format() for f in live]
    for (rule, path), n in baseline_mod.load().items():
        if rule in ("TL001", "TL006") and path.endswith(files):
            assert n == 0, f"baseline carries {rule} debt in {path}"


def test_spec_decode_has_zero_tl001_tl006():
    """ISSUE 8 contract: speculative decoding is host-side scheduling
    around two traced programs — no host-sync in traced code (TL001;
    the draft/verify closures must stay pure) and no silent broad
    excepts (TL006; a swallowed commit/rollback error would corrupt the
    accepted-prefix accounting) — live scan AND committed ledger."""
    tree = "paddle_tpu/spec_decode/"
    live = [f for f in _current_findings()
            if f.rule in ("TL001", "TL006") and f.path.startswith(tree)]
    assert live == [], [f.format() for f in live]
    for (rule, path), n in baseline_mod.load().items():
        if rule in ("TL001", "TL006") and path.startswith(tree):
            assert n == 0, f"baseline carries {rule} debt in {path}"


def test_serving_fleet_has_zero_tl001_tl006():
    """ISSUE 12 contract: the multi-replica router is pure host-side
    scheduling over supervised engines — no host-sync in traced code
    (TL001) and no silent broad excepts (TL006; a swallowed death /
    drain / re-placement error would strand streams the fleet layer
    exists to keep alive) — live scan AND committed ledger."""
    files = ("paddle_tpu/serving/fleet.py",)
    live = [f for f in _current_findings()
            if f.rule in ("TL001", "TL006") and f.path.endswith(files)]
    assert live == [], [f.format() for f in live]
    for (rule, path), n in baseline_mod.load().items():
        if rule in ("TL001", "TL006") and path.endswith(files):
            assert n == 0, f"baseline carries {rule} debt in {path}"


def test_serving_resilience_has_zero_tl001_tl006():
    """ISSUE 11 contract: the resilience layer (KV spill/restore +
    supervised recovery) is host-side scheduler code around compiled
    programs — no host-sync in traced code (TL001) and no silent broad
    excepts (TL006; a swallowed restore/replay error would silently
    lose a stream the whole subsystem exists to preserve) — live scan
    AND committed ledger."""
    files = ("paddle_tpu/serving/resilience.py",)
    live = [f for f in _current_findings()
            if f.rule in ("TL001", "TL006") and f.path.endswith(files)]
    assert live == [], [f.format() for f in live]
    for (rule, path), n in baseline_mod.load().items():
        if rule in ("TL001", "TL006") and path.endswith(files):
            assert n == 0, f"baseline carries {rule} debt in {path}"


def test_prefix_cache_has_zero_tl001_tl006():
    """ISSUE 14 contract: the cross-request prefix cache is host-side
    scheduler state around the paged pool — no host-sync in traced
    code (TL001; the radix tree must never be consulted from inside a
    compiled program) and no silent broad excepts (TL006; a swallowed
    offload/restore error would silently serve corrupt KV bytes as a
    cache hit) — live scan AND committed ledger."""
    files = ("paddle_tpu/serving/prefix_cache.py",)
    live = [f for f in _current_findings()
            if f.rule in ("TL001", "TL006") and f.path.endswith(files)]
    assert live == [], [f.format() for f in live]
    for (rule, path), n in baseline_mod.load().items():
        if rule in ("TL001", "TL006") and path.endswith(files):
            assert n == 0, f"baseline carries {rule} debt in {path}"


def test_quantization_serve_has_zero_tl001_tl006():
    """ISSUE 16 contract: the serving PTQ export path is host-side
    numpy by design (a traced quantize would recompile every engine
    construction — the serve_quant_warm budget row pins zero) — no
    host-sync in traced code (TL001) and no silent broad excepts
    (TL006; a swallowed export error would silently serve unquantized
    or half-quantized weights) — live scan AND committed ledger."""
    files = ("paddle_tpu/quantization/serve.py",)
    live = [f for f in _current_findings()
            if f.rule in ("TL001", "TL006") and f.path.endswith(files)]
    assert live == [], [f.format() for f in live]
    for (rule, path), n in baseline_mod.load().items():
        if rule in ("TL001", "TL006") and path.endswith(files):
            assert n == 0, f"baseline carries {rule} debt in {path}"


def test_decode_block_has_zero_tl001_tl006():
    """The serving layer bodies sit on the hottest serve path — no
    host-sync in traced code (TL001; one ``.item()`` in a layer body
    would sync every layer of every decode step and chunk fill) and no
    silent broad excepts (TL006) — live scan AND committed ledger."""
    files = ("paddle_tpu/ops/decode_block.py",)
    live = [f for f in _current_findings()
            if f.rule in ("TL001", "TL006") and f.path.endswith(files)]
    assert live == [], [f.format() for f in live]
    for (rule, path), n in baseline_mod.load().items():
        if rule in ("TL001", "TL006") and path.endswith(files):
            assert n == 0, f"baseline carries {rule} debt in {path}"


def test_parallel_elastic_has_zero_tl001_tl006():
    """ISSUE 17 contract: the elastic trainer is host-side supervision
    around the engine's compiled step — no host-sync in traced code
    (TL001; the SDC guard must stay an in-graph where-select, never a
    host check per step) and no silent broad excepts (TL006; a
    swallowed reshape/restore error would resume training on corrupt
    or stale state) — live scan AND committed ledger."""
    files = ("paddle_tpu/parallel/elastic.py",)
    live = [f for f in _current_findings()
            if f.rule in ("TL001", "TL006") and f.path.endswith(files)]
    assert live == [], [f.format() for f in live]
    for (rule, path), n in baseline_mod.load().items():
        if rule in ("TL001", "TL006") and path.endswith(files):
            assert n == 0, f"baseline carries {rule} debt in {path}"


def test_core_subsystems_have_zero_tl006():
    """The ISSUE 4 triage contract: checkpoint/, io/, optimizer/ and
    parallel/ carry NO un-triaged silent-except debt — in the live scan
    AND in the committed ledger."""
    findings = _current_findings()
    live = [f for f in findings if f.rule == "TL006"
            and f.path.startswith(CORE_TREES)]
    assert live == [], [f.format() for f in live]
    for (rule, path), n in baseline_mod.load().items():
        if rule == "TL006" and path.startswith(CORE_TREES):
            assert n == 0, f"baseline carries TL006 debt in {path}"


def test_ratchet_fails_on_injected_violation(tmp_path):
    """A synthetic violation in the analyzed tree must trip the
    comparison: the ratchet is live, not vacuously green."""
    bad = tmp_path / "injected.py"
    bad.write_text(
        "def leaky(q):\n"
        "    try:\n"
        "        q.get_nowait()\n"
        "    except Exception:\n"
        "        pass\n")
    findings = _current_findings() + core.run([str(bad)])
    assert any(f.rule == "TL006" and "injected.py" in f.path
               for f in findings)
    regressions = baseline_mod.compare(baseline_mod.counts(findings),
                                       baseline_mod.load())
    assert regressions, "injected TL006 violation did not trip the ratchet"


def test_standalone_checker_exits_zero():
    proc = subprocess.run(
        [sys.executable, os.path.join("tools", "tracelint_baseline.py"),
         "--check"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ratchet OK" in proc.stdout


def test_module_cli_reports_zero_above_baseline():
    """Acceptance criterion: `python -m paddle_tpu.analysis paddle_tpu/`
    reports zero above-baseline findings."""
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis",
         os.path.join(REPO, "paddle_tpu")],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 above baseline" in proc.stdout
