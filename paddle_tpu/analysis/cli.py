"""``python -m paddle_tpu.analysis`` — the tracelint CLI.

Modes:

* file/dir:  ``python -m paddle_tpu.analysis paddle_tpu/ tools/``
  (no paths: the repo's lint surface — paddle_tpu/, tools/)
* diff:      ``python -m paddle_tpu.analysis --diff HEAD~1`` — only
  files changed versus the git ref
* output:    human (default) or ``--json``
  (``{"version": 1, "findings": [...], "counts": {...}}``)

When committed ledgers exist (TRACELINT.md for TL rules, KERNELLINT.md
for KL rules; override: ``--baseline PATH``, opt out:
``--no-baseline``) the exit code reports the RATCHET against their
union, not raw findings: 0 at-or-below baseline, 2 above.  Without a
baseline, any finding exits 1.  ``--select`` accepts prefixes: the
kernellint lane is ``--select KL``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import List, Optional

from . import baseline as baseline_mod
from . import core

DEFAULT_LINT_SURFACE = ("paddle_tpu", "tools")


def default_paths() -> List[str]:
    root = core.repo_root()
    return [os.path.join(root, p) for p in DEFAULT_LINT_SURFACE
            if os.path.exists(os.path.join(root, p))]


def _diff_paths(ref: str) -> List[str]:
    root = core.repo_root()
    proc = subprocess.run(
        ["git", "-C", root, "diff", "--name-only", ref, "--", "*.py"],
        capture_output=True, text=True)
    if proc.returncode != 0:
        raise SystemExit(f"tracelint: git diff {ref} failed: "
                         f"{proc.stderr.strip()}")
    out = []
    for rel in proc.stdout.splitlines():
        p = os.path.join(root, rel.strip())
        if os.path.exists(p):
            out.append(p)
    return out


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.analysis",
        description="tracelint: trace-safety static analysis for "
                    "jit/shard_map/donation code")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to analyze (default: the repo lint "
                         "surface: paddle_tpu/, tools/)")
    ap.add_argument("--diff", metavar="REF",
                    help="analyze only .py files changed vs the git ref")
    ap.add_argument("--select", metavar="IDS",
                    help="comma-separated rule ids or prefixes "
                         "(e.g. TL001,TL006 — or KL for every "
                         "kernellint rule)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable output")
    ap.add_argument("--baseline", metavar="PATH",
                    help="baseline file (default: repo TRACELINT.md "
                         "when it exists)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore any baseline; exit 1 on any finding")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalogue and exit")
    return ap


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)

    if args.list_rules:
        for rule in core.all_rules():
            print(f"{rule.id} {rule.name} [{rule.severity}] — {rule.doc}")
        return 0

    if args.diff:
        paths = _diff_paths(args.diff)
    elif args.paths:
        paths = args.paths
    else:
        paths = default_paths()

    select = None
    if args.select:
        tokens = {t.strip() for t in args.select.split(",") if t.strip()}
        # a token is an exact id or a prefix: "KL" selects every
        # kernellint rule, "TL00" every tracelint rule
        select = {r.id for r in core.all_rules()
                  if any(r.id == t or r.id.startswith(t)
                         for t in tokens)}

    findings = core.run(paths, select=select)

    regressions: Optional[List[str]] = None
    if args.baseline:
        base_paths = [args.baseline]
    else:
        base_paths = baseline_mod.existing_ledgers()
    if base_paths and not args.no_baseline:
        base = baseline_mod.load_merged(base_paths)
        if select:
            base = {k: v for k, v in base.items() if k[0] in select}
        regressions = baseline_mod.compare(
            baseline_mod.counts(findings), base)

    # label the summary line by lane: a single-tool --select prints
    # that tool's name, anything mixed keeps the engine's default
    tools = {prefix: tool for _, prefix, tool in baseline_mod.LEDGERS}
    prefixes = {rid[:2] for rid in select} if select else set()
    label = tools.get(prefixes.pop(), "tracelint") if len(prefixes) == 1 \
        else "tracelint"

    if args.as_json:
        payload = {
            "version": 1,
            "findings": [f.to_json() for f in findings],
            "counts": {rule: sum(1 for f in findings if f.rule == rule)
                       for rule in sorted({f.rule for f in findings})},
            "baseline": (base_paths if regressions is not None
                         else None),
            "above_baseline": regressions or [],
        }
        print(json.dumps(payload, indent=1))
    else:
        for f in findings:
            print(f.format())
        n = len(findings)
        if regressions is None:
            print(f"{label}: {n} finding{'s' if n != 1 else ''}")
        else:
            names = ", ".join(os.path.relpath(p, core.repo_root())
                              for p in base_paths)
            print(f"{label}: {n} finding{'s' if n != 1 else ''}, "
                  f"{len(regressions)} above baseline ({names})")
            for r in regressions:
                print(f"  ABOVE BASELINE: {r}")

    if regressions is not None:
        return 2 if regressions else 0
    return 1 if findings else 0
