"""LOCKLINT.md baseline generator / standalone ratchet.

* ``python tools/locklint_baseline.py``          — regenerate
  LOCKLINT.md from the current LK findings (after fixing debt: the
  ledger ratchets DOWN; growing it requires explanation in review).
* ``python tools/locklint_baseline.py --check``  — exit non-zero if
  any (rule, file) count exceeds the committed baseline; the
  pre-commit-style one-liner for the ratchet
  tests/test_locklint_ratchet.py runs under pytest.

Mirrors ``tools/tracelint_baseline.py`` / ``kernellint_baseline.py``
on the same lint surface — ``paddle_tpu/``, ``tools/`` — restricted
to the LK (concurrency safety) rules from
``paddle_tpu/analysis/threads/``.  The ledger starts EMPTY: every
finding of the initial project-wide triage was either fixed (the
prefetcher lost-exception races, the unjoined serving/RPC/KV threads,
the unlocked drain-report/error/backpressure writes) or narrowly
suppressed in place with a justification — any new finding is above
baseline by construction.
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from paddle_tpu.analysis import baseline, core       # noqa: E402
from paddle_tpu.analysis.cli import default_paths    # noqa: E402


def _findings():
    select = {r.id for r in core.all_rules() if r.id.startswith("LK")}
    return core.run(default_paths(), select=select)


def generate() -> int:
    findings = _findings()
    path = baseline.locklint_path()
    with open(path, "w", encoding="utf-8") as f:
        f.write(baseline.render_md(findings, tool="locklint"))
    print(f"wrote {os.path.relpath(path, REPO)}: "
          f"{len(findings)} findings")
    return 0


def check() -> int:
    findings = _findings()
    try:
        base = baseline.load(baseline.locklint_path())
    except (OSError, ValueError) as e:
        print(f"RATCHET FAIL: cannot load baseline: {e}")
        return 1
    regressions = baseline.compare(baseline.counts(findings), base)
    if regressions:
        print(f"RATCHET FAIL: {len(regressions)} (rule, file) pairs "
              f"above the committed LOCKLINT.md baseline:")
        for r in regressions:
            print(f"  {r}")
        print("fix the findings (preferred), suppress with an inline "
              "justification, or — with reviewer sign-off — regenerate "
              "the baseline via `python tools/locklint_baseline.py`.")
        return 1
    print(f"ratchet OK: {len(findings)} findings, none above baseline")
    return 0


if __name__ == "__main__":
    sys.exit(check() if "--check" in sys.argv[1:] else generate())
