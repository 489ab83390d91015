"""KERNELLINT.md baseline generator / standalone ratchet.

* ``python tools/kernellint_baseline.py``          — regenerate
  KERNELLINT.md from the current KL findings (after fixing debt: the
  ledger ratchets DOWN; growing it requires explanation in review).
* ``python tools/kernellint_baseline.py --check``  — exit non-zero if
  any (rule, file) count exceeds the committed baseline; the
  pre-commit-style one-liner for the ratchet
  tests/test_kernellint_ratchet.py runs under pytest.

Mirrors ``tools/tracelint_baseline.py`` (the TL ledger) on the same
lint surface — ``paddle_tpu/``, ``tools/`` — restricted to the KL
(Pallas kernel safety) rules from ``paddle_tpu/analysis/kernel/``.  As of ISSUE 10 the ledger is EMPTY:
every pre-existing finding was fixed (the six KL006 interpret-parity
gaps got tests) — any new finding is above baseline by construction.
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from paddle_tpu.analysis import baseline, core       # noqa: E402
from paddle_tpu.analysis.cli import default_paths    # noqa: E402


def _findings():
    select = {r.id for r in core.all_rules() if r.id.startswith("KL")}
    return core.run(default_paths(), select=select)


def generate() -> int:
    findings = _findings()
    path = baseline.kernellint_path()
    with open(path, "w", encoding="utf-8") as f:
        f.write(baseline.render_md(findings, tool="kernellint"))
    print(f"wrote {os.path.relpath(path, REPO)}: "
          f"{len(findings)} findings")
    return 0


def check() -> int:
    findings = _findings()
    try:
        base = baseline.load(baseline.kernellint_path())
    except (OSError, ValueError) as e:
        print(f"RATCHET FAIL: cannot load baseline: {e}")
        return 1
    regressions = baseline.compare(baseline.counts(findings), base)
    if regressions:
        print(f"RATCHET FAIL: {len(regressions)} (rule, file) pairs "
              f"above the committed KERNELLINT.md baseline:")
        for r in regressions:
            print(f"  {r}")
        print("fix the findings (preferred), suppress with an inline "
              "justification, or — with reviewer sign-off — regenerate "
              "the baseline via `python tools/kernellint_baseline.py`.")
        return 1
    print(f"ratchet OK: {len(findings)} findings, none above baseline")
    return 0


if __name__ == "__main__":
    sys.exit(check() if "--check" in sys.argv[1:] else generate())
