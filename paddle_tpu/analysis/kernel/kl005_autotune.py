"""KL005 — autotune coverage drift.

The autotune registry (``ops/pallas/autotune``) is the ONLY channel
through which tunable kernel configs reach traced code: ``pick`` times
candidates eagerly at warmup, ``lookup`` reads the cached winner at
trace time.  Two drift modes have bitten similar stacks:

* a module grows a ``*_CANDIDATES`` tuple but never registers it —
  the knob silently stays at its default forever and the sweep code
  rots unexercised;
* the ``pick`` and ``lookup`` key strings drift apart (tuner writes
  under one name, trace-time reads another) — every traced call
  silently gets the default while the tuned winner sits unused in the
  cache.

The cost-model half of autotune hygiene ("a candidate that can never
fit") is enforced at RUNTIME, where the true shapes exist: candidate
lists are filtered through ``analysis/kernel/cost.py`` before timing
(``linear_ce._tuned_blocks``) and ``pick(valid=...)`` refuses provably-overflowing configs instead
of burning a compile to discover them.
"""

from __future__ import annotations

import ast
import re

from .. import core

_CANDIDATES_RE = re.compile(r"^_?[A-Z0-9_]*CANDIDATES$")
_REGISTRY_CALLS = {"pick", "lookup"}


def _key_literal(call: ast.Call):
    if call.args and isinstance(call.args[0], ast.Constant) \
            and isinstance(call.args[0].value, str):
        return call.args[0].value
    return None


@core.register
class AutotuneCoverageRule(core.Rule):
    id = "KL005"
    name = "autotune-coverage-drift"
    severity = "warning"
    doc = ("a *_CANDIDATES tuple exists with no ops/pallas/autotune "
           "pick/lookup registration in the module, or the module's "
           "pick and lookup key strings disagree")
    hint = ("register the knob: pick(\"<key>\", ...) at warmup, "
            "lookup(\"<key>\", ...) at trace time, one key string per "
            "kernel; dead candidate tuples should be deleted")

    def check(self, module):
        cand_nodes = []
        pick_keys, lookup_keys = set(), set()
        has_registry_call = False
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and _CANDIDATES_RE.match(node.targets[0].id) \
                    and isinstance(node.value, (ast.Tuple, ast.List)):
                cand_nodes.append(node)
            elif isinstance(node, ast.Call) \
                    and core.tail_name(node.func) in _REGISTRY_CALLS:
                has_registry_call = True
                key = _key_literal(node)
                if key is not None:
                    (pick_keys if core.tail_name(node.func) == "pick"
                     else lookup_keys).add(key)
        if not has_registry_call:
            for node in cand_nodes:
                yield self.finding(
                    module, node,
                    f"candidates tuple `{node.targets[0].id}` is not "
                    "registered with ops/pallas/autotune (no "
                    "pick/lookup call in this module) — the knob can "
                    "never leave its default")
        if pick_keys and lookup_keys and pick_keys != lookup_keys:
            missing = sorted(pick_keys ^ lookup_keys)
            anchor = next(
                (n for n in ast.walk(module.tree)
                 if isinstance(n, ast.Call)
                 and core.tail_name(n.func) in _REGISTRY_CALLS
                 and _key_literal(n) in missing), module.tree)
            yield self.finding(
                module, anchor,
                f"autotune key drift: pick registers {sorted(pick_keys)} "
                f"but lookup reads {sorted(lookup_keys)} — the traced "
                "path would silently use defaults")
