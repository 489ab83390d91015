"""The comparison that decides ``correct``: each number compared, with
the limit the cell's file gives it."""

from __future__ import annotations

import statistics
from typing import Dict, List

import numpy as np

# a leaf whose reference gradient is under this share of the median
# leaf's is nought to rounding (a norm gain's, say): under Adam it moves
# by round-off alone, so its CHANGE is not compared (its gradient still
# is, against the median leaf's norm)
TINY_GRADIENT = 1e-3


def flatten_leaves(tree: Dict) -> Dict[str, float]:
    """``{"q_w": [per layer], "wte": x}`` -> ``{"0.q_w": .., "wte": x}``,
    the reference's names."""
    out = {}
    for name, v in tree.items():
        v = np.asarray(v)
        if v.ndim == 0:
            out[name] = float(v)
        else:
            for i, x in enumerate(v.reshape(-1)):
                out[f"{i}.{name}"] = float(x)
    return out


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float],
                   leaves=None) -> Dict:
    """The widest gap between the program's norm and the reference's
    over the leaves, measured against the reference's norm of that leaf
    or of the median leaf, whichever is larger (some are all but
    zero)."""
    leaves = sorted(want) if leaves is None else leaves
    median = statistics.median(want[k] for k in sorted(want))
    worst, where = 0.0, None
    for k in leaves:
        gap = abs(got[k] - want[k]) / max(want[k], median)
        if gap >= worst:
            worst, where = gap, k
    return {"gap": worst, "leaf": where, "median": median}


def train_readings(first: Dict, want: Dict) -> Dict:
    """The numbers compared for a training cell, with where the worst
    leaf was."""
    out = {}
    for k, (a, b) in enumerate(zip(first["losses"], want["losses"]), 1):
        out[f"loss_gap_step{k}"] = abs(a - b) / abs(b)
    if set(first["grad_norm"]) != set(want["grad_norm"]):
        raise ValueError("the program's leaves are not the reference's: "
                         f"{sorted(first['grad_norm'])} vs "
                         f"{sorted(want['grad_norm'])}")
    g = worst_leaf_gap(first["grad_norm"], want["grad_norm"])
    moved = [k for k in sorted(want["grad_norm"])
             if want["grad_norm"][k] >= TINY_GRADIENT * g["median"]]
    d = worst_leaf_gap(first["delta_norm"], want["delta_norm"], moved)
    out["first_grad_norm_gap"] = g["gap"]
    out["param_change_gap"] = d["gap"]
    out["_where"] = {"first_grad_norm_gap": g["leaf"],
                     "param_change_gap": d["leaf"]}
    return out


def train_checks(first: Dict, want: Dict, limits: Dict) -> List[Dict]:
    r = train_readings(first, want)
    return [{"name": k, "value": r[k], "limit": limits[k]}
            for k in limits]


def serve_checks(gaps: Dict, leak: int, limits: Dict) -> List[Dict]:
    values = {"widest_gap": gaps["widest_gap"],
              "mean_gap": gaps["mean_gap"],
              "kv_leaked_blocks": leak,
              "tokens_compared_short": max(
                  0, int(limits.get("min_tokens_compared", 0))
                  - gaps["tokens"])}
    limits = dict(limits, tokens_compared_short=0)
    limits.pop("min_tokens_compared", None)
    return [{"name": k, "value": values[k], "limit": limits[k]}
            for k in limits]


def judge(checks: List[Dict]) -> bool:
    """``correct``: every number compared is there and within its
    limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks)
