"""KL001 — provable VMEM overflow at a pallas_call site.

The finding is a PROOF, not a guess: every contributing term is a
lower bound (unproven dims count 1, unproven dtypes count 1 byte,
unparsed buffers count 0), so if the provable working set alone
exceeds :func:`cost.budget_bytes` the kernel can never fit on any
configured generation's core — Mosaic would reject it on hardware
after a compile this rule catches at review time.

Runtime-dependent geometries (most real kernels) are NOT flagged: for
those, the same cost model is enforced dynamically by the fused CE
head's fallback in ``ops/fused_cross_entropy.py`` and the autotune
validity filters, which this package is the single source of truth
for.
"""

from __future__ import annotations

from .. import core
from . import cost
from .extract import extract_sites

_SEVERITY_NOTE = "provable lower bound"


def provable_bytes(site) -> int:
    """Sound lower bound of a site's per-grid-step VMEM residency."""
    total = 0
    for spec, dtype in (
            [(s, None) for s in site.in_specs]
            + list(zip(site.out_specs,
                       site.out_dtypes + [None] * len(site.out_specs)))):
        if not spec.known or spec.memory_space != "vmem":
            continue
        shape = spec.resolved_shape
        if shape is None:
            continue
        isz = 1
        if dtype is not None:
            try:
                isz = cost.itemsize(dtype)
            except ValueError:
                isz = 1
        total += cost.Buffer("block", shape, isz).bytes
    for scr in site.scratch:
        if scr.kind != "vmem" or scr.shape is None:
            continue
        shape = tuple(d if isinstance(d, int) else None
                      for d in scr.shape)
        isz = 1
        if scr.dtype is not None:
            try:
                isz = cost.itemsize(scr.dtype)
            except ValueError:
                isz = 1
        total += cost.Buffer("scratch", shape, isz).bytes
    return total


@core.register
class VmemFootprintRule(core.Rule):
    id = "KL001"
    name = "vmem-overflow"
    severity = "error"
    doc = ("a pallas_call's statically-provable per-grid-step working "
           "set (blocks + scratch, lower-bounded) exceeds the "
           "analysis/kernel/cost.py VMEM budget — the kernel can never "
           "fit a core")
    hint = ("shrink the block/scratch shapes or split the kernel; the "
            "budget table lives in analysis/kernel/cost.py "
            "(budget_bytes) — the same number the runtime fusion "
            "fallback enforces")

    def check(self, module):
        budget = cost.budget_bytes()
        for site in extract_sites(module):
            lb = provable_bytes(site)
            if lb > budget:
                yield self.finding(
                    module, site.call,
                    f"pallas_call working set is provably >= "
                    f"{lb / 2**20:.1f} MB "
                    f"({_SEVERITY_NOTE}) > VMEM budget "
                    f"{budget / 2**20:.1f} MB "
                    f"({cost.DEFAULT_GENERATION}, "
                    f"{int(cost.SAFETY_FRACTION * 100)}% of "
                    f"{cost.VMEM_BYTES_PER_CORE[cost.DEFAULT_GENERATION] / 2**20:.0f} MB)")
