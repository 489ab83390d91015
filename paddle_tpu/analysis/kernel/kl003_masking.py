"""KL003 — tile-edge masking discipline.

A grid axis built with ``pl.cdiv`` (or the ``-(-a // b)`` idiom) means
the LAST tile on that axis can run past the real extent: the block
machinery still delivers a full block (zero/garbage padded, or clamped
re-reads), so a kernel that folds such a tile into a reduction without
masking silently corrupts the result — off-TPU the interpret lane may
even hide it because padding happens to be zeros.

The rule demands that a kernel behind a ceil-divided grid contains at
least one masking construct in its transitive body: ``pl.when``,
``jnp.where``, a ``broadcasted_iota``/``iota`` position stream, or an
index clamp (``minimum``/``maximum``/``clip``).  This matches how
every masked kernel in the repo is written (linear_ce masks
``cols < V``; decode_attention masks ``t < length``).
"""

from __future__ import annotations

import ast

from .. import core
from .extract import extract_sites, kernel_closure

_MASK_TAILS = {"when", "where", "broadcasted_iota", "iota", "minimum",
               "maximum", "clip", "select", "select_n"}


@core.register
class TileEdgeMaskRule(core.Rule):
    id = "KL003"
    name = "unmasked-tile-edge"
    severity = "warning"
    doc = ("a pallas_call grid uses ceil-division (pl.cdiv / "
           "-(-a // b)) so its last tile overhangs the data, but the "
           "kernel body has no masking construct (pl.when / where / "
           "iota / clamp)")
    hint = ("mask the overhang: compare an iota position stream "
            "against the true extent (see linear_ce `cols < V`), or "
            "guard the fold with pl.when")

    def check(self, module):
        for site in extract_sites(module):
            if not site.grid_has_cdiv:
                continue
            body = kernel_closure(site)
            if not body:
                continue            # kernel unresolved: nothing provable
            masked = any(
                isinstance(node, ast.Call)
                and core.tail_name(node.func) in _MASK_TAILS
                for fn in body for node in ast.walk(fn))
            if not masked:
                yield self.finding(
                    module, site.call,
                    f"grid of kernel `{site.kernel_name}` uses "
                    "ceil-division but the kernel body never masks the "
                    "tile overhang")
