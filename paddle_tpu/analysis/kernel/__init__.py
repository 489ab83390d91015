"""kernellint — static Pallas-kernel safety analysis (ISSUE 10).

The package has two faces sharing one cost model:

* :mod:`.cost` — the VMEM cost model.  Closed-form per-kernel byte
  estimates plus the per-generation budget table.  This is ALSO the
  runtime source of truth: the fused CE head's dispatch
  (``ops/fused_cross_entropy.py``) and ``ops/pallas``'s autotune
  config-validity filter import it, so the number the static analyzer
  checks against is the number the dispatch actually enforces — they
  cannot drift.
* :mod:`.extract` + the ``kl00X_*`` rule modules — an AST model of
  every ``pl.pallas_call`` site (grid, BlockSpecs, index maps,
  scratch_shapes, dtypes) feeding the KL001–KL006 rules, registered in
  the same engine as tracelint (``analysis/core.py``): one CLI, one
  suppression syntax, one ratchet machinery, a separate KERNELLINT.md
  ledger.

``cost`` deliberately imports no jax: the analyzer (and CI ratchet)
must run on a bare interpreter, and the runtime callers only hand it
plain ints/strs.
"""

from . import cost  # noqa: F401

__all__ = ["cost"]
