"""The shared VMEM cost model (KL001) — static analysis AND runtime.

Everything that needs to know whether a Pallas working set fits on-chip
reads THIS module:

* the KL001 rule checks statically-extracted block/scratch shapes
  against :func:`budget_bytes`;
* the fused CE head's dispatch (``ops/fused_cross_entropy.py``) asks
  :func:`linear_ce_unsupported_reason` whether its kernels can fit at
  all, and its backward sizes its tile with
  :func:`linear_ce_bwd_blocks`;
* ``ops/pallas/linear_ce._tuned_blocks``' autotune candidate filter
  drops configs :func:`linear_ce_fits` rejects before ever timing them;
* ``ops/pallas/ssm.py`` sizes its state block from
  :func:`budget_bytes`, ``ops/pallas/moe_grouped_matmul.py`` its weight
  blocks.

There is one table and one estimator, so the number the lint proves
things about is the number the dispatches enforce.

The byte model is the sum of per-grid-step VMEM residents: one block
per (in_spec, out_spec) with a block shape (``None`` dims count 1;
``SMEM``/``ANY`` specs don't occupy VMEM) plus every ``pltpu.VMEM``
scratch entry.  It deliberately does NOT model Mosaic's (8, 128) tile
padding or double-buffering of streamed blocks — both round UP, so the
documented contract is: the estimate is within ``MODEL_TOLERANCE`` of
the kernel's declared allocation (pinned by tests/test_kernel_cost.py
against interpret-mode-captured block+scratch bytes), and the safety
margin for padding/double-buffering lives in ``SAFETY_FRACTION``.

No jax imports: the analyzer and the CI ratchet run this on a bare
interpreter; runtime callers pass plain ints and dtype strings.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Optional, Tuple

__all__ = [
    "VMEM_BYTES_PER_CORE", "SAFETY_FRACTION", "DEFAULT_GENERATION",
    "MODEL_TOLERANCE", "budget_bytes", "fits",
    "generation_from_device_kind", "itemsize", "Buffer", "vmem_bytes",
    "linear_ce_vmem", "linear_ce_fits", "linear_ce_bwd_vmem",
    "linear_ce_bwd_blocks", "linear_ce_unsupported_reason",
]

# Physical per-core VMEM by TPU generation (the Pallas guide's ~16 MB
# figure for v4/v5; v6e doubles it).  "interpret" is the CPU tier-1
# lane: budgeted like v4 so the dispatch decisions tier-1 pins are the
# ones real hardware makes.
VMEM_BYTES_PER_CORE: Dict[str, int] = {
    "v4": 16 * 2 ** 20,
    "v5e": 16 * 2 ** 20,
    "v5p": 16 * 2 ** 20,
    "v6e": 32 * 2 ** 20,
    "interpret": 16 * 2 ** 20,
}

# Fraction of physical VMEM a single kernel's declared working set may
# claim.  The remainder absorbs what the closed form does not model:
# Mosaic (8, 128) tile padding, pipeline double-buffering of streamed
# blocks, and compiler-internal temporaries: 12 MB of a 16 MB core.
SAFETY_FRACTION = 0.75

DEFAULT_GENERATION = "v4"

# Documented tolerance for static-estimate vs kernel-declared bytes
# (tests/test_kernel_cost.py pins linear_ce to it).
MODEL_TOLERANCE = 0.02

_ITEMSIZE = {
    "float64": 8, "int64": 8, "uint64": 8, "complex64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool": 1,
    "float8_e4m3fn": 1, "float8_e5m2": 1, "float8_e4m3": 1,
}


def itemsize(dtype) -> int:
    """Bytes per element for a dtype given as string or anything whose
    ``str()`` names one ("bfloat16", ``jnp.float32``, ``np.dtype``)."""
    s = str(dtype)
    s = s.rsplit(".", 1)[-1].strip("'\"<>")   # "<class 'jax...bfloat16'>"
    if s in _ITEMSIZE:
        return _ITEMSIZE[s]
    for name, n in _ITEMSIZE.items():
        if name in s:
            return n
    raise ValueError(f"unknown dtype {dtype!r} for itemsize")


def generation_from_device_kind(kind: str) -> str:
    """Map a jax ``device_kind`` string to a budget-table key; unknown
    kinds get the conservative default generation."""
    k = kind.lower()
    for gen in ("v6e", "v5p", "v5e", "v4"):
        if gen in k:
            return gen
    return DEFAULT_GENERATION


def budget_bytes(generation: Optional[str] = None) -> int:
    """Usable single-kernel VMEM budget for a generation (the ONE
    number every fusion/validity decision compares against)."""
    gen = generation or DEFAULT_GENERATION
    if gen not in VMEM_BYTES_PER_CORE:
        raise KeyError(f"unknown TPU generation {gen!r}; have "
                       f"{sorted(VMEM_BYTES_PER_CORE)}")
    return int(VMEM_BYTES_PER_CORE[gen] * SAFETY_FRACTION)


def fits(total_bytes: int, generation: Optional[str] = None) -> bool:
    return total_bytes <= budget_bytes(generation)


@dataclasses.dataclass(frozen=True)
class Buffer:
    """One VMEM-resident buffer: a per-grid-step block or a scratch
    allocation.  ``None`` dims (Pallas squeezed block dims) count 1."""
    name: str
    shape: Tuple[Optional[int], ...]
    itemsize: int

    @property
    def bytes(self) -> int:
        n = 1
        for d in self.shape:
            n *= 1 if d is None else int(d)
        return n * self.itemsize


def vmem_bytes(buffers: Iterable[Buffer]) -> int:
    """Total declared VMEM of a kernel invocation: per-grid-step input/
    output blocks plus scratch accumulators/staging."""
    return sum(b.bytes for b in buffers)


# ---------------------------------------------------------------------------
# linear_ce: the fused CE head forward kernel (ops/pallas/linear_ce)
# ---------------------------------------------------------------------------
def linear_ce_vmem(*, block_rows: int, chunk: int, hidden: int,
                   x_itemsize: int = 4, w_itemsize: int = 4) -> Dict[str, int]:
    """Byte breakdown of one linear_ce forward invocation per grid
    step, mirroring ``ops/pallas/linear_ce._fwd``: an activation row
    block, a vocab-chunk weight block, the label column, two fp32
    outputs and four fp32 online-softmax scratch columns."""
    br, C, H = block_rows, chunk, hidden
    blocks = vmem_bytes([
        Buffer("x", (br, H), x_itemsize),
        Buffer("w", (C, H), w_itemsize),
        Buffer("labels", (br, 1), 4),
        Buffer("nll", (br, 1), 4),
        Buffer("lse", (br, 1), 4),
    ])
    scratch = 4 * br * 4
    return {"blocks": blocks, "scratch": scratch,
            "total": blocks + scratch}


def linear_ce_fits(block_rows: int, chunk: int, hidden: int,
                   x_itemsize: int = 4, w_itemsize: int = 4,
                   generation: Optional[str] = None) -> bool:
    """Autotune validity: can a (block_rows, chunk) candidate's working
    set ever fit?  ``_tuned_blocks`` filters candidates through this
    BEFORE timing them — a config this rejects would only die inside
    Mosaic on hardware, after burning a compile."""
    return fits(linear_ce_vmem(block_rows=block_rows, chunk=chunk,
                               hidden=hidden, x_itemsize=x_itemsize,
                               w_itemsize=w_itemsize)["total"],
                generation)


# Smallest backward tile the lowering accepts: 8 sublanes of rows, one
# 128-lane vocab chunk.
_LINEAR_CE_MIN_BLOCKS = (8, 128)


def linear_ce_bwd_vmem(*, block_rows: int, chunk: int, hidden: int,
                       x_itemsize: int = 4, w_itemsize: int = 4) -> int:
    """Scoped VMEM the larger of the two backward kernels
    (``ops/pallas/linear_ce._bwd``: dx, then dw) asks Mosaic for.
    Unlike the forward model above this one COUNTS the pipeline's two
    buffers per streamed block, because here they decide: every in/out
    block twice plus one fp32 accumulator the size of the output block.
    It reproduces the compiler's own figure — (256, 512) at H 4096 bf16
    is 20 MiB for dx, which a v5e's 16 MiB scoped limit refuses."""
    x_blk = block_rows * hidden * x_itemsize
    w_blk = chunk * hidden * w_itemsize
    dx = 2 * x_blk + 2 * w_blk + block_rows * hidden * 4 + 2 * x_blk
    dw = 2 * x_blk + 2 * w_blk + chunk * hidden * 4 + 2 * w_blk
    return max(dx, dw)


def linear_ce_bwd_blocks(block_rows: int, chunk: int, hidden: int,
                         x_itemsize: int = 4, w_itemsize: int = 4,
                         generation: Optional[str] = None
                         ) -> Tuple[int, int]:
    """The forward's (block_rows, chunk) halved — larger side first —
    until the backward kernels fit the budget or reach the smallest
    tile.  The backward recomputes from ``lse``, so its tiling is free
    to differ from the forward's."""
    br, c = block_rows, chunk
    min_br, min_c = _LINEAR_CE_MIN_BLOCKS

    def over():
        return not fits(linear_ce_bwd_vmem(
            block_rows=br, chunk=c, hidden=hidden, x_itemsize=x_itemsize,
            w_itemsize=w_itemsize), generation)

    while over() and (br > min_br or c > min_c):
        if c > min_c and (c * w_itemsize >= br * x_itemsize
                          or br <= min_br):
            c //= 2
        else:
            br //= 2
    return br, c


def linear_ce_unsupported_reason(hidden: int, x_itemsize: int = 4,
                                 w_itemsize: int = 4,
                                 generation: Optional[str] = None
                                 ) -> Optional[str]:
    """None when the fused CE head's kernels can fit at this hidden
    size at all, else the reason (the dispatch's typed-fallback signal):
    the smallest backward tile still holds whole ``[rows, H]`` and
    ``[chunk, H]`` blocks, so a wide enough ``H`` overflows VMEM
    whatever the tiling."""
    br, c = _LINEAR_CE_MIN_BLOCKS
    need = linear_ce_bwd_vmem(block_rows=br, chunk=c, hidden=hidden,
                              x_itemsize=x_itemsize,
                              w_itemsize=w_itemsize)
    if fits(need, generation):
        return None
    return (f"linear_ce backward needs {need} B of VMEM at its smallest "
            f"tile {(br, c)} for hidden={hidden} (itemsizes "
            f"{x_itemsize}/{w_itemsize}); budget "
            f"{budget_bytes(generation)} B")
