"""The shared VMEM cost model (KL001) — static analysis AND runtime.

Everything that needs to know whether a Pallas working set fits on-chip
reads THIS module:

* the KL001 rule checks statically-extracted block/scratch shapes
  against :func:`budget_bytes`;
* ``ops/pallas/decode_block.py``'s fusion-fallback gate
  (``unsupported_reason`` → ``DecodeBlockUnsupportedError``) computes
  its working set with :func:`decode_block_vmem`;
* ``ops/pallas``'s autotune candidate filters
  (``decode_block._fitting_candidates``, ``linear_ce._tuned_blocks``)
  drop configs :func:`fits` rejects before ever timing them.

Before ISSUE 10 the budget lived as a hand-maintained
``VMEM_BUDGET_BYTES = 12MB`` constant inside the decode-block kernel
plus an ad-hoc try/except skip in the autotuner; the static analyzer
could not see either.  Now there is one table and one estimator, so the
number the lint proves things about is the number the serving dispatch
enforces.

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
from typing import Dict, Iterable, Optional, Sequence, Tuple

__all__ = [
    "VMEM_BYTES_PER_CORE", "SAFETY_FRACTION", "DEFAULT_GENERATION",
    "MAX_HEAD_DIM", "LANE_WIDTH", "head_dim_lane_reason",
    "MODEL_TOLERANCE", "DMA_STAGING_SLOTS",
    "budget_bytes", "fits",
    "generation_from_device_kind", "itemsize", "Buffer", "vmem_bytes",
    "decode_block_vmem", "decode_block_weight_bytes",
    "decode_block_unsupported_reason",
    "prefill_block_vmem", "prefill_block_unsupported_reason",
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
# blocks, and compiler-internal temporaries.  0.75 * 16 MB reproduces
# the pre-ISSUE-10 hand constant (12 MB) exactly.
SAFETY_FRACTION = 0.75

DEFAULT_GENERATION = "v4"

# Attention-scratch layout cap carried over from the decode-block
# kernel (one (head, D) row must fit a VMEM register tile fan-out).
MAX_HEAD_DIM = 256

# Lanes of a TPU vector register.  The block megakernels split
# ``[rows, heads * head_dim]`` lanes into heads, a shape cast Mosaic
# only lowers when ``head_dim`` is a whole number of registers: on a
# v5e, head_dim 16 and 64 are refused ("infer-vector-layout:
# unsupported shape cast"), 128 and 256 compile and run (PR 22).
LANE_WIDTH = 128

# Documented tolerance for static-estimate vs kernel-declared bytes
# (tests/test_kernel_cost.py pins decode_block and linear_ce to it).
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


def head_dim_lane_reason(head_dim: int) -> Optional[str]:
    """Why the Mosaic lowering refuses the block megakernels at this
    ``head_dim``, or None.  A limit of the COMPILED kernel only — the
    interpreter has none, so the dispatch applies it when it compiles
    (``ops/pallas/decode_block.unsupported_reason``)."""
    if head_dim % LANE_WIDTH == 0:
        return None
    return (f"head_dim {head_dim} is not a multiple of the "
            f"{LANE_WIDTH}-lane vector width: the kernel splits "
            "[rows, heads*head_dim] lanes into heads, a shape cast "
            "Mosaic only lowers lane-aligned — per-op tier serves it")


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
# decode_block / prefill_block: the fused block megakernels (ops/pallas)
# ---------------------------------------------------------------------------
# Both block kernels stage KV pages through a revolving two-slot buffer
# (start the NEXT page-chunk's DMA while the current one accumulates),
# so the declared staging allocation is 2x the per-chunk footprint.
DMA_STAGING_SLOTS = 2


def _page_staging_bytes(pages: int, block_size: int, kv_heads: int,
                        head_dim: int, pool_itemsize: int,
                        kv_quant: bool) -> int:
    """Declared bytes of the double-buffered page staging tier: k + v
    data pages per slot, plus per-(token, head) fp32 scale rows when the
    pool is quantized (ops/paged_kv.QuantizedKVPool layout)."""
    per_chunk = 2 * pages * block_size * kv_heads * head_dim * pool_itemsize
    if kv_quant:
        per_chunk += 2 * pages * block_size * kv_heads * 4
    return DMA_STAGING_SLOTS * per_chunk


def decode_block_vmem(*, hidden: int, num_heads: int, kv_heads: int,
                      head_dim: int, block_size: int, pages: int,
                      weight_bytes: int, pool_itemsize: int,
                      x_itemsize: int = 4,
                      kv_quant: bool = False) -> Dict[str, int]:
    """Byte breakdown of one decode_block kernel invocation.

    Mirrors ``ops/pallas/decode_block._call`` exactly: the layer's full
    weight set streams into VMEM as whole-array blocks
    (``weight_bytes``), ``pages`` KV pages stage per attention chunk
    (k + v, two revolving DMA slots so the next chunk's copy overlaps
    the current chunk's accumulation), the online-softmax state is fp32
    scratch, and the residual stream/RoPE rows/outputs are one-row
    blocks.  Keys: ``weights``, ``staging``, ``scratch``, ``io``,
    ``total``.

    With ``kv_quant`` the pool is int8 data plus per-(token, head) fp32
    scales: the staging tier gains a scale row per page (k + v) and the
    kernel emits fp32 ``k_new``/``v_new`` (the host quantizes on
    append), so ``pool_itemsize`` must be 1 and the new-KV io rows are
    fp32.
    """
    Hq, Hkv, D, BS = num_heads, kv_heads, head_dim, block_size
    staging = _page_staging_bytes(pages, BS, Hkv, D, pool_itemsize,
                                  kv_quant)
    # fp32 scratch: q (Hq, D) + acc (Hq, D) + new k/v (2 * Hkv * D)
    # + running max/sum (2 * Hq)
    scratch = 4 * (2 * Hq * D + 2 * Hkv * D + 2 * Hq)
    new_kv_itemsize = 4 if kv_quant else pool_itemsize
    io = vmem_bytes([
        Buffer("x", (1, hidden), x_itemsize),
        Buffer("cos", (1, D), x_itemsize),
        Buffer("sin", (1, D), x_itemsize),
        Buffer("x_out", (1, hidden), x_itemsize),
        Buffer("k_new", (1, Hkv, D), new_kv_itemsize),
        Buffer("v_new", (1, Hkv, D), new_kv_itemsize),
    ])
    total = weight_bytes + staging + scratch + io
    return {"weights": weight_bytes, "staging": staging,
            "scratch": scratch, "io": io, "total": total}


def _quantized_matmul_bytes(k: int, n: int, weight_dtype: Optional[str],
                            group_size: int, itemsize_: int) -> int:
    """Stored bytes of one (K, N) matmul weight under weight-only
    quantization — the ``nn.quant.weight_quantize`` layout: int8 keeps
    K*N one-byte codes, int4 packs two codes per byte along K (halves
    packing, ceil(K/2) rows), and every matmul carries fp32 scales —
    one per output channel (``group_size == -1``) or one per
    (K-group, channel)."""
    if weight_dtype is None:
        return k * n * itemsize_
    groups = 1 if group_size in (-1, None, 0) else -(-k // int(group_size))
    scale = groups * n * 4
    if weight_dtype == "int8":
        return k * n + scale
    if weight_dtype == "int4":
        return -(-k // 2) * n + scale
    raise ValueError(f"unknown weight_dtype {weight_dtype!r} "
                     "(want None, 'int8' or 'int4')")


def decode_block_weight_bytes(*, hidden: int, num_heads: int,
                              kv_heads: int, head_dim: int,
                              ffn_hidden: int, arch: str = "llama",
                              fused_qkv: bool = False, bias: bool = False,
                              weight_dtype: Optional[str] = None,
                              group_size: int = -1,
                              itemsize_: int = 4) -> int:
    """Closed-form bytes of one decode-block layer's weight set, with
    optional weight-only quantization — the static side of the fusion
    envelope proof (``decode_block_unsupported_reason`` admits widths
    under int8/int4 that fall back at full width).

    Matmul weights quantize (int8: 1 B/code; int4: packed halves,
    ceil(K/2) rows; + fp32 scales per channel or per (group, channel));
    norm weights and biases stay at ``itemsize_`` — exactly what
    ``quantization.serve.quantize_params_for_serving`` produces.
    """
    H, Hq, Hkv, D, F = hidden, num_heads, kv_heads, head_dim, ffn_hidden

    def mm(k, n):
        return _quantized_matmul_bytes(k, n, weight_dtype, group_size,
                                       itemsize_)

    if fused_qkv:
        qkv = mm(H, (Hq + 2 * Hkv) * D)
    else:
        qkv = mm(H, Hq * D) + 2 * mm(H, Hkv * D)
    total = qkv + mm(Hq * D, H)
    if arch == "llama":
        total += 2 * mm(H, F) + mm(F, H)          # gate, up, down
        total += 2 * H * itemsize_                # ln1_w, ln2_w
    elif arch == "gpt":
        total += mm(H, F) + mm(F, H)              # fc, proj
        total += 2 * H * itemsize_                # ln1_w, ln2_w
    else:
        raise ValueError(f"unknown arch {arch!r}")
    if bias:
        # qkv + o + fc/proj (+ up/gate-less llama has no bias path, but
        # the spec permits it symmetrically) and the layernorm biases
        nb = (Hq + 2 * Hkv) * D + H + F + H + 2 * H
        total += nb * itemsize_
    return total


def decode_block_unsupported_reason(
        *, hidden: int, num_heads: int, kv_heads: int, head_dim: int,
        block_size: int, rope: bool, weight_bytes: int,
        pool_itemsize: int, x_itemsize: int = 4,
        kv_quant: bool = False,
        budget: Optional[int] = None,
        generation: Optional[str] = None) -> Optional[str]:
    """None when one decode_block layer fits the kernel's limits, else
    a human-readable reason — the runtime fusion-fallback signal
    (``DecodeBlockUnsupportedError`` when the kernel is forced) and the
    KL001 ground truth, from one formula."""
    D = head_dim
    if D > MAX_HEAD_DIM:
        return f"head_dim {D} exceeds the kernel cap {MAX_HEAD_DIM}"
    if rope and D % 2:
        return f"rotate-half RoPE needs an even head_dim, got {D}"
    limit = budget if budget is not None else budget_bytes(generation)
    est = decode_block_vmem(
        hidden=hidden, num_heads=num_heads, kv_heads=kv_heads,
        head_dim=D, block_size=block_size, pages=1,
        weight_bytes=weight_bytes, pool_itemsize=pool_itemsize,
        x_itemsize=x_itemsize, kv_quant=kv_quant)
    if est["total"] > limit:
        return (f"layer needs ~{est['total'] / 2**20:.1f} MB VMEM "
                f"({est['weights'] / 2**20:.1f} MB weights) > budget "
                f"{limit / 2**20:.1f} MB — multi-core fusion "
                "territory, per-op tier serves it")
    return None


def prefill_block_vmem(*, hidden: int, num_heads: int, kv_heads: int,
                       head_dim: int, block_size: int, pages: int,
                       chunk: int, weight_bytes: int, pool_itemsize: int,
                       x_itemsize: int = 4,
                       kv_quant: bool = False) -> Dict[str, int]:
    """Byte breakdown of one prefill_block kernel invocation — the
    chunked-prefill twin of :func:`decode_block_vmem`.

    Mirrors ``ops/pallas/prefill_block._call``: the same whole-array
    weight blocks and double-buffered page staging as the decode
    kernel, but the resident tile is ``chunk`` prompt tokens instead of
    one — q/new-k/new-v/acc scratch and the io blocks all scale by
    ``chunk``, and the in-chunk causal attention runs over the same
    scratch the epilogue folds.  Keys: ``weights``, ``staging``,
    ``scratch``, ``io``, ``total``.
    """
    Hq, Hkv, D, BS = num_heads, kv_heads, head_dim, block_size
    staging = _page_staging_bytes(pages, BS, Hkv, D, pool_itemsize,
                                  kv_quant)
    # fp32 scratch, all carrying the chunk-tile dim: q (Hq, chunk, D)
    # + acc (Hq, chunk, D) + new k/v (2 * Hkv * chunk * D) + running
    # max/sum (2 * Hq * chunk) — the decode formula times the tile
    scratch = 4 * chunk * (2 * Hq * D + 2 * Hkv * D + 2 * Hq)
    new_kv_itemsize = 4 if kv_quant else pool_itemsize
    io = vmem_bytes([
        Buffer("x", (chunk, hidden), x_itemsize),
        Buffer("cos", (chunk, D), x_itemsize),
        Buffer("sin", (chunk, D), x_itemsize),
        Buffer("x_out", (chunk, hidden), x_itemsize),
        Buffer("k_new", (chunk, Hkv, D), new_kv_itemsize),
        Buffer("v_new", (chunk, Hkv, D), new_kv_itemsize),
    ])
    total = weight_bytes + staging + scratch + io
    return {"weights": weight_bytes, "staging": staging,
            "scratch": scratch, "io": io, "total": total}


def prefill_block_unsupported_reason(
        *, hidden: int, num_heads: int, kv_heads: int, head_dim: int,
        block_size: int, chunk: int, rope: bool, weight_bytes: int,
        pool_itemsize: int, x_itemsize: int = 4,
        kv_quant: bool = False,
        budget: Optional[int] = None,
        generation: Optional[str] = None) -> Optional[str]:
    """None when one prefill_block chunk fits the kernel's limits, else
    a human-readable reason — the runtime fusion-fallback signal
    (``PrefillBlockUnsupportedError`` when the kernel is forced), from
    the same formula the autotune validity filter reads."""
    D = head_dim
    if D > MAX_HEAD_DIM:
        return f"head_dim {D} exceeds the kernel cap {MAX_HEAD_DIM}"
    if rope and D % 2:
        return f"rotate-half RoPE needs an even head_dim, got {D}"
    limit = budget if budget is not None else budget_bytes(generation)
    est = prefill_block_vmem(
        hidden=hidden, num_heads=num_heads, kv_heads=kv_heads,
        head_dim=D, block_size=block_size, pages=1, chunk=chunk,
        weight_bytes=weight_bytes, pool_itemsize=pool_itemsize,
        x_itemsize=x_itemsize, kv_quant=kv_quant)
    if est["total"] > limit:
        return (f"chunk of {chunk} needs ~{est['total'] / 2**20:.1f} MB "
                f"VMEM ({est['weights'] / 2**20:.1f} MB weights) > "
                f"budget {limit / 2**20:.1f} MB — multi-core fusion "
                "territory, per-op tier serves it")
    return None


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
