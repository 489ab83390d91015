"""The main path's kernels, compiled for a DESCRIBED TPU v5e at real
widths — what interpret mode and ``jax.export`` cannot see: block shapes
the Mosaic lowering refuses, scoped-VMEM overflow, HBM overflow.

The TPU compiler is installed in the sandbox and compiles for a chip
that is described, not attached (``on-chip-measurement`` guide,
section 2, rehearsal 3).  Nothing runs: a pass here is not a chip run.

Rules this file keeps (the guide says why): the topology is described
in a module-scoped fixture — never at import, in a ``skipif``, in
``parametrize`` or in ``conftest.py`` — because only one process may
load libtpu, and every xdist worker imports every test file; every
compile happens in this process; all such tests live in this ONE file.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.core.flags import FLAGS

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """``chip(shape, dtype)`` -> a ShapeDtypeStruct on one described
    chip; the persistent compile cache is off while the module runs (a
    described-chip entry is written but can never be read back here)."""
    from paddle_tpu.aot.artifact import fresh_backend_compile
    one = SingleDeviceSharding(topo.devices[0])
    with fresh_backend_compile():
        yield lambda shape, dtype=BF16: jax.ShapeDtypeStruct(
            tuple(shape), jnp.dtype(dtype), sharding=one)


@pytest.fixture(autouse=True)
def force_mosaic():
    """Kernels take their real Mosaic path though the backend is CPU."""
    FLAGS.pallas_force_compile = True
    yield
    FLAGS.pallas_force_compile = False


def compile_kernel(fn, *args, kernels=1):
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") >= kernels, \
        "a kernel fell back to a non-Mosaic path"
    return compiled


def on(chip, tree):
    return jax.tree.map(lambda a: chip(a.shape, a.dtype), tree)


def fsum(x):
    return x.astype(jnp.float32).sum()


# ---------------------------------------------------------------------
# training path: attention, CE head, norm, rope
# ---------------------------------------------------------------------
def test_flash_attention_fwd_bwd(chip):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    q = chip((4, 2048, 32, 128))

    def attn(a, b, c):
        return flash_attention(a, b, c, None, True)

    compile_kernel(attn, q, q, q)
    compile_kernel(jax.grad(lambda a, b, c: fsum(attn(a, b, c)),
                            argnums=(0, 1, 2)), q, q, q, kernels=2)


def test_splash_attention_fwd_bwd(chip):
    """The kernel the train cell runs (``tuned_flash`` leads with it on
    a TPU), at the cell's shape and at the tiles ``splash_block_sizes``
    gives there: held to the compiler's scoped-VMEM limit here."""
    from paddle_tpu.ops.pallas.flash_backends import (run_backend,
                                                      splash_block_sizes)
    q, kv = chip((4, 2048, 32, 128)), chip((4, 2048, 8, 128))

    def attn(a, b, c):
        return run_backend("splash", a, b, c, 1.0 / math.sqrt(128), True)

    fused = splash_block_sizes(2048, 2048, 128, 4).use_fused_bwd_kernel
    compile_kernel(attn, q, kv, kv)
    compile_kernel(jax.grad(lambda a, b, c: fsum(attn(a, b, c)),
                            argnums=(0, 1, 2)), q, kv, kv,
                   kernels=2 if fused else 3)


def test_linear_cross_entropy_fwd_bwd(chip):
    """The llama_7b-width head (b4 x s2048 tokens) on the tier the
    dispatch picks on a TPU.  At the forward's (256, 512) tile the
    backward needs 20 MiB of scoped VMEM and was refused; it now
    shrinks its own tile (cost.linear_ce_bwd_blocks)."""
    from paddle_tpu.ops.fused_cross_entropy import (
        linear_cross_entropy, pallas_unsupported_reason)
    x, w, lab = chip((8192, 4096)), chip((4096, 32000)), \
        chip((8192,), jnp.int32)
    assert pallas_unsupported_reason(x, w) is None

    def nll(x, w, lab):
        return linear_cross_entropy(x, w, lab, w_layout="hv",
                                    backend="pallas").sum()

    compile_kernel(nll, x, w, lab)
    compile_kernel(jax.grad(nll, argnums=(0, 1)), x, w, lab, kernels=3)


def test_linear_cross_entropy_refusal_is_typed(chip):
    """Past the width whose smallest backward tile fits VMEM the
    dispatch stands down with a reason, and forcing the kernel raises
    the typed error naming it."""
    from paddle_tpu.ops.fused_cross_entropy import (
        LinearCEUnsupportedError, linear_cross_entropy,
        pallas_unsupported_reason)
    x, w = chip((256, 16384), jnp.float32), chip((1024, 16384),
                                                 jnp.float32)
    reason = pallas_unsupported_reason(x, w)
    assert reason is not None and "VMEM" in reason
    with pytest.raises(LinearCEUnsupportedError, match="VMEM"):
        jax.eval_shape(lambda a, b, c: linear_cross_entropy(
            a, b, c, backend="pallas"), x, w, chip((256,), jnp.int32))


def test_rms_norm_fwd_bwd(chip):
    from paddle_tpu.ops.pallas.norms import rms_norm
    x, w = chip((8192, 4096)), chip((4096,))
    compile_kernel(lambda x, w: rms_norm(x, w, 1e-5), x, w)
    compile_kernel(jax.grad(lambda x, w: fsum(rms_norm(x, w, 1e-5)),
                            argnums=(0, 1)), x, w)


def test_rope_fwd_bwd(chip):
    from paddle_tpu.ops.pallas.rope import fused_rope
    q, cs = chip((4, 2048, 32, 128)), chip((2048, 128), jnp.float32)

    def rope(q, k, cos, sin):
        return fused_rope(q, k, None, sin, cos)[:2]

    compile_kernel(rope, q, q, cs, cs, kernels=2)
    compile_kernel(jax.grad(lambda *a: sum(fsum(t) for t in rope(*a)),
                            argnums=(0, 1)), q, q, cs, cs, kernels=2)


# ---------------------------------------------------------------------
# serving path: the engine's programs, decode attention, int8 matmul
# ---------------------------------------------------------------------
# the Mistral serve cells' engine (benchmark/configs: 16 layers of the
# published widths, 32 slots, 1024 pages of 16, a table of 256)
_SERVE = dict(layers=16, slots=32, pages=1024, page=16, table=256)
_MIB = 1 << 20
_COPY = re.compile(r"= (\w+)\[([\d,]*)\]\S* copy(?:-start)?\(")


def _bank_cut(E, h, f):
    """One layer's bank of ``E`` experts ``[h, f]`` (or its transpose)
    as the result of a slice."""
    return re.compile(r"= bf16\[(?:1,)?%d,(?:%d,%d|%d,%d)\]\S* "
                      r"(?:dynamic-)?slice\(" % (E, h, f, f, h))


_BANK_CUT = _bank_cut(64, 2048, 1536)           # GLM-4.7-Flash's
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
          "u32": 4, "f32": 4}


@pytest.mark.parametrize("program, temp_mib", [
    ("step", 64), ("fill128", 64), ("fill512", 512)])
def test_engine_programs_move_no_byte_twice(chip, program, temp_mib):
    """The engine's decode step and its 128 and 512 chunk fills at the
    Mistral cells' sizes, from abstract arguments: the pools ride whole
    in the layer scan's carry and q/k/v are read in the layout they are
    stored in, so the compiled program holds no temporaries to speak of
    and copies no large array.

    Before ISSUE 31 the three read ``temp_size_in_bytes`` 1,880,259,072
    / 2,015,508,480 / 2,547,390,976: both ``[16, 1024, 16, 8, 128]``
    pools copied whole every call (scanned as inputs and outputs), each
    layer's ``[1024, 16, 8, 128]`` pool sliced out of the stack and put
    back, and the stacked ``q_w`` / ``k_w`` / ``v_w`` transposed whole.
    Now 1,032,192 / 387,072 / 269,467,136 (the 512 fill's own float32
    scores, ``[32, 512, 4096]``)."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models.llama import LlamaConfig, stack_block_params
    from paddle_tpu.ops.decode_block import serving_layout
    z = _SERVE
    cfg = LlamaConfig(vocab_size=32768, hidden_size=4096,
                      intermediate_size=14336, num_layers=z["layers"],
                      num_heads=32, num_kv_heads=8,
                      max_position_embeddings=32768, rms_norm_eps=1e-5,
                      rope_theta=1e6, dtype="bfloat16")
    H, V = cfg.hidden_size, cfg.vocab_size
    params = on(chip, {
        "wte": jax.ShapeDtypeStruct((V, H), BF16),
        "head": jax.ShapeDtypeStruct((H, V), BF16),
        "lnf_w": jax.ShapeDtypeStruct((H,), BF16),
        "blocks": jax.eval_shape(lambda: serving_layout(
            stack_block_params(cfg, jax.random.key(0), 1)))})
    # the builders read these of an engine and nothing else: no pools,
    # no weights
    eng = object.__new__(ContinuousBatchingEngine)
    eng.cfg, eng.BS, eng._hybrid, eng._latent, eng.quant_config = \
        cfg, z["page"], False, False, None
    pool = chip((z["layers"], z["pages"], z["page"], cfg.kv_heads,
                 cfg.head_dim))
    i32 = jnp.int32
    if program == "step":
        fn, args = eng._build_step(), (
            chip((z["slots"], z["table"]), i32), chip((z["slots"],), i32),
            chip((z["slots"],), i32))
    else:
        Ts = int(program[4:])
        fn, args = eng._build_chunk_fill(Ts), (
            chip((z["table"],), i32), chip((), i32), chip((Ts,), i32),
            chip((), i32))
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(
        params, pool, pool, *args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes \
        < temp_mib * _MIB
    text = compiled.as_text()
    copied = [(dt, dims) for dt, dims in _COPY.findall(text)
              if _BYTES.get(dt, 4) * math.prod(int(d) for d in dims.split(",")
                                        if d) >= 32 * _MIB]
    assert not copied, f"large arrays copied: {copied}"
    # no layer's pool is sliced out of the stack (or put back into it)
    assert "bf16[1024,16,8,128]" not in text
    assert "bf16[16384,16,8,128]" in text


# the GLM-4.7-Flash serve cell's engine (benchmark/configs: layer 0 and
# six expert layers at the published widths, 64 slots, 32768 pages of
# 16, a table of 512)
_LATENT = dict(layers=7, slots=64, pages=32768, page=16, table=512)


@pytest.mark.parametrize("program, temp_mib", [
    ("step", 256), ("fill512", 256), ("fill2048", 512)])
def test_latent_engine_programs_fit_and_copy_no_pool(chip, program,
                                                     temp_mib):
    """The latent-attention engine's decode step and its 512 and 2048
    chunk fills at the benchmark cell's sizes, from abstract arguments:
    9.06 GB of weights and a 4.70 GB latent pool ride through whole, so
    a program's temporaries stay in the megabytes, the whole fits the
    chip's 15.75 GiB, and nothing pool- or bank-sized is copied.

    What this holds (ISSUE 34's compiles): a pool row of 576 made every
    program copy the pool into the padded layout and back (4.4 GB each
    way: ``ops/mla.py``, ``pool_width``); an expert layer under
    ``lax.cond``, or handed a layer's bank as an operand, copied a
    layer's three banks (1.2 GB) or the whole stacks (2 x 2.4 GB) every
    call.  The fills' grouped matmuls (ISSUE 35) are handed the stacks
    whole and find the layer's experts by index: no bank is the result
    of a slice in a fill's program, nor (ISSUE 37) in the step's.  Now
    82 MB / 9 MB / 180 MB of temporaries."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models.glm_moe_lite import (glm_4_7_flash,
                                                init_glm_moe_lite_params)
    z = _LATENT
    cfg = glm_4_7_flash(num_hidden_layers=z["layers"])
    params = on(chip, jax.eval_shape(
        lambda: init_glm_moe_lite_params(cfg, 0)))
    held = sum(math.prod(a.shape) for a in jax.tree.leaves(params))
    assert held == 4530936960
    eng = object.__new__(ContinuousBatchingEngine)
    eng.cfg, eng.BS, eng._hybrid, eng._latent, eng.quant_config = \
        cfg, z["page"], False, True, None
    pool = chip((z["layers"], z["pages"], z["page"], cfg.pool_width))
    i32 = jnp.int32
    if program == "step":
        fn, args = eng._build_step(), (
            chip((z["slots"], z["table"]), i32), chip((z["slots"],), i32),
            chip((z["slots"],), i32))
    else:
        Ts = int(program[4:])
        fn, args = eng._build_chunk_fill(Ts), (
            chip((z["table"],), i32), chip((), i32), chip((Ts,), i32),
            chip((2,), i32), chip((), i32))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pool, *args).compile()
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < temp_mib * _MIB
    total = (m.temp_size_in_bytes + m.argument_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes
             + m.generated_code_size_in_bytes)
    assert total < 14.2e9, total            # of the chip's 16.9e9
    text = compiled.as_text()
    copied = [(dt, dims) for dt, dims in _COPY.findall(text)
              if _BYTES.get(dt, 4) * math.prod(int(d) for d in dims.split(",")
                                        if d) >= 64 * _MIB]
    assert not copied, f"large arrays copied: {copied}"
    # the pool rides as ONE pool of all layers' pages, in place
    assert "bf16[229376,16,640]" in text
    assert "bf16[32768,16,640]" not in text
    # the grouped matmuls, the step's too, find the layer's experts in
    # the stacks
    assert not _BANK_CUT.search(text), "a bank was cut out"
    assert text.count("moe_grouped_matmul") >= 2


# the Ling-3.0-flash serve cell's engine (benchmark/configs: layer 0 and
# six expert layers at the published widths, 128 of each layer's 512
# experts, a quarter of the vocabulary; 128 slots, 32768 pages of 16 for
# the ONE latent layer, a table of 256)
_LINEAR = dict(layers=7, held=128, vocab=39296, slots=128, pages=32768,
               page=16, table=256)
_LINEAR_BANK_CUT = _bank_cut(128, 2560, 768)    # its held experts


@pytest.mark.parametrize("program, temp_mib", [
    ("step", 256), ("fill128", 64), ("fill512", 128)])
def test_linear_engine_programs_fit_and_copy_no_state(chip, program,
                                                      temp_mib):
    """The engine's decode step and its 128 and 512 chunk fills for a
    model with a recurrent state BESIDE a latent pool, at the benchmark
    cell's sizes, from abstract arguments: 10.34 GB of weights, 1.61 GB
    of KDA state, 57 MB of conv tails and a 0.67 GB latent pool ride
    through whole, so the whole fits the chip's 15.75 GiB with room for
    temporaries and nothing pool-, state- or bank-sized is copied.

    What this holds (ISSUE 36's compiles): conv tails held ``[.., C, 3]``
    or ``[.., 3, C]`` were copied whole into a padded layout and back by
    every fill (57 MB each way), and as ``[.., 3 C]`` rows by the step
    until its conv read them as 2-D slices (``ops.ssm.
    causal_conv_step``).  Now 168 MB / 16 MB / 30 MB of temporaries
    (ISSUE 37: the step and the 128 fill take the grouped form too)."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models import ling_linear as zoo
    z = _LINEAR
    cfg = zoo.ling_3_0_flash(
        num_hidden_layers=z["layers"], first_k_dense_replace=1,
        experts_held=z["held"], vocab_size=z["vocab"])
    params = on(chip, jax.eval_shape(
        lambda: zoo.init_ling_linear_params(cfg, 0)))
    held = sum(math.prod(a.shape) for a in jax.tree.leaves(params))
    assert held == 5169390784
    eng = object.__new__(ContinuousBatchingEngine)
    eng.cfg, eng.BS, eng._hybrid, eng._latent, eng.quant_config = \
        cfg, z["page"], True, True, None
    pool = chip((cfg.num_attention_layers, z["pages"], z["page"],
                 cfg.pool_width))
    ssm, conv = on(chip, jax.eval_shape(
        lambda: zoo.init_slot_state(cfg, z["slots"])))
    assert ssm.shape == (6, 128, 32, 128, 128) and ssm.dtype == jnp.float32
    i32 = jnp.int32
    if program == "step":
        fn, args = eng._build_step(), (
            chip((z["slots"], z["table"]), i32), chip((z["slots"],), i32),
            chip((z["slots"],), i32))
    else:
        Ts = int(program[4:])
        fn, args = eng._build_chunk_fill(Ts), (
            chip((z["table"],), i32), chip((), i32), chip((Ts,), i32),
            chip((), i32), chip((2,), i32), chip((), i32))
    compiled = jax.jit(fn, donate_argnums=(1, 2, 3)).lower(
        params, pool, ssm, conv, *args).compile()
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < temp_mib * _MIB
    total = (m.temp_size_in_bytes + m.argument_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes
             + m.generated_code_size_in_bytes)
    assert total < 14.2e9, total            # of the chip's 16.9e9
    text = compiled.as_text()
    # the largest leaf that is no pool, state or bank is a dense layer's
    # [2560, 6144] (31 MB: a run of ONE layer is read out of its stack)
    copied = [(dt, dims) for dt, dims in _COPY.findall(text)
              if _BYTES.get(dt, 4) * math.prod(int(d) for d in dims.split(",")
                                        if d) >= 40 * _MIB]
    assert not copied, f"large arrays copied: {copied}"
    # the state rides whole, in place; the pool as ONE pool of pages
    assert "f32[6,128,32,128,128]" in text
    assert "bf16[32768,16,640]" in text
    if program == "step":
        assert text.count("kda_state_update") >= 3    # a run of KDA layers
    # the grouped matmuls, the step's too, find the layer's held experts
    # in the stacks
    assert not _LINEAR_BANK_CUT.search(text), "a bank was cut out"
    assert text.count("moe_grouped_matmul") >= 2


def test_decode_attention(chip):
    from paddle_tpu.ops.pallas.decode_attention import decode_attention
    cache = chip((8, 2048, 32, 128))
    compile_kernel(
        lambda q, k, v, ln: decode_attention(q, k, v, ln, use_pallas=True),
        chip((8, 32, 128)), cache, cache, chip((8,), jnp.int32))


def test_ssm_state_update(chip):
    """The hybrid's decode-step state update at the published Mamba-2
    widths (128 heads x 64, state 128), 64 slots, 9 layers: in place."""
    from paddle_tpu.ops.pallas.ssm import ssm_state_update_rows
    f32 = jnp.float32
    compiled = compile_kernel(
        ssm_state_update_rows, chip((64, 128, 64)), chip((64, 128), f32),
        chip((128,), f32), chip((64, 1, 128)), chip((64, 1, 128)),
        chip((128,), f32), chip((9, 64, 128, 64, 128), f32),
        chip((), jnp.int32))
    assert "ssm_state_update" in compiled.as_text()


def test_kda_state_update(chip):
    """The linear-attention family's decode-step state update at the
    published KDA widths (32 heads of 128 x 128), 128 slots, 6 layers:
    in place."""
    from paddle_tpu.ops.pallas.kda import kda_state_update_rows
    f32 = jnp.float32
    side = chip((128, 32, 128), f32)
    compiled = compile_kernel(
        kda_state_update_rows, side, side, side, side, chip((128, 32), f32),
        chip((6, 128, 32, 128, 128), f32), chip((), jnp.int32))
    assert "kda_state_update" in compiled.as_text()


@pytest.mark.parametrize("tokens, E, router, k, h, f", [
    pytest.param(512, 64, 64, 4, 2048, 1536, id="512"),
    pytest.param(2048, 64, 64, 4, 2048, 1536, id="2048"),
    pytest.param(64, 64, 64, 4, 2048, 1536, id="step-64-of-64-held"),
    pytest.param(128, 128, 512, 8, 2560, 768, id="step-128-of-512-held"),
])
def test_moe_grouped_matmul(chip, tokens, E, router, k, h, f):
    """The expert layer alone (``parallel/moe.py``'s grouped form: the
    sort, the two ``moe_grouped_matmul`` calls, the gathers), its bank
    one layer of a 6-layer stack that the kernels are handed whole: no
    bank is cut out of it.  At GLM-4.7-Flash's widths the cell's two
    chunk sizes and its 64-row decode step; at Ling-3.0-flash's the
    128-row step (and 128 fill) of a rank that holds 128 of the
    router's 512 experts."""
    from paddle_tpu.parallel.moe import moe_swiglu_ffn_routed
    up, down = chip((6, E, h, f)), chip((6, E, f, h))
    compiled = compile_kernel(
        lambda x, w, idx, g, u, d, i: moe_swiglu_ffn_routed(
            x, w, idx, g, u, d, layer=i, router_experts=router),
        chip((tokens, h)), chip((tokens, k), jnp.float32),
        chip((tokens, k), jnp.int32), up, up, down, chip((), jnp.int32),
        kernels=2)
    text = compiled.as_text()
    assert text.count("moe_grouped_matmul") >= 2
    assert not _bank_cut(E, h, f).search(text), \
        "a bank was cut out of the stack"
    # 0.7 MB of temporaries for a step, 4 for the 512 fill, 48 for the
    # 2048 fill
    limit = 256 if tokens > 128 else 16
    assert compiled.memory_analysis().temp_size_in_bytes < limit * _MIB


def test_quant_linear_int8(chip):
    from paddle_tpu.ops.pallas.quant_linear import weight_only_matmul
    compile_kernel(weight_only_matmul, chip((8, 4096)),
                   chip((4096, 11008), jnp.int8),
                   chip((11008,), jnp.float32))


# ---------------------------------------------------------------------
# off the main path: kernels the chip refused in the `-m tpu` lane
# (tests/test_pallas_hw.py, PR 22) — strict xfails, so the PR that
# repairs one has to say so here
# ---------------------------------------------------------------------
def _swiglu(chip):
    from paddle_tpu.ops.pallas.fused import swiglu
    x = chip((4096, 11008))
    return swiglu, (x, x)


def _bias_act(chip):
    from paddle_tpu.ops.pallas.fused import fused_bias_act
    return (lambda x, b: fused_bias_act(x, b, "gelu")), \
        (chip((4096, 8192)), chip((8192,)))


def _bias_dropout_residual_ln(chip):
    from paddle_tpu.ops.pallas.norms import (
        fused_bias_dropout_residual_layer_norm)
    x, v = chip((1024, 4096)), chip((4096,))
    return (lambda x, r, b, w: fused_bias_dropout_residual_layer_norm(
        x, r, b, w, b, dropout_rate=0.0)), (x, x, v, v)


def _int4_grouped(chip):
    from paddle_tpu.ops.pallas.quant_linear import weight_only_matmul_int4
    return (lambda x, w, s: weight_only_matmul_int4(x, w, s,
                                                    group_size=64)), \
        (chip((1024, 4096)), chip((2048, 4096), jnp.int8),
         chip((64, 4096), jnp.float32))


@pytest.mark.parametrize("case", [
    pytest.param(_swiglu, id="swiglu[4096x11008]:scoped-vmem"),
    pytest.param(_bias_act, id="fused_bias_act[4096x8192]:scoped-vmem"),
    pytest.param(_bias_dropout_residual_ln,
                 id="bias_dropout_residual_ln[1024x4096]:scoped-vmem"),
    pytest.param(_int4_grouped,
                 id="weight_only_int4[g64]:unaligned-scale-load"),
])
@pytest.mark.xfail(strict=True, reason="refused by the v5e compiler; "
                   "not on the main path — queued in ROADMAP S2")
def test_known_refusals_off_the_main_path(chip, case):
    fn, args = case(chip)
    compile_kernel(fn, *args)
