"""Backend-dispatch layer over flash attention (ops/pallas/flash_backends).

Mirrors the reference's per-shape attention-backend dispatch
(python/paddle/nn/functional/flash_attention.py:976); numeric ground truth
is dense softmax attention.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_backends as fb


def _dense_ref(q, k, v, scale, causal):
    Hq, Hkv = q.shape[2], k.shape[2]
    if Hq != Hkv:
        k = jnp.repeat(k, Hq // Hkv, axis=2)
        v = jnp.repeat(v, Hq // Hkv, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        m = jnp.tril(jnp.ones((sq, sk), bool), sk - sq)
        logits = jnp.where(m, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _qkv(b, sq, sk, hq, hkv, d, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, sq, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, sk, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, sk, hkv, d)), jnp.float32)
    return q, k, v


def test_interpret_mode_restricts_to_ours():
    cands = fb.available_backends((2, 256, 4, 64), (2, 256, 4, 64), True, False,
                                  False, interpret=True)
    assert cands == ("ours",)


def test_backend_order_tpu_signature():
    cands = fb.available_backends((2, 1024, 12, 64), (2, 1024, 12, 64), True,
                                  False, False, interpret=False)
    assert cands[0] == "splash" and cands[-1] == "ours"
    # bias excludes splash
    cands = fb.available_backends((2, 1024, 12, 64), (2, 1024, 12, 64), True,
                                  False, True, interpret=False)
    assert "splash" not in cands and "jax_flash" in cands
    # misaligned seq -> only ours
    cands = fb.available_backends((2, 1000, 12, 64), (2, 1000, 12, 64), True,
                                  False, False, interpret=False)
    assert cands == ("ours",)


def test_tuned_flash_dispatches_ours_on_cpu():
    q, k, v = _qkv(1, 128, 128, 2, 2, 64)
    out = fb.tuned_flash(q, k, v, causal=True)
    ref = _dense_ref(q, k, v, 1.0 / math.sqrt(64), True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


# (Sq, Sk, D, group): the train cell's shape first, then the interpret
# test's, a GPT-like MHA, a long row, Sq != Sk, the smallest legal, and a
# row long enough that the backward's two kernels run apart
@pytest.mark.parametrize("sq,sk,d,group", [
    (2048, 2048, 128, 4), (256, 256, 128, 1), (1024, 1024, 64, 1),
    (4096, 4096, 128, 4), (384, 2048, 128, 4), (128, 128, 64, 1),
    (8192, 8192, 128, 4)])
def test_splash_block_sizes_fit_the_shape(sq, sk, d, group):
    """No kernel runs: every tile divides its length and is a multiple
    of the 128 lanes, the compute tile divides the memory tile, and the
    backward has its tiles (the library raises without them)."""
    bs = fb.splash_block_sizes(sq, sk, d, group)
    q_tiles = [bs.block_q, bs.block_q_dkv]
    kv_tiles = [bs.block_kv, bs.block_kv_compute, bs.block_kv_dkv,
                bs.block_kv_dkv_compute]
    if not bs.use_fused_bwd_kernel:
        q_tiles.append(bs.block_q_dq)
        kv_tiles.append(bs.block_kv_dq)
    assert all(t % 128 == 0 and sq % t == 0 for t in q_tiles), bs
    assert all(t % 128 == 0 and sk % t == 0 for t in kv_tiles), bs
    assert bs.block_kv % bs.block_kv_compute == 0
    assert bs.block_kv_dkv % bs.block_kv_dkv_compute == 0
    assert bs.has_backward_blocks
    if (sq, sk) == (2048, 2048):
        # the cell's shape runs at none of the library's placeholders
        assert 128 not in q_tiles + kv_tiles, bs
    # one partial dq per kv tile: fused only while they are few
    assert bs.use_fused_bwd_kernel == (
        sk // bs.block_kv_dkv <= fb._SPLASH_FUSED_BWD_MAX_PARTIALS)


@pytest.mark.parametrize("d,itemsize,rows", [
    (64, 2, 1024), (128, 2, 1024), (256, 2, 512), (128, 4, 512),
    (256, 4, 256)])
def test_splash_tiles_shrink_with_row_bytes(d, itemsize, rows):
    """Wider rows get shorter tiles (VMEM is what a tile costs): 1024
    rows of 256 float32 are what the v5e compiler refuses."""
    bs = fb.splash_block_sizes(2048, 2048, d, 1, itemsize)
    assert (bs.block_q, bs.block_kv, bs.block_q_dkv, bs.block_kv_dkv) \
        == (rows,) * 4
    assert bs.block_kv_compute == bs.block_kv_dkv_compute == min(rows, 512)


@pytest.mark.slow
@pytest.mark.parametrize("hq,hkv", [(2, 2), (4, 2)])
def test_splash_backend_interpret_mha(hq, hkv):
    """Equal heads (the MHA form) and grouped heads (the MQA form over
    groups of 2), forward and ``jax.grad``, against dense attention."""
    q, k, v = _qkv(1, 256, 256, hq, hkv, 128)
    scale = 1.0 / math.sqrt(128)
    out = fb.run_backend("splash", q, k, v, scale, True)
    ref = _dense_ref(q, k, v, scale, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)
    w = jnp.asarray(np.random.default_rng(1).standard_normal(ref.shape),
                    jnp.float32)
    grads = [jax.grad(lambda a, b, c: jnp.sum(f(a, b, c) * w),
                      argnums=(0, 1, 2))(q, k, v)
             for f in (lambda a, b, c: fb.run_backend("splash", a, b, c,
                                                      scale, True),
                       lambda a, b, c: _dense_ref(a, b, c, scale, True))]
    for got, want in zip(*grads):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-2, rtol=5e-2)


@pytest.mark.slow
def test_jax_flash_backend_interpret():
    from jax.experimental.pallas import tpu as pltpu
    q, k, v = _qkv(1, 256, 256, 2, 2, 128)
    with pltpu.force_tpu_interpret_mode():
        out = fb.run_backend("jax_flash", q, k, v,
                             1.0 / math.sqrt(128), True)
    ref = _dense_ref(q, k, v, 1.0 / math.sqrt(128), True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.tpu
@pytest.mark.usefixtures("tpu")
@pytest.mark.parametrize("backend", ["ours", "jax_flash", "splash"])
@pytest.mark.parametrize("hq,hkv", [(8, 8), (8, 2)])
def test_backends_match_dense_on_tpu(backend, hq, hkv):
    q, k, v = _qkv(2, 512, 512, hq, hkv, 64)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    scale = 1.0 / math.sqrt(64)
    out = fb.run_backend(backend, q, k, v, scale, True)
    ref = _dense_ref(q, k, v, scale, True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2, rtol=3e-2)


@pytest.mark.tpu
@pytest.mark.usefixtures("tpu")
@pytest.mark.parametrize("backend", ["ours", "jax_flash", "splash"])
def test_backend_grads_finite_on_tpu(backend):
    q, k, v = _qkv(1, 512, 512, 4, 4, 64)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))

    def loss(qq, kk, vv):
        o = fb.run_backend(backend, qq, kk, vv, 0.125, True)
        return jnp.sum(o.astype(jnp.float32))

    gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in (gq, gk, gv):
        assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
