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


@pytest.mark.slow
def test_splash_backend_interpret_mha():
    q, k, v = _qkv(1, 256, 256, 2, 2, 128)
    out = fb.run_backend("splash", q, k, v, 1.0 / math.sqrt(128), True)
    ref = _dense_ref(q, k, v, 1.0 / math.sqrt(128), True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)


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
