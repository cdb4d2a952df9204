"""Optimized-variant equivalence: flash attention and local MoE dispatch
must match the baseline paths (f32-exact for flash; routing-exact for MoE),
and the flash kernel must sweep shapes/dtypes against the oracle."""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.distributed import sharding as shd
from repro.kernels.flash_attention import flash_attention
from repro.models import LM
from repro.models.attention import attn_defs, attn_forward
from repro.models.params import materialize


def _ref(q, k, v, causal, window, softcap, scale):
    B, H, Lq, D = q.shape
    Hkv = k.shape[1]
    g = H // Hkv
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qp = jnp.arange(Lq)[:, None]
    kp = jnp.arange(k.shape[2])[None, :]
    m = jnp.ones((Lq, k.shape[2]), bool)
    if causal:
        m &= qp >= kp
    if window:
        m &= (qp - kp) < window
    s = jnp.where(m[None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      vv.astype(jnp.float32)).astype(q.dtype)


@pytest.mark.parametrize("causal,window,softcap",
                         [(True, None, None), (False, None, None),
                          (True, 48, None), (True, None, 30.0)])
@pytest.mark.parametrize("gqa", [(4, 4), (4, 2), (4, 1)])
def test_flash_kernel_sweep(causal, window, softcap, gqa):
    H, Hkv = gqa
    rng = np.random.default_rng(0)
    B, L, D = 2, 128, 32
    q = jnp.asarray(rng.standard_normal((B, H, L, D)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Hkv, L, D)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Hkv, L, D)) * 0.5, jnp.float32)
    scale = 1 / math.sqrt(D)
    out = flash_attention(q, k, v, scale, causal, window, softcap, 64, 64,
                          True)
    ref = _ref(q, k, v, causal, window, softcap, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_flash_kernel_grads():
    rng = np.random.default_rng(1)
    B, H, Hkv, L, D = 1, 4, 2, 128, 32
    q = jnp.asarray(rng.standard_normal((B, H, L, D)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Hkv, L, D)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Hkv, L, D)) * 0.5, jnp.float32)
    scale = 1 / math.sqrt(D)

    def lf(q, k, v):
        return jnp.sum(flash_attention(q, k, v, scale, True, None, None,
                                       64, 64, True) ** 2)

    def lr(q, k, v):
        return jnp.sum(_ref(q, k, v, True, None, None, scale) ** 2)

    gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_flash_model_path_matches_baseline_f32(monkeypatch):
    # the model path compiles the kernel for the TPU; on CPU run the same
    # kernel through the Pallas interpreter
    from repro.kernels import flash_attention as fa
    monkeypatch.setattr(fa, "flash_attention",
                        functools.partial(fa.flash_attention,
                                          interpret=True))
    p = materialize(attn_defs(64, 4, 2, 16, qkv_bias=True),
                    jax.random.key(0))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 32, 64)) * 0.5, jnp.float32)
    kw = dict(n_heads=4, n_kv=2, head_dim=16, causal=True)
    y0 = attn_forward(p, x, **kw)
    yf = attn_forward(p, x, flash=True, flash_block=16, **kw)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(yf),
                               rtol=1e-4, atol=1e-4)


def test_flash_falls_back_on_indivisible_length():
    p = materialize(attn_defs(64, 4, 2, 16), jax.random.key(0))
    x = jnp.ones((1, 37, 64), jnp.float32) * 0.1
    y = attn_forward(p, x, n_heads=4, n_kv=2, head_dim=16, causal=True,
                     flash=True, flash_block=16)      # 37 % 16 != 0
    assert y.shape == (1, 37, 64)


def test_moe_local_dispatch_matches_gather():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_smoke_config("deepseek-moe-16b")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
    cfgl = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, dispatch="local",
                                     capacity_factor=16.0))
    m0, ml = LM(cfg), LM(cfgl)
    params = m0.init(jax.random.key(2))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 32)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (2, 32)),
                                   jnp.int32)}
    with shd.use_sharding(mesh, shd.DEFAULT_RULES):
        l0, _ = jax.jit(m0.loss)(params, batch)
        ll, _ = jax.jit(ml.loss)(params, batch)
    assert abs(float(l0) - float(ll)) < 1e-3
