"""Fused attention tests — mirrors the reference's
tests/L0/run_contrib (self/encdec multihead attn vs reference math) plus the
flash-kernel interpret-vs-fallback oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import apex_tpu.nn as nn
from apex_tpu.contrib.multihead_attn import (
    EncdecMultiheadAttn, SelfMultiheadAttn, flash_attention, self_attn_func)
from apex_tpu.contrib.multihead_attn.attn_funcs import attention_reference
from apex_tpu.ops.pallas import force_mode


def _qkv(rng, b=2, h=4, sq=48, sk=72, d=32, dtype=jnp.float32):
    q = jnp.asarray(rng.standard_normal((b, h, sq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, h, sk, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, h, sk, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_interpret_matches_reference(rng, causal):
    q, k, v = _qkv(rng, sq=48, sk=48)
    scale = 1.0 / np.sqrt(q.shape[-1])

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(q, k, v, causal=causal)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(
            attention_reference(q, k, v, None, causal, scale)))

    with force_mode("interpret"):
        out = flash_attention(q, k, v, causal=causal)
        g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    ref = attention_reference(q, k, v, None, causal, scale)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    for a, r in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-3, atol=1e-4)


def test_flash_padding_and_bias(rng):
    # uneven seq lens exercise block padding; key-padding bias masks keys
    q, k, v = _qkv(rng, b=2, h=2, sq=40, sk=56, d=16)
    kp = np.zeros((2, 56), bool)
    kp[0, 50:] = True
    kp[1, 20:30] = True
    bias = jnp.where(jnp.asarray(kp), -1e30, 0.0)[:, None, :]
    scale = 0.25
    with force_mode("interpret"):
        out = flash_attention(q, k, v, bias=bias, scale=scale)
    ref = attention_reference(q, k, v, bias, False, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_self_attn_func_fast_matches_default(rng):
    t, b, e, h = 24, 3, 32, 4
    x = jnp.asarray(rng.standard_normal((t, b, e)), jnp.float32)
    wi = jnp.asarray(rng.standard_normal((3 * e, e)) * 0.1, jnp.float32)
    wo = jnp.asarray(rng.standard_normal((e, e)) * 0.1, jnp.float32)
    scale = (e // h) ** -0.5
    out_default = self_attn_func(False, False, h, scale, x, wi, wo,
                                 use_flash=False)
    with force_mode("interpret"):
        out_fast = self_attn_func(False, False, h, scale, x, wi, wo,
                                  use_flash=True)
    np.testing.assert_allclose(np.asarray(out_fast), np.asarray(out_default),
                               rtol=1e-4, atol=1e-5)


def test_self_attn_module_masks(rng):
    nn.manual_seed(0)
    t, b, e = 16, 2, 32
    m = SelfMultiheadAttn(e, 4, dropout=0.0, impl="default").eval()
    x = jnp.asarray(rng.standard_normal((t, b, e)), jnp.float32)
    out, w = m(x, x, x)
    assert w is None
    assert out.shape == (t, b, e)
    # time mask upper-triangular: masked queries can't see future keys
    tri = np.triu(np.ones((t, t), bool), 1)
    out_m, _ = m(x, x, x, attn_mask=jnp.asarray(tri))
    assert out_m.shape == (t, b, e)
    with pytest.raises(AssertionError):
        m(x, x, x, key_padding_mask=jnp.zeros((b, t), bool),
          attn_mask=jnp.asarray(tri))


def test_norm_add_residual(rng):
    nn.manual_seed(0)
    t, b, e = 8, 2, 16
    m = SelfMultiheadAttn(e, 2, dropout=0.0, include_norm_add=True,
                          impl="default").eval()
    # zero projection weights → attention contributes 0; output == residual
    m.out_proj_weight.data = jnp.zeros_like(m.out_proj_weight.data)
    x = jnp.asarray(rng.standard_normal((t, b, e)), jnp.float32)
    out, _ = m(x, x, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), rtol=1e-6)


def test_encdec_module(rng):
    nn.manual_seed(0)
    tq, tk, b, e = 12, 20, 2, 32
    m = EncdecMultiheadAttn(e, 4, dropout=0.0, impl="default").eval()
    q = jnp.asarray(rng.standard_normal((tq, b, e)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((tk, b, e)), jnp.float32)
    out, _ = m(q, kv, kv)
    assert out.shape == (tq, b, e)
    kp = np.zeros((b, tk), bool)
    kp[:, 15:] = True
    out_m, _ = m(q, kv, kv, key_padding_mask=jnp.asarray(kp))
    assert np.isfinite(np.asarray(out_m)).all()


def test_dropout_path_runs(rng):
    nn.manual_seed(0)
    t, b, e = 8, 2, 16
    m = SelfMultiheadAttn(e, 2, dropout=0.5, impl="fast")
    x = jnp.asarray(rng.standard_normal((t, b, e)), jnp.float32)
    out, _ = m(x, x, x)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("impl", ["default", "fast"])
def test_causal_flag_matches_explicit_time_mask(rng, impl):
    """SelfMultiheadAttn(causal=True) must equal the same module fed an
    explicit upper-triangle time mask (the in-kernel triangle vs the
    materialized O(S^2) operand)."""
    t, b, e = 16, 2, 32
    nn.manual_seed(9)
    m_causal = SelfMultiheadAttn(e, 4, dropout=0.0, impl=impl,
                                 causal=True).eval()
    nn.manual_seed(9)
    m_masked = SelfMultiheadAttn(e, 4, dropout=0.0, impl=impl).eval()
    x = jnp.asarray(rng.standard_normal((t, b, e)), jnp.float32)
    tri = np.triu(np.ones((t, t), bool), k=1)  # True = excluded
    with force_mode("interpret"):
        out_c, _ = m_causal(x)
        out_m, _ = m_masked(x, attn_mask=jnp.asarray(tri))
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_m),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_inkernel_dropout_matches_reference(rng, causal):
    """In-kernel dropout (the reference's fused-dropout feature,
    apex/contrib/csrc/multihead_attn/dropout.cuh) must agree with the
    XLA oracle applying the SAME counter-based hash mask
    (dropout_keep_reference) — fwd and grads, across block boundaries
    (sq 320 > bq 256 forces a multi-q-block grid)."""
    q, k, v = _qkv(rng, b=1, h=2, sq=320, sk=320, d=16)
    scale = 1.0 / np.sqrt(q.shape[-1])
    seed = jnp.int32(424242)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       dropout_p=0.3,
                                       dropout_seed=seed) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(
            q, k, v, None, causal, scale, dropout_p=0.3,
            dropout_seed=seed) ** 2)

    with force_mode("interpret"):
        out = flash_attention(q, k, v, causal=causal, dropout_p=0.3,
                              dropout_seed=seed)
        g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    ref = attention_reference(q, k, v, None, causal, scale,
                              dropout_p=0.3, dropout_seed=seed)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    for a, r in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-3, atol=1e-4)


def test_flash_dropout_mask_properties(rng):
    """The hash mask is seed-deterministic, seed-sensitive, and drops
    ~p of the positions with inverted scaling on the rest."""
    from apex_tpu.ops.pallas.attention import dropout_keep_reference

    m1 = np.asarray(dropout_keep_reference(4, 64, 64, jnp.int32(7), 0.25))
    m2 = np.asarray(dropout_keep_reference(4, 64, 64, jnp.int32(7), 0.25))
    m3 = np.asarray(dropout_keep_reference(4, 64, 64, jnp.int32(8), 0.25))
    assert (m1 == m2).all()
    assert not (m1 == m3).all()
    assert set(np.unique(m1)).issubset({0.0, np.float32(1.0 / 0.75)})
    drop_frac = (m1 == 0.0).mean()
    assert abs(drop_frac - 0.25) < 0.02
    # distinct heads get distinct masks
    assert not (m1[0] == m1[1]).all()


def test_flash_dropout_zero_p_is_plain_attention(rng):
    q, k, v = _qkv(rng, sq=48, sk=48)
    with force_mode("interpret"):
        a = flash_attention(q, k, v, causal=True)
        b = flash_attention(q, k, v, causal=True, dropout_p=0.0,
                            dropout_seed=jnp.int32(1))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_flash_dropout_requires_seed():
    q = jnp.zeros((1, 1, 8, 8), jnp.float32)
    with pytest.raises(ValueError, match="dropout_seed"):
        flash_attention(q, q, q, dropout_p=0.1)


@pytest.mark.parametrize("shape", [(256, 256), (192, 320)])
def test_flash_causal_block_skip_multi_block(rng, shape, monkeypatch):
    """The causal block-skip must be exercised across MANY q/k blocks
    (the default 256/512 blocks make small tests single-block, where
    skipping never triggers): shrink blocks to 64x64 so the grid has
    fully-masked, diagonal, and fully-valid blocks, and assert fwd+bwd
    against the reference — skipped blocks contribute exactly p=0, so
    agreement must be as tight as the unskipped kernel's."""
    from apex_tpu.ops.pallas import attention as A

    monkeypatch.setattr(A, "_block_sizes", lambda *shape: (64, 64))
    sq, sk = shape
    q, k, v = _qkv(rng, sq=sq, sk=sk, d=32)
    scale = 1.0 / np.sqrt(q.shape[-1])

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(q, k, v, causal=True)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(
            attention_reference(q, k, v, None, True, scale)))

    with force_mode("interpret"):
        out = flash_attention(q, k, v, causal=True)
        g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    ref = attention_reference(q, k, v, None, True, scale)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    for a, r in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-3, atol=1e-4)
