"""Sequence-parallel attention (ring + Ulysses) vs single-device oracle.

Oracle: attention_reference (full-softmax jnp attention) on the gathered
sequence.  The ring/Ulysses paths run under shard_map on the 8-device CPU
mesh with the sequence axis sharded — the same pattern the TPU deployment
uses over ICI.  Gradients are checked through jax.grad to exercise the
custom ring backward (rotating dk/dv accumulators).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu.contrib.multihead_attn.attn_funcs import attention_reference
from apex_tpu.parallel import ring_attention, ulysses_attention

B, H, S, D = 2, 4, 64, 16


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("sp",))


def _inputs(rng, dtype=jnp.float32):
    q, k, v = (jnp.asarray(rng.standard_normal((B, H, S, D)), dtype)
               for _ in range(3))
    return q, k, v


def _run_sharded(fn, mesh, q, k, v):
    shard = jax.shard_map(fn, mesh=mesh, in_specs=P(None, None, "sp", None),
                          out_specs=P(None, None, "sp", None),
                          check_vma=False)
    return jax.jit(shard)(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("n", [4, 8])
def test_ring_forward_matches_reference(rng, causal, n):
    mesh = _mesh(n)
    q, k, v = _inputs(rng)
    scale = 1.0 / np.sqrt(D)
    ref = attention_reference(q, k, v, None, causal, scale)
    out = _run_sharded(
        functools.partial(ring_attention, axis_name="sp", causal=causal),
        mesh, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_grads_match_reference(rng, causal):
    mesh = _mesh(4)
    q, k, v = _inputs(rng)
    w = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    scale = 1.0 / np.sqrt(D)

    def ref_loss(q, k, v):
        return jnp.sum(attention_reference(q, k, v, None, causal, scale) * w)

    def ring_loss(q, k, v):
        fn = functools.partial(ring_attention, axis_name="sp", causal=causal)
        shard = jax.shard_map(fn, mesh=mesh,
                              in_specs=P(None, None, "sp", None),
                              out_specs=P(None, None, "sp", None),
                              check_vma=False)
        return jnp.sum(shard(q, k, v) * w)

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_reference(rng, causal):
    mesh = _mesh(4)  # H=4 heads divisible by 4
    q, k, v = _inputs(rng)
    scale = 1.0 / np.sqrt(D)
    ref = attention_reference(q, k, v, None, causal, scale)
    out = _run_sharded(
        functools.partial(ulysses_attention, axis_name="sp", causal=causal),
        mesh, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_grads(rng):
    mesh = _mesh(4)
    q, k, v = _inputs(rng)
    w = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    scale = 1.0 / np.sqrt(D)

    def ref_loss(q, k, v):
        return jnp.sum(attention_reference(q, k, v, None, True, scale) * w)

    def uly_loss(q, k, v):
        fn = functools.partial(ulysses_attention, axis_name="sp",
                               causal=True)
        shard = jax.shard_map(fn, mesh=mesh,
                              in_specs=P(None, None, "sp", None),
                              out_specs=P(None, None, "sp", None),
                              check_vma=False)
        return jnp.sum(shard(q, k, v) * w)

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    g_uly = jax.jit(jax.grad(uly_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_uly, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_pallas_interpret_chunks(rng, causal):
    """Ring with the actual Pallas flash kernels (interpreted) per chunk."""
    from apex_tpu.ops.pallas import force_mode
    mesh = _mesh(4)
    q, k, v = _inputs(rng)
    scale = 1.0 / np.sqrt(D)
    ref = attention_reference(q, k, v, None, causal, scale)
    with force_mode("interpret"):
        out = _run_sharded(
            functools.partial(ring_attention, axis_name="sp", causal=causal),
            mesh, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_cross_attention_uneven_sq_sk(rng, causal):
    """Sq_local != Sk_local (cross attention): offset math idx*sq vs src*sk."""
    mesh = _mesh(4)
    sq, sk = 32, 64
    q = jnp.asarray(rng.standard_normal((B, H, sq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, sk, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, sk, D)), jnp.float32)
    scale = 1.0 / np.sqrt(D)
    ref = attention_reference(q, k, v, None, causal, scale)
    out = _run_sharded(
        functools.partial(ring_attention, axis_name="sp", causal=causal),
        mesh, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_bf16_tolerance(rng):
    """Ring attention with bf16 inputs stays close to the f32 oracle."""
    mesh = _mesh(8)
    q, k, v = _inputs(rng, jnp.bfloat16)
    scale = 1.0 / np.sqrt(D)
    ref = attention_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), None, True, scale)
    out = _run_sharded(
        functools.partial(ring_attention, axis_name="sp", causal=True),
        mesh, q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=0.05, atol=0.05)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_pallas_interpret_grads(rng, causal):
    """Gradients with the Pallas flash kernels (interpreted) per chunk:
    covers the _ring_vjp_bwd -> flash_attention_bwd path (global lse/out,
    rotating dk/dv accumulators) that the jnp fallback tests miss."""
    from apex_tpu.ops.pallas import force_mode
    mesh = _mesh(4)
    q, k, v = _inputs(rng)
    w = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    scale = 1.0 / np.sqrt(D)

    def ref_loss(q, k, v):
        return jnp.sum(attention_reference(q, k, v, None, causal, scale) * w)

    def ring_loss(q, k, v):
        fn = functools.partial(ring_attention, axis_name="sp", causal=causal)
        shard = jax.shard_map(fn, mesh=mesh,
                              in_specs=P(None, None, "sp", None),
                              out_specs=P(None, None, "sp", None),
                              check_vma=False)
        return jnp.sum(shard(q, k, v) * w)

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    with force_mode("interpret"):
        g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_fori_loop_path(rng, causal, monkeypatch):
    """Large-ring fallback: with UNROLL_LIMIT forced to 0 the fwd and bwd
    ring loops run as lax.fori_loop (O(1) HLO per pass) and must match the
    reference exactly like the unrolled path does — with causal masking
    (the only live axis-index consumer in the ring body) on and off."""
    import importlib
    ra_mod = importlib.import_module("apex_tpu.parallel.ring_attention")
    monkeypatch.setattr(ra_mod, "UNROLL_LIMIT", 0)
    mesh = _mesh(8)
    q, k, v = _inputs(rng)
    w = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    scale = 1.0 / np.sqrt(D)
    ref = attention_reference(q, k, v, None, causal, scale)
    out = _run_sharded(
        functools.partial(ring_attention, axis_name="sp", causal=causal),
        mesh, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def ref_loss(q, k, v):
        return jnp.sum(attention_reference(q, k, v, None, causal, scale) * w)

    def ring_loss(q, k, v):
        fn = functools.partial(ring_attention, axis_name="sp", causal=causal)
        shard = jax.shard_map(fn, mesh=mesh,
                              in_specs=P(None, None, "sp", None),
                              out_specs=P(None, None, "sp", None),
                              check_vma=False)
        return jnp.sum(shard(q, k, v) * w)

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-5)


def test_ring_gqa_matches_expanded(rng):
    """KVH-wide ring (GQA: chunks rotate un-expanded, H/KVH x fewer ICI
    bytes) equals the ring over pre-repeated K/V — values and gradients."""
    import functools
    from jax.sharding import Mesh, PartitionSpec as P

    b, h, kvh, s, d = 2, 8, 2, 32, 16
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, kvh, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, kvh, s, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)

    def run(q, k, v, w, expand_first):
        def f(q_l, k_l, v_l, w_l):
            kk, vv = k_l, v_l
            if expand_first:
                kk = jnp.repeat(k_l, h // kvh, axis=1)
                vv = jnp.repeat(v_l, h // kvh, axis=1)
            out = ring_attention(q_l, kk, vv, "sp", causal=True)
            return jax.lax.psum(jnp.sum(out * w_l), "sp")
        shard = jax.shard_map(
            f, mesh=mesh,
            in_specs=(P(None, None, "sp"), P(None, None, "sp"),
                      P(None, None, "sp"), P(None, None, "sp")),
            out_specs=P(), check_vma=False)
        loss, grads = jax.value_and_grad(
            lambda q, k, v: shard(q, k, v, w), argnums=(0, 1, 2))(q, k, v)
        return loss, grads

    l_g, g_g = jax.jit(functools.partial(run, expand_first=False))(
        q, k, v, w)
    l_e, g_e = jax.jit(functools.partial(run, expand_first=True))(
        q, k, v, w)
    np.testing.assert_allclose(float(l_g), float(l_e), rtol=1e-5)
    for a, bb in zip(g_g, g_e):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_dropout_bit_consistent_with_single_device(rng, causal):
    """Ring attention with dropout: the hash mask is a function of GLOBAL
    (seed, head, row, col), so the 4-way sequence-sharded result equals
    the single-device dropped attention under the same seed — sequence
    parallelism does not change which probabilities drop.  Gradients
    exercise the dropped ring backward (dk/dv accumulators rotating
    through dropped chunks)."""
    mesh = _mesh(4)
    q, k, v = _inputs(rng)
    scale = 1.0 / np.sqrt(D)
    seed = jnp.int32(90210)
    p = 0.3

    ref = attention_reference(q, k, v, None, causal, scale,
                              dropout_p=p, dropout_seed=seed)
    out = _run_sharded(
        functools.partial(ring_attention, axis_name="sp", causal=causal,
                          dropout_p=p, dropout_seed=seed),
        mesh, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss_ring(q, k, v):
        shard = jax.shard_map(
            functools.partial(ring_attention, axis_name="sp",
                              causal=causal, dropout_p=p,
                              dropout_seed=seed),
            mesh=mesh, in_specs=P(None, None, "sp", None),
            out_specs=P(None, None, "sp", None), check_vma=False)
        return jnp.sum(shard(q, k, v).astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(
            q, k, v, None, causal, scale, dropout_p=p,
            dropout_seed=seed).astype(jnp.float32) ** 2)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)


def test_ulysses_dropout_runs_and_decorrelates(rng):
    """Ulysses dropout: per-head-shard streams — runs, is finite, differs
    from the dropout-free output, and is deterministic per seed."""
    mesh = _mesh(4)
    q, k, v = _inputs(rng)
    seed = jnp.int32(7)

    def run(p, s):
        return _run_sharded(
            functools.partial(ulysses_attention, axis_name="sp",
                              causal=False, dropout_p=p, dropout_seed=s),
            mesh, q, k, v)

    clean = run(0.0, None)
    a = run(0.4, seed)
    b = run(0.4, seed)
    c = run(0.4, jnp.int32(8))
    assert np.isfinite(np.asarray(a)).all()
    assert not np.allclose(np.asarray(a), np.asarray(clean))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not (np.asarray(a) == np.asarray(c)).all()


def test_ring_dropout_requires_seed():
    q = jnp.zeros((1, 1, 8, 8), jnp.float32)
    with pytest.raises(ValueError, match="dropout_seed"):
        ring_attention(q, q, q, axis_name="sp", dropout_p=0.1)


def test_sp_seed_fold_not_symmetric_with_tp_fold():
    """Round-4 advisor finding: the SP fold must not be the TP fold's
    linear xor with the same constant — a shard-replicated base seed on
    a TP x SP mesh would then give devices with swapped (tp, sp)
    indices identical dropout streams (seed ^ a*C ^ b*C is symmetric).
    The SP fold is multiply-then-avalanche; assert no swap collision
    and no collision with the TP fold itself over a realistic range."""
    from apex_tpu.parallel.ring_attention import _sp_seed_fold

    def tp_fold(seed, idx):   # mirrors attn_funcs._dropout_seed's fold
        return int(jnp.asarray(
            (jnp.uint32(seed) ^ (jnp.uint32(idx)
                                 * jnp.uint32(0x9E3779B1)))
            .astype(jnp.int32)))

    base = 0x12345678
    n = 8
    seen = {}
    for tp in range(n):
        for sp in range(n):
            s = int(_sp_seed_fold(jnp.int32(tp_fold(base, tp)),
                                  jnp.uint32(sp)))
            assert (tp, sp) not in seen
            for (otp, osp), os in seen.items():
                assert s != os, (
                    f"seed collision between (tp={tp},sp={sp}) and "
                    f"(tp={otp},sp={osp})")
            seen[(tp, sp)] = s
