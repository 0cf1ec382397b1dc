"""The kernel tier (apex_tpu.kernels): interpret-mode parity pins for
all three kernels (flash attention incl. causal/window masks and the
ring sp composition, fused multi-tensor updates vs the per-bucket
stacks, the fused vocab chain vs the chunked XLA chain), and the rule
each kernel's module holds for which tier a call takes — a pure
function of the mode and the shapes, tallied where it is applied.

Parity regime: fp32 comparisons are BITWISE but always jit-vs-jit —
XLA CPU contracts mul+add into FMA under jit but not eagerly, so an
eager arm differs from any jitted arm by ~1 ulp while two jitted arms
(the only configuration production runs) agree exactly.
"""
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.contrib.multihead_attn.attn_funcs import (
    attention_reference, flash_attention)
from apex_tpu.contrib.xentropy.chunked import chunked_lm_head_loss
from apex_tpu.kernels import dispatch
from apex_tpu.kernels.dispatch import force_mode
from apex_tpu.kernels.multi_tensor import fused_adam, fused_sgd
from apex_tpu.kernels.vocab_chain import vocab_chain_loss
from apex_tpu.ops import multi_tensor as ops_mt
from apex_tpu.parallel import ring_attention
from apex_tpu.runtime import step_cache

pytestmark = pytest.mark.kernels


def _tensors(rng, shapes, dtype=jnp.float32):
    return [jnp.asarray(rng.standard_normal(s), dtype) for s in shapes]


SHAPES = [(33, 7), (128,), (5, 3, 11), (257,)]


# ---------------------------------------------------------------------------
# fused multi-tensor vs per-bucket: the same op chain, jit-vs-jit
# ---------------------------------------------------------------------------


def _assert_same_to_rounding(ref, got):
    """Equal up to 2 ulps of the result's own dtype.  The fused kernel
    body and the per-bucket loop run the identical elementwise op chain,
    but they are two compilations whose fused-multiply-add contraction
    this test does not control: an FMA rounds once where a multiply
    followed by an add rounds twice, so the last bit may differ (seen:
    1 ulp on 1-2 elements of 231, max abs 2.4e-7 in float32).  The
    inputs are O(1), so the absolute floor is the same 2 ulps at 1.0."""
    assert ref.dtype == got.dtype
    tol = 2 * float(jnp.finfo(ref.dtype).eps)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)



@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("momentum,nesterov,wd,wd_after", [
    (0.9, False, 0.01, False),
    (0.9, True, 0.01, True),
    (0.0, False, 0.0, False),
])
def test_fused_sgd_bitwise_vs_per_bucket(rng, dtype, momentum, nesterov,
                                         wd, wd_after):
    gs = _tensors(rng, SHAPES, dtype)
    ps = _tensors(rng, SHAPES, dtype)
    ms = _tensors(rng, SHAPES, jnp.float32)
    flag = jnp.zeros((), jnp.int32)
    args = (wd, momentum, 0.0, 0.1, nesterov, False, wd_after, 2.0)
    with force_mode("interpret"):
        ref = jax.jit(lambda f, t: ops_mt.sgd_unfused(f, t, *args))(
            flag, [gs, ps, ms])
        got = jax.jit(lambda f, t: fused_sgd(f, t, *args))(
            flag, [gs, ps, ms])
    for r, g in zip(ref[1] + ref[2], got[1] + got[2]):
        _assert_same_to_rounding(r, g)


def test_fused_sgd_depth4_model_copy_bitwise(rng):
    gs = _tensors(rng, SHAPES)
    ps = _tensors(rng, SHAPES)          # fp32 masters
    ms = _tensors(rng, SHAPES)
    model = [p.astype(jnp.bfloat16) for p in ps]
    flag = jnp.zeros((), jnp.int32)
    args = (0.01, 0.9, 0.1, 0.05, False, True, False, 1.0)
    with force_mode("interpret"):
        ref = jax.jit(lambda f, t: ops_mt.sgd_unfused(f, t, *args))(
            flag, [gs, ps, ms, model])
        got = jax.jit(lambda f, t: fused_sgd(f, t, *args))(
            flag, [gs, ps, ms, model])
    assert len(ref) == len(got) == 4
    for lr, lg in zip(ref[1:], got[1:]):
        for r, g in zip(lr, lg):
            assert r.dtype == g.dtype
            np.testing.assert_array_equal(np.asarray(r, np.float32),
                                          np.asarray(g, np.float32))


def test_fused_sgd_noop_flag_skips(rng):
    gs, ps, ms = (_tensors(rng, SHAPES) for _ in range(3))
    flag = jnp.ones((), jnp.int32)
    with force_mode("interpret"):
        got = jax.jit(lambda f, t: fused_sgd(
            f, t, 0.0, 0.9, 0.0, 0.1, False, False, False))(
            flag, [gs, ps, ms])
    for p, np_ in zip(ps, got[1]):
        np.testing.assert_array_equal(np.asarray(p), np.asarray(np_))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode,bias_correction,wd", [
    (0, True, 0.01),        # ADAM_MODE_L2
    (1, True, 0.01),        # decoupled (AdamW)
    (0, False, 0.0),
])
def test_fused_adam_bitwise_vs_per_bucket(rng, dtype, mode,
                                          bias_correction, wd):
    gs = _tensors(rng, SHAPES, dtype)
    ps = _tensors(rng, SHAPES, dtype)
    ms = _tensors(rng, SHAPES, jnp.float32)
    vs = [jnp.abs(t) for t in _tensors(rng, SHAPES, jnp.float32)]
    flag = jnp.zeros((), jnp.int32)
    args = (1e-3, 0.9, 0.999, 1e-8, 7, mode, bias_correction, wd)
    with force_mode("interpret"):
        ref = jax.jit(lambda f, t: ops_mt.adam_unfused(f, t, *args))(
            flag, [gs, ps, ms, vs])
        got = jax.jit(lambda f, t: fused_adam(f, t, *args))(
            flag, [gs, ps, ms, vs])
    for lr, lg in zip(ref[1:], got[1:]):
        for r, g in zip(lr, lg):
            _assert_same_to_rounding(r, g)


# ---------------------------------------------------------------------------
# flash attention parity (incl. masks and the ring sp composition)
# ---------------------------------------------------------------------------

B, H, S, D = 2, 4, 64, 16


def _qkv(rng, dtype=jnp.float32):
    return tuple(jnp.asarray(rng.standard_normal((B, H, S, D)), dtype)
                 for _ in range(3))


@pytest.mark.parametrize("causal,window", [(False, None), (True, None),
                                           (True, 24)])
def test_flash_interpret_parity_masks(rng, causal, window):
    q, k, v = _qkv(rng)
    scale = 1.0 / np.sqrt(D)
    ref = attention_reference(q, k, v, None, causal, scale, window=window)
    with force_mode("interpret"):
        out = flash_attention(q, k, v, causal=causal,
                              sliding_window=window)
        g = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=causal, sliding_window=window))))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    g_ref = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(attention_reference(
        q, k, v, None, causal, scale, window=window))))(q, k, v)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=3e-4, atol=3e-5)


# --- the kernels' MXU operands keep the dtype the tensors came in -----------


def _l2_gap(got, ref):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def _int8_rounded(x):
    """Each row of the last axis on its own 255-step grid: the
    precision one below bfloat16's 8 significant bits."""
    step = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    return jnp.round(x / step) * step


# Relative L2 gap to the float32 reference on the same (widened) bf16
# inputs.  Readings on the two shapes below: the kernel 2.0e-3 on out
# (the bf16 result's own rounding) and 2.8e-3 .. 3.0e-3 on dq, dk, dv;
# the reference itself on int8-rounded inputs 7.0e-3 .. 1.1e-2.  The
# tolerance is the geometric mean of the two nearest: 1.5x of room on
# either side.
BF16_L2_TOL = 4.6e-3


@pytest.mark.parametrize("s,d,window", [(1100, 32, None), (328, 64, 96)],
                         ids=["ragged_two_k_blocks", "sliding_window"])
def test_flash_bf16_operands_against_f32_reference(rng, s, d,
                                                   window):
    """bf16 q, k, v: the products take them as stored and P, dS are
    rounded to bf16 for the other four; softmax statistics and
    accumulators stay float32, so the results stay within bf16's own
    rounding of the float32 reference — which int8-rounded inputs do
    not."""
    q, k, v = (jnp.asarray(rng.standard_normal((1, 2, s, d)), jnp.bfloat16)
               for _ in range(3))
    wide = tuple(x.astype(jnp.float32) for x in (q, k, v))
    scale = 1.0 / np.sqrt(d)

    def ref_out(q, k, v):
        return attention_reference(q, k, v, None, True, scale, window=window)

    def flash_out(q, k, v):
        return flash_attention(q, k, v, causal=True, sliding_window=window)

    w = jnp.asarray(rng.standard_normal((1, 2, s, d)), jnp.float32)

    def grads(fn, args):
        return jax.grad(lambda *a: jnp.sum(
            fn(*a).astype(jnp.float32) * w), argnums=(0, 1, 2))(*args)

    ref = (ref_out(*wide),) + grads(ref_out, wide)
    with force_mode("interpret"):
        got = (flash_out(q, k, v),) + grads(flash_out, (q, k, v))
    low = tuple(_int8_rounded(x) for x in wide)
    control = (ref_out(*low),) + grads(ref_out, low)
    for name, g, r, c in zip(("out", "dq", "dk", "dv"), got, ref, control):
        assert g.dtype == jnp.bfloat16, name
        assert _l2_gap(g, r) < BF16_L2_TOL, (name, _l2_gap(g, r))
        assert _l2_gap(c, r) > BF16_L2_TOL, (name, _l2_gap(c, r))


def _dot_generals(jaxpr):
    """Every dot_general equation under ``jaxpr``, the branches of
    ``pl.when`` included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _dot_generals(sub)
    return found


def _kernel_bodies(fn, *args):
    """name -> body jaxpr of each pallas_call that ``fn`` stages."""
    def calls(jaxpr):
        out = {}
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out[eqn.params["name"]] = eqn.params["jaxpr"]
            else:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    out.update(calls(sub))
        return out
    return calls(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("dropout_p", [0.0, 0.1], ids=["plain", "dropout"])
def test_flash_dot_operands_keep_the_input_dtype(dtype, dropout_p):
    """The mechanism itself: all nine products of a block pair (forward
    S, PV; dq S, dP, dS.K; dkv S, P^T.dO, dP, dS^T.Q) take operands of
    the tensors' dtype and accumulate in float32 — and so do the seven
    of the resident path (forward S, PV; backward S, dP, P^T.dO, dS^T.Q,
    dS.K), which plain bf16 calls take."""
    from apex_tpu.kernels import attention as ka
    x = jax.ShapeDtypeStruct((2, 256, 64), dtype)
    lse = jax.ShapeDtypeStruct((2, 256), jnp.float32)
    seed = jnp.int32(7) if dropout_p else None
    bodies = _kernel_bodies(
        lambda q, k, v: ka.flash_attention_fwd(
            q, k, v, None, 0.125, True, interpret=True,
            dropout_p=dropout_p, dropout_seed=seed), x, x, x)
    bodies.update(_kernel_bodies(
        lambda q, k, v, o, l, g: ka.flash_attention_bwd(
            q, k, v, None, o, l, g, 0.125, True, interpret=True,
            dropout_p=dropout_p, dropout_seed=seed), x, x, x, x, lse, x))
    want = {"flash_attn_fwd": 2, "flash_attn_bwd_dq": 3,
            "flash_attn_bwd_dkv": 4}
    if dtype == jnp.bfloat16 and not dropout_p:
        want = {"flash_attn_fwd": 2, "flash_attn_bwd": 5}
    assert {n: len(_dot_generals(b)) for n, b in bodies.items()} == want
    for name, body in bodies.items():
        for eqn in _dot_generals(body):
            assert [a.aval.dtype for a in eqn.invars] == [dtype, dtype], \
                (name, eqn)
            assert eqn.outvars[0].aval.dtype == jnp.float32, (name, eqn)


@pytest.mark.parametrize("sq,sk,d,itemsize,want", [
    (1024, 1024, 64, 4, (256, 512)),     # fp32: the tiles its bits follow
    (1024, 1024, 64, 2, (512, 1024)),    # bf16: fewer, larger grid steps
    (4096, 4096, 128, 2, (512, 1024)),
    (200, 328, 64, 2, (200, 328)),       # short inputs: one block
    (2048, 2048, 256, 2, (512, 512)),    # wide heads: what fits VMEM
    (2048, 2048, 1024, 4, (256, 256)),
])
def test_flash_block_sizes_follow_lengths_width_and_itemsize(
        sq, sk, d, itemsize, want):
    from apex_tpu.kernels import attention as ka
    assert ka._block_sizes(sq, sk, d, itemsize) == want
    assert ka._vmem_estimate(*want, d, itemsize) <= ka._VMEM_BUDGET
    if itemsize == 4:       # the default is fp32's: callers of old
        assert ka._block_sizes(sq, sk, d) == want


# sha256 of out, lse, dq, dk, dv (float32 inputs, jitted, interpret mode)
# as the kernels of commit e1d87c4 gave them, before their products took
# bf16 operands: float32 callers keep those bits.  ``canary`` is the same
# digest of a plain dot + exp + row sum on this backend: where it differs
# the CPU rounds otherwise than the one the digests were recorded on, and
# the comparison would say nothing of the kernels.
F32_DIGESTS = {
    "causal_ragged": {
        "canary":
            "6066f8d710655982648d7c8b7539c24f36d6fdb74d82eb2d25374be7ef448353",
        "out":
            "04b4e1785ffc713ec831b0e76a96a6a979f79089a41f2eddc1bf5112f58f6cef",
        "lse":
            "df384f71c1f7b6e89c7f2da145304b7c953304aed5046fe260d3cbf342e921d5",
        "dq":
            "505841e4043b2d67a9f2edc56f80a15ac7df828cf47f68481f1bce5dcdc6eb2f",
        "dk":
            "20249289e8acab794c3be2774b050e0b5a26211c33507664d7a8542c38421de8",
        "dv":
            "99f0394fe0d38af8312d8385232b371ab8fe49659837997d6b389b1041714563",
    },
    "band_bias_dropout": {
        "canary":
            "615d5fa7db13861d1cc2dacef832f688df72a2bf10ac9c57620693572ce21c10",
        "out":
            "8d3726b6bfaa9ba9b115ce5614889c67f1dd4b8589799f2130b5b2488ba77ce5",
        "lse":
            "042bc15ebf9720f30209df3320a6366f32f1aabd0d58520235c273a42d162ab6",
        "dq":
            "72550d46a0b2627f4dcd1c25d487bb90dc83787061e73299ed8da254837fcaa9",
        "dk":
            "22ddc0ee8a52cce0ec44b9ef9dd4543facaa4720041058b3180eb548c2ca1dbc",
        "dv":
            "6b9df91b1231cb98ca9eda0959ea8fbc26cd89347bd832ff9a74f3907986c291",
    },
}


def _digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a, np.float32)).tobytes())
    return h.hexdigest()


def _f32_case(which):
    from apex_tpu.kernels import attention as ka
    r = np.random.default_rng(30)
    if which == "causal_ragged":        # 2 q blocks, padded rows and keys
        bh, sq, sk, d = 3, 328, 328, 64
        kw = {}
    else:                               # 3 x 2 blocks, band, bias, dropout
        bh, sq, sk, d = 2, 600, 600, 32
        kw = dict(window=160, dropout_p=0.1, dropout_seed=jnp.int32(11))
    q = jnp.asarray(r.standard_normal((bh, sq, d)), jnp.float32)
    k, v = (jnp.asarray(r.standard_normal((bh, sk, d)), jnp.float32)
            for _ in range(2))
    g = jnp.asarray(r.standard_normal((bh, sq, d)), jnp.float32)
    bias = None
    if which != "causal_ragged":
        bias = jnp.asarray(r.standard_normal((1, sq, sk)), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    @jax.jit
    def run(q, k, v, g, bias):
        out, lse = ka.flash_attention_fwd(q, k, v, bias, scale, True,
                                          interpret=True, **kw)
        return (out, lse) + ka.flash_attention_bwd(
            q, k, v, bias, out, lse, g, scale, True, interpret=True, **kw)

    @jax.jit
    def canary(q, k):
        s = jnp.exp(jax.lax.dot_general(
            q[0], k[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale)
        return s, jnp.sum(s, axis=1)

    return run(q, k, v, g, bias), canary(q, k)


@pytest.mark.parametrize("which", ["causal_ragged", "band_bias_dropout"])
def test_flash_f32_bits_are_the_parents(which):
    got, canary = _f32_case(which)
    want = F32_DIGESTS[which]
    if _digest(canary) != want["canary"]:
        pytest.skip("this CPU rounds a plain dot/exp/sum otherwise than "
                    "the one the digests were recorded on")
    names = ("out", "lse", "dq", "dk", "dv")
    assert {n: _digest([a]) for n, a in zip(names, got)} == \
        {n: want[n] for n in names}


# --- the resident path: a sequence that fits VMEM whole ---------------------


def _pallas_calls(fn, *args):
    return sorted(_kernel_bodies(fn, *args))


def _flash_paths():
    from apex_tpu.observe import registry as obs
    return {t: obs.counter(f"kernels.dispatch.flash_attention.{t}").value
            for t in ("pallas", "xla", "resident")}


@pytest.mark.parametrize("sq,sk,d,causal,window", [
    (600, 600, 64, True, None),      # three row blocks, padded rows and keys
    (768, 768, 128, True, 160),      # a band: extents start past key 0
    (384, 640, 64, False, None),     # cross attention, no mask but padding
    (300, 300, 128, False, None),
    (256, 1024, 64, False, None),    # a ring hop's shape: one row block
], ids=["causal_ragged", "window_d128", "cross", "plain_d128", "hop"])
def test_flash_resident_against_f32_reference_and_tiled(
        rng, monkeypatch, sq, sk, d, causal, window):
    """Plain bf16 calls take the resident kernels (one grid step a head,
    key extents cut at the diagonal, one backward kernel): out, dq, dk
    and dv stay within bf16's rounding of the float32 reference, where
    int8-rounded inputs do not, and agree with the tiled kernels far
    inside that."""
    from apex_tpu.kernels import attention as ka
    bh = 2
    q, g = (jnp.asarray(rng.standard_normal((bh, sq, d)), jnp.bfloat16)
            for _ in range(2))
    k, v = (jnp.asarray(rng.standard_normal((bh, sk, d)), jnp.bfloat16)
            for _ in range(2))
    scale = 1.0 / np.sqrt(d)

    def run(q, k, v, g):
        out, lse = ka.flash_attention_fwd(q, k, v, None, scale, causal,
                                          interpret=True, window=window)
        return (out, lse) + ka.flash_attention_bwd(
            q, k, v, None, out, lse, g, scale, causal, interpret=True,
            window=window)

    assert _pallas_calls(run, q, k, v, g) == ["flash_attn_bwd",
                                              "flash_attn_fwd"]
    got = jax.jit(run)(q, k, v, g)
    assert got[1].shape == (bh, sq) and got[1].dtype == jnp.float32
    # a new function object: a trace of ``run`` itself would be found
    # in the cache, made under the rule as it was
    monkeypatch.setattr(ka, "_resident", lambda *a, **kw: None)
    rerun = lambda *a: run(*a)
    assert _pallas_calls(rerun, q, k, v, g) == [
        "flash_attn_bwd_dkv", "flash_attn_bwd_dq", "flash_attn_fwd"]
    tiled = jax.jit(rerun)(q, k, v, g)

    def ref(q, k, v):
        return attention_reference(q[None], k[None], v[None], None, causal,
                                   scale, window=window)[0]

    def ref_all(q, k, v):
        out, vjp = jax.vjp(ref, q, k, v)
        return (out,) + vjp(g.astype(jnp.float32))

    wide = tuple(x.astype(jnp.float32) for x in (q, k, v))
    want = ref_all(*wide)
    control = ref_all(*(_int8_rounded(x) for x in wide))
    names = ("out", "dq", "dk", "dv")
    for name, a, t, r, c in zip(names, got[:1] + got[2:],
                                tiled[:1] + tiled[2:], want, control):
        assert a.dtype == jnp.bfloat16, name
        assert _l2_gap(a, r) < BF16_L2_TOL, (name, _l2_gap(a, r))
        assert _l2_gap(c, r) > BF16_L2_TOL, (name, _l2_gap(c, r))
        assert _l2_gap(a, t) < BF16_L2_TOL / 10, (name, _l2_gap(a, t))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(tiled[1]),
                               rtol=1e-6, atol=1e-6)


def _rule_case(sq, sk, d, dtype=jnp.bfloat16, causal=True, window=None,
               bias=False, dropout_p=0.0):
    return dict(sq=sq, sk=sk, d=d, dtype=dtype, causal=causal,
                window=window, bias=bias, dropout_p=dropout_p)


RESIDENT_RULE_CASES = {
    "train_cell": (_rule_case(1024, 1024, 64), True),
    "longest_that_fits": (_rule_case(2048, 2048, 128), True),
    "window": (_rule_case(2048, 2048, 64, window=256), True),
    "ring_hop": (_rule_case(512, 2048, 128, causal=False), True),
    "short_ragged": (_rule_case(200, 328, 64), True),
    "fp32_pinned_bits": (_rule_case(1024, 1024, 64, dtype=jnp.float32),
                         False),
    "bias": (_rule_case(1024, 1024, 64, bias=True), False),
    "dropout": (_rule_case(1024, 1024, 64, dropout_p=0.1), False),
    "ninth_row_block": (_rule_case(2304, 2304, 64), False),
    "past_the_estimate": (_rule_case(4096, 4096, 128), False),
    "wide_heads_past_it": (_rule_case(2048, 2048, 256), False),
    "one_rows_extent_past_it": (_rule_case(256, 8192, 64, causal=False),
                                False),
    "rows_the_band_leaves_no_key": (_rule_case(768, 256, 64, window=128),
                                    False),
}


@pytest.mark.parametrize("case,resident", list(RESIDENT_RULE_CASES.values()),
                         ids=list(RESIDENT_RULE_CASES))
def test_flash_resident_rule(case, resident):
    """Which of the two sets of kernels a call takes is read off its own
    operands — dtype, lengths, head width, whether a bias or dropout
    rides along — and the resident path counts itself."""
    from apex_tpu.kernels import attention as ka
    sq, sk, d, dtype = case["sq"], case["sk"], case["d"], case["dtype"]
    q = jax.ShapeDtypeStruct((2, sq, d), dtype)
    k = jax.ShapeDtypeStruct((2, sk, d), dtype)
    lse = jax.ShapeDtypeStruct((2, sq), jnp.float32)
    bias = jnp.zeros((1, 1, sk), jnp.float32) if case["bias"] else None
    kw = dict(interpret=True, window=case["window"],
              dropout_p=case["dropout_p"],
              dropout_seed=jnp.int32(3) if case["dropout_p"] else None)
    before = _flash_paths()
    fwd = _pallas_calls(lambda q, k, v: ka.flash_attention_fwd(
        q, k, v, bias, 0.125, case["causal"], **kw), q, k, k)
    bwd = _pallas_calls(lambda q, k, v, o, l, g: ka.flash_attention_bwd(
        q, k, v, bias, o, l, g, 0.125, case["causal"], **kw),
        q, k, k, q, lse, q)
    assert fwd == ["flash_attn_fwd"]
    assert bwd == (["flash_attn_bwd"] if resident else
                   ["flash_attn_bwd_dkv", "flash_attn_bwd_dq"])
    after = _flash_paths()
    # counted once a forward trace; the backward follows the same rule
    assert after["resident"] - before["resident"] == int(resident)
    assert (after["pallas"], after["xla"]) == (before["pallas"],
                                               before["xla"])


# --- the packed entry: the resident kernels on the projection's layout -------


def _packed_count():
    from apex_tpu.observe import registry as obs
    return obs.counter("kernels.dispatch.flash_attention.packed").value


def _attn_operands(rng, t, b, heads, d, dtype=jnp.bfloat16, in_bias=False):
    e = heads * d
    x, g = (jnp.asarray(rng.standard_normal((t, b, e)), dtype)
            for _ in range(2))
    iw = jnp.asarray(rng.standard_normal((3 * e, e)) / np.sqrt(e), dtype)
    ow = jnp.asarray(rng.standard_normal((e, e)) / np.sqrt(e), dtype)
    ib = (jnp.asarray(rng.standard_normal((3 * e,)), dtype) if in_bias
          else None)
    return x, iw, ow, ib, g


def _split_path(heads, scale, x, iw, ow, ib=None, mask=None, causal=False,
                dropout_p=0.0, key=None, attend=None):
    """``self_attn_func(use_flash=True)`` as it was before the packed
    entry, from the public pieces: the projection, the interleaved split
    to (B.H, T, D), ``flash_attention`` on (B, H, T, D) (or ``attend``
    in its place), the heads-major context back to (T, B, H.D), the
    output projection.  ``heads`` are those of ``iw``'s rows."""
    from apex_tpu.contrib.multihead_attn import attn_funcs as af
    t, b, _ = x.shape
    d = iw.shape[0] // (3 * heads)
    lin = jnp.matmul(x, iw.T)
    if ib is not None:
        lin = lin + ib
    q4, k4, v4 = (a.reshape(b, heads, t, d) for a in
                  af._split_interleaved_qkv(lin, t, b, heads, d))
    bias = af._masks_to_bias(mask, False, b, heads, t, t)
    seed = af._dropout_seed(key) if dropout_p > 0.0 else None
    if attend is None:
        attend = functools.partial(flash_attention, bias=bias,
                                   dropout_p=dropout_p, dropout_seed=seed)
    ctx4 = attend(q4, k4, v4, causal=causal, scale=scale)
    ctx = jnp.swapaxes(ctx4.reshape(b * heads, t, d), 0, 1)
    return jnp.matmul(ctx.reshape(t, b, heads * d), ow.T)


def _with_grads(fn, g, *args):
    out, vjp = jax.vjp(fn, *args)
    return (out,) + vjp(g)


@pytest.mark.parametrize("t,b,heads,d,causal,in_bias", [
    (1024, 1, 12, 64, True, False),     # the train cell's layer, one row
    (1024, 1, 2, 64, False, False),
    (328, 2, 2, 64, True, True),        # T no multiple of 128: padded rows
    (600, 1, 4, 64, False, False),
    (1024, 1, 1, 128, True, False),     # a head a lane row: stored order
    (200, 2, 2, 128, False, True),
], ids=["d64_h12_s1024_causal", "d64_h2_s1024", "d64_ragged_causal_bias",
        "d64_h4_ragged", "d128_s1024_causal", "d128_ragged_bias"])
def test_flash_packed_against_the_split_entry(rng, t, b, heads, d, causal,
                                              in_bias):
    """Where its rule holds, ``self_attn_func`` hands the QKV projection's
    output to the resident kernels as it lies and takes their context as
    the output projection reads it: one forward and one backward call,
    no (B.H, T, D) operand, and the output and every gradient (to the
    inputs, ``in_proj_weight`` in its stored row order, its bias, the
    output projection) agree with the split (B, H, T, D) entry far
    inside bf16's rounding."""
    from apex_tpu.contrib.multihead_attn import self_attn_func
    x, iw, ow, ib, g = _attn_operands(rng, t, b, heads, d, in_bias=in_bias)
    scale = d ** -0.5
    args = (x, iw, ow) + ((ib,) if in_bias else ())

    def packed(x, iw, ow, ib=None):
        return self_attn_func(False, True, heads, scale, x, iw, ow, ib,
                              use_flash=True, causal=causal)

    def split(x, iw, ow, ib=None):
        return _split_path(heads, scale, x, iw, ow, ib, causal=causal)

    with force_mode("interpret"):
        before = (_packed_count(), _flash_paths())
        bodies = _kernel_bodies(lambda *a: _with_grads(packed, g, *a), *args)
        after = (_packed_count(), _flash_paths())
        got = jax.jit(lambda *a: _with_grads(packed, g, *a))(*args)
        want = jax.jit(lambda *a: _with_grads(split, g, *a))(*args)
    assert sorted(bodies) == ["flash_attn_bwd", "flash_attn_fwd"]
    # counted once a forward trace, as a Pallas and a resident call too
    assert after[0] - before[0] == 1
    for tier, n in (("pallas", 1), ("resident", 1), ("xla", 0)):
        assert after[1][tier] - before[1][tier] == n, tier
    # the kernels' blocks are whole lane rows of (B, T, .) arrays
    for name, body in bodies.items():
        shapes = [v.aval.shape for v in body.invars]
        assert (t_p := shapes[0][0]) >= t and shapes[0][1] == 384, shapes
        assert all(sh[-1] in (128, 384, t_p) for sh in shapes), (name,
                                                                 shapes)
    for name, a, w in zip(("out", "dx", "diw", "dow", "dib"), got, want):
        assert a.shape == w.shape and a.dtype == jnp.bfloat16, name
        # the bias gradient is a bf16 sum over T x B rows, which the two
        # paths hold in another order: its own rounding, not the kernels'
        tol = 4 * BF16_L2_TOL if name == "dib" else BF16_L2_TOL / 10
        assert _l2_gap(a, w) < tol, (name, _l2_gap(a, w))


def _sharded(axis, n, fn, *specs):
    mesh = Mesh(np.array(jax.devices()[:n]), (axis,))
    return jax.shard_map(fn, mesh=mesh, in_specs=specs[:-1],
                         out_specs=specs[-1], check_vma=False)


PACKED_RULE_CASES = {
    # name: (heads, d, what rides along, packed)
    "even_heads_d64": (2, 64, {}, True),
    "three_heads_d64": (3, 64, {}, False),
    "head_width_32": (4, 32, {}, False),
    "fp32_operands": (2, 64, {"dtype": jnp.float32}, False),
    "a_mask": (2, 64, {"mask": True}, False),
    "dropout": (2, 64, {"dropout_p": 0.1}, False),
    "sequence_parallel_axis": (2, 64, {"sp": 2}, False),
    "tp_even_local_heads": (4, 64, {"tp": 2}, True),
    "tp_odd_local_heads": (6, 64, {"tp": 2}, False),
}


@pytest.mark.parametrize("heads,d,rides,packed",
                         list(PACKED_RULE_CASES.values()),
                         ids=list(PACKED_RULE_CASES))
def test_flash_packed_rule(rng, heads, d, rides, packed):
    """The packed entry's rule is read off the call: bf16, whole lane
    rows of *local* heads (D 128, or D 64 and an even count), no mask, no
    dropout, no sequence-parallel axis.  Every other call takes the
    split path as before: ``.packed`` does not count, and the output and
    the gradients to the inputs and both weights are those of the split
    composition, written out here from the public pieces, to the bit.
    Where the rule holds on a tensor-parallel head shard, the gradient
    comes back through the lane-row order of the *local* row block to
    the full weight in its stored order, within bf16's rounding."""
    from apex_tpu.contrib.multihead_attn import self_attn_func
    t, b = 256, 2
    x, iw, ow, _, _ = _attn_operands(rng, t, b, heads, d,
                                     rides.get("dtype", jnp.bfloat16))
    mask = (jnp.arange(t)[None, :] >= jnp.asarray([[t], [t - 56]])
            if rides.get("mask") else None)
    dropout_p = rides.get("dropout_p", 0.0)
    key = jax.random.PRNGKey(5) if dropout_p else None
    scale = d ** -0.5

    def attn(x, iw, ow, **axes):
        return self_attn_func(False, True, heads, scale, x, iw, ow,
                              mask=mask, dropout_prob=dropout_p, key=key,
                              use_flash=True, causal=mask is None, **axes)

    def split(x, iw, ow):
        return _split_path(heads, scale, x, iw, ow, mask=mask,
                           causal=mask is None, dropout_p=dropout_p, key=key)

    if "sp" in rides:
        # the split composition on a time shard, written out: the
        # projections local, the attention on the ring
        def split_sp(x, iw, ow):
            return _split_path(heads, scale, x, iw, ow, causal=True,
                               attend=functools.partial(ring_attention,
                                                        axis_name="sp"))
        specs = (P("sp"), P(), P(), P("sp"))
        fn = _sharded("sp", rides["sp"], functools.partial(
            attn, seq_parallel_axis="sp"), *specs)
        split = _sharded("sp", rides["sp"], split_sp, *specs)
    elif "tp" in rides:
        # the split composition on a head shard: the layer's entry
        # protocol (f on the stream, a row block of in_proj, a column
        # block of out_proj), the split path on the local heads, g
        from apex_tpu.parallel.tensor_parallel import (
            reduce_from_tp_region, tp_attn_begin)

        def split_tp(x, iw, ow):
            (x,), local, (iw,), (ow,) = tp_attn_begin("tp", heads, [x],
                                                      [iw], [ow])
            return reduce_from_tp_region(_split_path(
                local, scale, x, iw, ow, causal=True), "tp")
        specs = (P(), P(), P(), P())
        fn = _sharded("tp", rides["tp"], functools.partial(
            attn, tensor_parallel_axis="tp"), *specs)
        split = _sharded("tp", rides["tp"], split_tp, *specs)
    else:
        fn = attn

    g = jnp.asarray(rng.standard_normal(x.shape), x.dtype)
    with force_mode("interpret"):
        before = _packed_count()
        got = jax.jit(lambda *a: _with_grads(fn, g, *a))(x, iw, ow)
        assert _packed_count() - before == int(packed)
        want = jax.jit(lambda *a: _with_grads(split, g, *a))(x, iw, ow)
    for name, a, w in zip(("out", "dx", "diw", "dow"), got, want):
        assert a.shape == w.shape and a.dtype == w.dtype, name
        if packed:
            assert _l2_gap(a, w) < BF16_L2_TOL / 10, (name, _l2_gap(a, w))
        else:
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(w, np.float32), name)
    if "tp" in rides:
        # and the head shards together are the unsharded layer: every row
        # of the full weight's gradient lands where the weight stores it
        with force_mode("interpret"):
            whole = jax.jit(lambda *a: _with_grads(
                lambda x, iw, ow: _split_path(heads, scale, x, iw, ow,
                                              causal=True), g, *a))(x, iw, ow)
        for name, a, w in zip(("out", "dx", "diw", "dow"), got, whole):
            if name in ("diw", "dow"):
                # with its checks off shard_map hands a replicated operand
                # the mean over the axis of its shards' cotangents, and
                # each shard's is its own block with zeros beside it
                a = a.astype(jnp.float32) * rides["tp"]
            assert _l2_gap(a, w) < BF16_L2_TOL, (name, _l2_gap(a, w))


def _tier_counts(kernel):
    from apex_tpu.observe import registry as obs
    return {t: obs.counter(f"kernels.dispatch.{kernel}.{t}").value
            for t in ("pallas", "xla")}


def test_ring_sp_composition_honors_ledger_fallback(rng, monkeypatch):
    """The sp plan's ring step follows the flash kernel's own rule at the
    LOCAL chunk shape: in a compiled program, chunks below
    ``FLASH_MIN_SK`` keys send every hop to the XLA chunk math and
    chunks from it up keep the Pallas kernel (traced, not run: the CPU
    cannot) — and both tiers match the gathered-sequence oracle."""
    from apex_tpu.kernels import attention as ka

    n = 4
    mesh = Mesh(np.array(jax.devices()[:n]), ("sp",))
    q, k, v = _qkv(rng)
    scale = 1.0 / np.sqrt(D)
    ref = attention_reference(q, k, v, None, True, scale)
    assert S // n < ka.FLASH_MIN_SK

    def ring():
        fn = functools.partial(ring_attention, axis_name="sp",
                               causal=True)
        return jax.shard_map(fn, mesh=mesh,
                             in_specs=P(None, None, "sp", None),
                             out_specs=P(None, None, "sp", None),
                             check_vma=False)

    for min_sk, want_tier in ((ka.FLASH_MIN_SK, "xla"),
                              (S // n, "pallas")):
        monkeypatch.setattr(ka, "FLASH_MIN_SK", min_sk)
        before = _tier_counts("flash_attention")
        with force_mode("compiled"):
            program = str(jax.make_jaxpr(ring())(q, k, v))
        took = {t: c - before[t]
                for t, c in _tier_counts("flash_attention").items()}
        assert took == {want_tier: 1,
                        "pallas" if want_tier == "xla" else "xla": 0}
        assert ("pallas_call" in program) == (want_tier == "pallas")
    for mode in ("off", "interpret"):
        with force_mode(mode):
            out = jax.jit(ring())(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# vocab chain: fused kernel vs chunked XLA chain, fwd + bwd
# ---------------------------------------------------------------------------


def test_vocab_chain_fwd_bwd_bitwise(rng):
    n, v, e = 24, 384, 64
    hidden = jnp.asarray(rng.standard_normal((n, e)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((v, e)) * 0.02, jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, (n,)), jnp.int32)
    labels = labels.at[3].set(-100)      # padding row

    def chunked_mean(h, w):
        per = chunked_lm_head_loss(h, w, labels)
        return per.sum() / jnp.maximum((labels != -100).sum(), 1)

    def fused_mean(h, w):
        per = vocab_chain_loss(h, w, labels)
        return per.sum() / jnp.maximum((labels != -100).sum(), 1)

    with force_mode("interpret"):
        ref = jax.jit(chunked_mean)(hidden, w)
        got = jax.jit(fused_mean)(hidden, w)
        g_ref = jax.jit(jax.grad(chunked_mean, argnums=(0, 1)))(hidden, w)
        g_got = jax.jit(jax.grad(fused_mean, argnums=(0, 1)))(hidden, w)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    for r, g in zip(g_ref, g_got):
        np.testing.assert_allclose(np.asarray(r), np.asarray(g),
                                   rtol=1e-6, atol=1e-7)


def test_vocab_chain_smoothing_takes_chunked_path(rng):
    """Smoothing is outside the kernel's contract — the dispatch-gated
    entry must produce the chunked chain's exact result."""
    n, v, e = 16, 256, 32
    hidden = jnp.asarray(rng.standard_normal((2, n // 2, e)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((v, e)) * 0.02, jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, (2, n // 2)), jnp.int32)
    with force_mode("interpret"):
        ref = chunked_lm_head_loss(hidden, w, labels, smoothing=0.1)
        got = vocab_chain_loss(hidden, w, labels, smoothing=0.1)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    assert ref.shape == hidden.shape[:-1]


# ---------------------------------------------------------------------------
# the rules: which tier a call takes, from the mode and the shapes alone
# ---------------------------------------------------------------------------


def _sgd_lists(rng):
    gs, ps, ms = (_tensors(rng, [(16, 8), (40,)]) for _ in range(3))
    return [gs, ps, ms]


@pytest.mark.parametrize("mode,tier", [("off", "xla"),
                                       ("interpret", "pallas")])
def test_dispatch_tier_pinned_via_kind_stats(rng, mode, tier):
    """The eager entry's program kind names the tier the kernel's rule
    chose, and the counter moves with it."""
    from apex_tpu.kernels.multi_tensor import multi_tensor_sgd

    lists = _sgd_lists(rng)
    kind = f"kernel.multi_tensor_sgd.{tier}"
    other = f"kernel.multi_tensor_sgd.{'pallas' if tier == 'xla' else 'xla'}"
    before = step_cache.kind_stats(kind)["dispatches"]
    before_other = step_cache.kind_stats(other)["dispatches"]
    counts = _tier_counts("multi_tensor_sgd")
    with force_mode(mode):
        out = multi_tensor_sgd(jnp.zeros((), jnp.int32), lists,
                               0.0, 0.9, 0.0, 0.1, False, True, False)
    assert len(out) == 3
    assert step_cache.kind_stats(kind)["dispatches"] == before + 1
    assert step_cache.kind_stats(other)["dispatches"] == before_other
    assert _tier_counts("multi_tensor_sgd")[tier] == counts[tier] + 1


def test_dispatch_defaults_no_mode_is_xla(rng):
    """CPU default (no forced mode): every rule answers XLA and the
    per-bucket paths run unchanged — the tier-1 invariance guarantee."""
    from apex_tpu.kernels import attention as ka, vocab_chain
    from apex_tpu.kernels import multi_tensor as kmt

    assert dispatch.pallas_mode() is None
    assert ka.kernel_mode(2, 4, 1024, 1024) is None
    assert kmt.kernel_mode("sgd") is None
    assert vocab_chain.kernel_mode() is None
    assert not ops_mt._use_fused("sgd", _sgd_lists(rng))


def test_dispatch_probe_decides_compiled_unmeasured(monkeypatch):
    """Compiled mode: the flash rule is the module's constant and
    nothing else — 512 keys by default, and it moves with the constant
    (what ``bench._pin_flash_dispatch`` relies on)."""
    from apex_tpu.kernels import attention as ka

    assert ka.FLASH_MIN_SK == 512
    with force_mode("compiled"):
        assert ka.kernel_mode(2, 4, 64, 64) is None
        assert ka.kernel_mode(2, 4, 1024, 1024) == "compiled"
        monkeypatch.setattr(ka, "FLASH_MIN_SK", 0)
        assert ka.kernel_mode(2, 4, 64, 64) == "compiled"


def test_kernel_catalog_declares_fallbacks():
    cat = dispatch.catalog()
    for name in ("flash_attention", "multi_tensor_sgd",
                 "multi_tensor_adam", "vocab_chain_loss"):
        assert name in cat, f"{name} not registered"
        assert cat[name].xla_fallback
        assert callable(cat[name].audit_programs)
    with pytest.raises(ValueError):
        dispatch.register_kernel("bad", xla_fallback="")


def _flash_rule(b, h, sq, sk):
    from apex_tpu.kernels import attention as ka
    return lambda: ka.kernel_mode(b, h, sq, sk)


def _paged_rule(heads, head_dim, block_size, dtype):
    from apex_tpu.kernels import paged_attention as pa
    from apex_tpu.serve.pool import init_pool_buffer

    def rule():
        pool = init_pool_buffer(1, heads, head_dim, 4, block_size, dtype)
        return pa.kernel_mode(jnp.zeros((2, heads, head_dim), jnp.bfloat16),
                              pool)
    return rule


def _latent_rule(heads, rank, rope, block_size):
    from apex_tpu.kernels import latent_attention as la

    def rule():
        w = rank + rope
        pool = jax.ShapeDtypeStruct((1, 1, 4, block_size, w), jnp.bfloat16)
        q = jax.ShapeDtypeStruct((2, heads, w), jnp.bfloat16)
        return la.kernel_mode(q, pool, rank)
    return rule


def _experts_rule(kdim, n, tile):
    from apex_tpu.kernels import grouped_matmul as gmm

    def rule():
        lhs = jax.ShapeDtypeStruct((2 * gmm.TILE_ROWS, kdim), jnp.bfloat16)
        rhs = jax.ShapeDtypeStruct((2, kdim, n), jnp.bfloat16)
        return gmm.kernel_mode(lhs, rhs, tile or gmm.TILE_ROWS)
    return rule


def _adam_rule():
    from apex_tpu.kernels import multi_tensor as kmt
    return kmt.kernel_mode("adam")


def _vocab_rule():
    from apex_tpu.kernels import vocab_chain
    return vocab_chain.kernel_mode()


#: 8 x 16 heads of 256 x 256 fp32 scores = 128 MiB exactly: the cap is
#: "greater than", so one more head row passes it
_AT_CAP = (8, 64, 256, 256)
_OVER_CAP = (8, 65, 256, 256)

RULE_CASES = [
    # flash: the 512-key boundary and the score-byte cap, per mode
    ("flash_attention", _flash_rule(2, 4, 256, 256), "compiled", "xla"),
    ("flash_attention", _flash_rule(2, 4, 512, 512), "compiled", "pallas"),
    ("flash_attention", _flash_rule(2, 4, 1024, 1024), "compiled",
     "pallas"),
    ("flash_attention", _flash_rule(2, 4, 128, 1024), "compiled", "pallas"),
    ("flash_attention", _flash_rule(*_AT_CAP), "compiled", "xla"),
    ("flash_attention", _flash_rule(*_OVER_CAP), "compiled", "pallas"),
    ("flash_attention", _flash_rule(2, 4, 256, 256), "interpret",
     "pallas"),
    ("flash_attention", _flash_rule(2, 4, 1024, 1024), "interpret",
     "pallas"),
    ("flash_attention", _flash_rule(2, 4, 1024, 1024), "off", "xla"),
    ("flash_attention", _flash_rule(*_OVER_CAP), "off", "xla"),
    # the three that are taken wherever their tiles fit
    ("paged_attention", _paged_rule(2, 64, 16, jnp.bfloat16), "compiled",
     "pallas"),
    ("paged_attention", _paged_rule(2, 64, 16, "int8"), "compiled", "xla"),
    ("paged_attention", _paged_rule(4, 8, 16, jnp.bfloat16), "compiled",
     "xla"),
    ("paged_attention", _paged_rule(2, 64, 16, jnp.bfloat16), "off", "xla"),
    ("latent_attention", _latent_rule(8, 128, 128, 16), "compiled",
     "pallas"),
    ("latent_attention", _latent_rule(8, 64, 64, 16), "compiled", "xla"),
    ("latent_attention", _latent_rule(4, 128, 128, 16), "compiled", "xla"),
    ("routed_experts", _experts_rule(128, 256, None), "compiled", "pallas"),
    ("routed_experts", _experts_rule(128, 256, 1), "compiled", "xla"),
    ("routed_experts", _experts_rule(96, 256, None), "compiled", "xla"),
    # the two a compiled program leaves to XLA
    ("multi_tensor_adam", _adam_rule, "compiled", "xla"),
    ("multi_tensor_adam", _adam_rule, "interpret", "pallas"),
    ("vocab_chain_loss", _vocab_rule, "compiled", "xla"),
    ("vocab_chain_loss", _vocab_rule, "interpret", "pallas"),
]


@pytest.mark.parametrize(
    "kernel,rule,mode,tier", RULE_CASES,
    ids=[f"{k}-{i}-{m}-{t}" for i, (k, _, m, t) in enumerate(RULE_CASES)])
def test_each_kernels_rule(kernel, rule, mode, tier):
    """Every kernel's rule answers from the mode and the shapes alone:
    the mode the kernel runs in, or ``None`` for the XLA tier — and the
    tier's counter moves by one where the rule is applied."""
    before = _tier_counts(kernel)
    with force_mode(mode):
        got = rule()
    assert got == (None if tier == "xla" else mode)
    after = _tier_counts(kernel)
    other = "pallas" if tier == "xla" else "xla"
    assert after[tier] == before[tier] + 1
    assert after[other] == before[other]
