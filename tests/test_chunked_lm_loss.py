"""chunked_lm_head_loss: the chunkwise vocab chain must be numerically
identical (up to summation order) to the materialized head+loss chain —
losses, dx (hidden grads), and d(head_weight) accumulated across
chunks; plus the output_hidden model wiring end-to-end.  The factory's
mean loss takes its gradient with its forward (one loop, three
vocabulary-wide products a chunk): the same chain is its oracle, and
the jaxprs say which of the two paths a call took."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.contrib.xentropy import (chunked_lm_head_loss,
                                       make_chunked_lm_loss,
                                       softmax_cross_entropy_loss)

E, V = 32, 97


def _oracle(hidden, w, labels, smoothing=0.0, padding_idx=-100,
            logical_vocab=None):
    logits = jnp.matmul(hidden, w.T.astype(hidden.dtype))
    if logical_vocab is not None and logical_vocab < w.shape[0]:
        cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                        logits.ndim - 1)
        logits = jnp.where(cols < logical_vocab, logits,
                           jnp.asarray(-1e30, logits.dtype))
    return softmax_cross_entropy_loss(logits, labels, smoothing,
                                      padding_idx, True)


@pytest.mark.parametrize("n,chunk", [(24, 8), (25, 8), (24, 100), (7, 2)])
def test_matches_materialized_chain(rng, n, chunk):
    hidden = jnp.asarray(rng.standard_normal((n, E)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((V, E)) * 0.1, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (n,)))

    def tot_chunked(h, ww):
        per = chunked_lm_head_loss(h, ww, labels, chunk_rows=chunk)
        return jnp.sum(per ** 2), per

    def tot_ref(h, ww):
        per = _oracle(h, ww, labels)
        return jnp.sum(per ** 2), per

    (_, per_c), (dh_c, dw_c) = jax.value_and_grad(
        tot_chunked, argnums=(0, 1), has_aux=True)(hidden, w)
    (_, per_r), (dh_r, dw_r) = jax.value_and_grad(
        tot_ref, argnums=(0, 1), has_aux=True)(hidden, w)
    np.testing.assert_allclose(np.asarray(per_c), np.asarray(per_r),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dh_c), np.asarray(dh_r),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dw_c), np.asarray(dw_r),
                               rtol=1e-4, atol=1e-6)


def test_leading_dims_and_padding_idx(rng):
    hidden = jnp.asarray(rng.standard_normal((2, 6, E)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((V, E)) * 0.1, jnp.float32)
    labels = np.asarray(rng.integers(0, V, (2, 6)))
    labels[0, 2] = -100
    labels = jnp.asarray(labels)
    per = chunked_lm_head_loss(hidden, w, labels, chunk_rows=4)
    assert per.shape == (2, 6)
    assert float(per[0, 2]) == 0.0
    ref = _oracle(hidden.reshape(-1, E), w, labels.reshape(-1))
    np.testing.assert_allclose(np.asarray(per).reshape(-1),
                               np.asarray(ref), rtol=1e-5, atol=1e-6)


def test_padded_head_smoothing_exact(rng):
    """Lane-padded head (logical_vocab < V) under smoothing: equals the
    unpadded table's loss exactly (mask-aware smoothing through the
    chunked path)."""
    v_pad = 128
    hidden = jnp.asarray(rng.standard_normal((10, E)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((V, E)) * 0.1, jnp.float32)
    w_pad = jnp.concatenate(
        [w, jnp.asarray(rng.standard_normal((v_pad - V, E)) * 0.1,
                        jnp.float32)])
    labels = jnp.asarray(rng.integers(0, V, (10,)))
    ref = chunked_lm_head_loss(hidden, w, labels, smoothing=0.1)
    got = chunked_lm_head_loss(hidden, w_pad, labels, smoothing=0.1,
                               logical_vocab=V, chunk_rows=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    # pad table rows receive zero gradient
    dw = jax.grad(lambda ww: jnp.sum(chunked_lm_head_loss(
        hidden, ww, labels, smoothing=0.1, logical_vocab=V,
        chunk_rows=4)))(w_pad)
    assert np.all(np.asarray(dw[V:]) == 0.0)


def test_bf16_hidden(rng):
    hidden = jnp.asarray(rng.standard_normal((16, E)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((V, E)) * 0.1, jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, V, (16,)))
    per = chunked_lm_head_loss(hidden, w, labels, chunk_rows=8)
    ref = _oracle(hidden, w, labels)
    assert per.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(per), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)
    dh, dw = jax.grad(lambda h, ww: jnp.sum(chunked_lm_head_loss(
        h, ww, labels, chunk_rows=8)), argnums=(0, 1))(hidden, w)
    assert dh.dtype == jnp.bfloat16 and dw.dtype == jnp.bfloat16


def test_gpt_output_hidden_train_step_parity(rng):
    """A GPT train step over output_hidden + make_chunked_lm_loss
    matches the logits-returning model + fused-xentropy step losses to
    near-f32 for several steps (same init, same batch)."""
    import apex_tpu.nn as nn
    from apex_tpu.models import GptModel
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.training import make_train_step
    from apex_tpu.contrib.xentropy import make_chunked_lm_loss

    def build(output_hidden):
        nn.manual_seed(7)
        m = GptModel(vocab_size=V, hidden=E, layers=2, heads=4,
                     max_positions=16, dropout=0.0, attn_dropout=0.0,
                     output_hidden=output_hidden)
        opt = FusedAdam(list(m.parameters()), lr=1e-3)
        return m, opt

    ids = jnp.asarray(rng.integers(0, V, (4, 16)))

    m1, o1 = build(False)

    def loss_logits(logits, ids_):
        flat = logits[:, :-1].reshape((-1, V))
        tgt = ids_[:, 1:].reshape((-1,))
        return jnp.mean(softmax_cross_entropy_loss(flat, tgt, 0.0, -1,
                                                   True))

    s1 = make_train_step(m1, o1, loss_logits, loss_scale=1.0)

    m2, o2 = build(True)
    s2 = make_train_step(m2, o2,
                         make_chunked_lm_loss(chunk_rows=16,
                                              padding_idx=-1),
                         loss_scale=1.0)
    for step in range(3):
        l1 = float(s1(ids, ids))
        l2 = float(s2(ids, ids))
        np.testing.assert_allclose(l2, l1, rtol=2e-5,
                                   err_msg=f"step {step}")


def test_llama_output_hidden_shapes(rng):
    import apex_tpu.nn as nn
    from apex_tpu.models import LlamaModel

    nn.manual_seed(3)
    m = LlamaModel(vocab_size=V, hidden=E, layers=1, heads=4, kv_heads=2,
                   intermediate=64, max_positions=16, output_hidden=True)
    ids = jnp.asarray(rng.integers(0, V, (2, 8)))
    hidden, w = m(ids).value if hasattr(m(ids), "value") else m(ids)
    assert hidden.shape == (2, 8, E)
    assert w.shape == (V, E)


def test_chunked_composes_with_remat_and_grad_accum(rng):
    """The chunked loss under jax.checkpoint composes with block remat
    and grad accumulation in one compiled step (nested checkpoints +
    scan-in-scan)."""
    import apex_tpu.nn as nn
    from apex_tpu.models import GptModel
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.training import make_train_step
    from apex_tpu.contrib.xentropy import make_chunked_lm_loss

    nn.manual_seed(4)
    m = GptModel(vocab_size=V, hidden=E, layers=2, heads=4,
                 max_positions=16, dropout=0.0, attn_dropout=0.0,
                 remat=True, output_hidden=True)
    opt = FusedAdam(list(m.parameters()), lr=1e-3)
    s = make_train_step(m, opt, make_chunked_lm_loss(chunk_rows=16,
                                                     padding_idx=-1),
                        half_dtype=jnp.bfloat16, loss_scale=1.0,
                        grad_accum_steps=2)
    ids = jnp.asarray(rng.integers(0, V, (4, 16)))
    losses = [float(s(ids, ids)) for _ in range(5)]
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


# -- the factory's loss: its gradient computed with its forward ----------------


def _materialized_mean(hidden, table, ids, smoothing=0.0, padding_idx=-1,
                       logical_vocab=None):
    """hidden @ table.T -> softmax_cross_entropy_loss -> mean over all
    rows, next-token shifted as the factory shifts."""
    return jnp.mean(_oracle(hidden[:, :-1], table, ids[:, 1:], smoothing,
                            padding_idx, logical_vocab))


_B, _S = 3, 9           # 24 shifted rows

_FACTORY_CASES = {
    # name: (dtype, chunk_rows, padding rows, smoothing, lane-padded head)
    "f32-one-chunk": (jnp.float32, 100, False, 0.0, False),
    "f32-whole-chunks": (jnp.float32, 8, False, 0.0, False),
    "f32-remainder-chunk": (jnp.float32, 5, False, 0.0, False),
    "f32-padding-rows": (jnp.float32, 5, True, 0.0, False),
    "f32-smoothing-padded-head": (jnp.float32, 8, True, 0.1, True),
    "bf16-one-chunk": (jnp.bfloat16, 100, False, 0.0, False),
    "bf16-whole-chunks": (jnp.bfloat16, 8, False, 0.0, False),
    "bf16-remainder-chunk": (jnp.bfloat16, 5, True, 0.0, False),
    "bf16-smoothing-padded-head": (jnp.bfloat16, 5, False, 0.1, True),
}


def _factory_inputs(rng, dtype, pad_rows, padded_head):
    v = 128 if padded_head else V
    hidden = jnp.asarray(rng.standard_normal((_B, _S, E)), dtype)
    table = jnp.asarray(rng.standard_normal((v, E)) * 0.1, dtype)
    ids = np.asarray(rng.integers(0, V, (_B, _S)))
    if pad_rows:
        ids[0, 3] = ids[2, 8] = ids[1, 1] = -1
    return hidden, table, jnp.asarray(ids)


@pytest.mark.parametrize("case", sorted(_FACTORY_CASES))
def test_factory_loss_and_gradients_match_materialized_chain(rng, case):
    dtype, chunk, pad_rows, smoothing, padded_head = _FACTORY_CASES[case]
    hidden, table, ids = _factory_inputs(rng, dtype, pad_rows, padded_head)
    logical = V if padded_head else None
    loss_fn = make_chunked_lm_loss(vocab_size=logical, smoothing=smoothing,
                                   padding_idx=-1, chunk_rows=chunk)
    loss, (dh, dw) = jax.value_and_grad(
        lambda h, w: loss_fn((h, w), ids), argnums=(0, 1))(hidden, table)
    # the oracle in float32 on the same (rounded) operands
    ref, (dh_r, dw_r) = jax.value_and_grad(
        lambda h, w: _materialized_mean(h, w, ids, smoothing, -1, logical),
        argnums=(0, 1))(hidden.astype(jnp.float32),
                        table.astype(jnp.float32))
    assert loss.dtype == jnp.float32
    assert dh.dtype == dtype and dw.dtype == dtype
    assert dh.shape == hidden.shape and dw.shape == table.shape
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    # the un-differentiated call is the same number (to a bf16 logit's
    # rounding, where one product runs inside a loop and one outside)
    np.testing.assert_allclose(float(loss_fn((hidden, table), ids)),
                               float(loss), rtol=tol * 1e-1)
    np.testing.assert_allclose(float(loss), float(ref), rtol=tol)
    for got, want in ((dh, dh_r), (dw, dw_r)):
        got = np.asarray(got.astype(jnp.float32))
        want = np.asarray(want)
        assert np.abs(got - want).max() <= tol * np.abs(want).max()
    # the last position of a sequence has no next token
    assert np.all(np.asarray(dh[:, -1].astype(jnp.float32)) == 0.0)
    if pad_rows:
        # ids[0, 3] labels hidden[0, 2]
        assert np.all(np.asarray(dh[0, 2].astype(jnp.float32)) == 0.0)
    if padded_head:
        assert np.all(np.asarray(dw[V:].astype(jnp.float32)) == 0.0)


def test_factory_under_a_float16_loss_scale(rng):
    """The loss times 2^16 in float16: 1/n and the scale meet the sums in
    float32 after the products, so the gradients are the checkpointed
    path's to float16's last bit and no farther from the float32
    chain's."""
    hidden, table, ids = _factory_inputs(rng, jnp.float16, True, False)
    scale = 2.0 ** 16
    loss_fn = make_chunked_lm_loss(padding_idx=-1, chunk_rows=5)

    def checkpointed(h, w):
        return scale * jnp.mean(chunked_lm_head_loss(
            h[:, :-1], w, ids[:, 1:], padding_idx=-1, chunk_rows=5))

    got = jax.grad(lambda h, w: scale * loss_fn((h, w), ids),
                   argnums=(0, 1))(hidden, table)
    old = jax.grad(checkpointed, argnums=(0, 1))(hidden, table)
    ref = jax.grad(lambda h, w: scale * _materialized_mean(h, w, ids),
                   argnums=(0, 1))(hidden.astype(jnp.float32),
                                   table.astype(jnp.float32))
    for g, o, r in zip(got, old, ref):
        assert g.dtype == jnp.float16
        g, o, r = (np.asarray(a, np.float64) for a in (g, o, r))
        assert np.all(np.isfinite(g)) and np.abs(r).max() > 1.0
        # in units of the tensor's largest element's last bit: a
        # gradient's small elements are sums of rounded large terms
        ulp = np.abs(r).max() * 2.0 ** -10
        assert np.abs(g - o).max() <= 2 * ulp
        assert np.abs(g - r).max() <= np.abs(o - r).max() + ulp / 2


# -- which path a call took: the jaxpr and the counters ------------------------

# perfbench/families/gpt.py::tiny(): 64 wide, 211 tokens, 128 positions
_TE, _TV, _TB, _TS = 64, 211, 4, 128
_TCHUNK = 127           # four chunks of the 4 x 127 shifted rows


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def _vocab_wide_dots(jaxpr):
    """dot_generals with the vocabulary among an operand's or the
    result's dimensions."""
    return [e for e in _walk(jaxpr) if e.primitive.name == "dot_general"
            and any(_TV in v.aval.shape for v in (*e.invars, *e.outvars))]


def _loops(jaxpr):
    return [e for e in _walk(jaxpr) if e.primitive.name in ("scan", "while")]


def _tiny_operands():
    hidden = jnp.zeros((_TB, _TS, _TE), jnp.bfloat16)
    table = jnp.zeros((_TV, _TE), jnp.bfloat16)
    ids = jnp.zeros((_TB, _TS), jnp.int32)
    return hidden, table, ids


def _lm_head_counters():
    from apex_tpu.observe import registry as obs
    return {p: obs.counter(f"kernels.dispatch.lm_head_loss.{p}").value
            for p in ("grad_with_forward", "checkpointed")}


def _counted(before):
    return {p: n - before[p] for p, n in _lm_head_counters().items()}


def test_factory_gradient_is_one_loop_of_three_vocabulary_wide_products():
    hidden, table, ids = _tiny_operands()
    loss_fn = make_chunked_lm_loss(vocab_size=_TV, padding_idx=-1,
                                   chunk_rows=_TCHUNK)
    before = _lm_head_counters()
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda h, w: loss_fn((h, w), ids), argnums=(0, 1)))(hidden, table)
    assert _counted(before) == {"grad_with_forward": 1, "checkpointed": 0}
    loops = _loops(jaxpr.jaxpr)
    assert len(loops) == 1 and loops[0].params["length"] == 4, loops
    dots = _vocab_wide_dots(jaxpr.jaxpr)
    assert len(dots) == 3, dots
    # all three inside the loop, and nothing as wide as the vocabulary
    # times a chunk's rows leaves it: the carry is d table, the stacked
    # outputs are the rows' losses and d hidden
    assert len(_vocab_wide_dots(loops[0].params["jaxpr"].jaxpr)) == 3
    shapes = sorted(v.aval.shape for v in loops[0].outvars)
    assert shapes == [(4, _TCHUNK), (4, _TCHUNK, _TE), (_TV, _TE)], shapes
    for eqn in jaxpr.jaxpr.eqns:
        for v in eqn.outvars:
            assert _TV not in v.aval.shape or v.aval.shape == (_TV, _TE), eqn


def test_factory_undifferentiated_computes_the_loss_alone():
    hidden, table, ids = _tiny_operands()
    loss_fn = make_chunked_lm_loss(vocab_size=_TV, padding_idx=-1,
                                   chunk_rows=_TCHUNK)
    before = _lm_head_counters()
    jaxpr = jax.make_jaxpr(lambda h, w: loss_fn((h, w), ids))(hidden, table)
    assert _counted(before) == {"grad_with_forward": 0, "checkpointed": 0}
    assert len(_loops(jaxpr.jaxpr)) == 1
    assert len(_vocab_wide_dots(jaxpr.jaxpr)) == 1


def test_per_row_losses_keep_the_checkpointed_two_loops():
    hidden, table, ids = _tiny_operands()

    def total(h, w):
        return jnp.sum(chunked_lm_head_loss(
            h[:, :-1], w, ids[:, 1:], padding_idx=-1,
            chunk_rows=_TCHUNK) ** 2)

    before = _lm_head_counters()
    jaxpr = jax.make_jaxpr(jax.grad(total, argnums=(0, 1)))(hidden, table)
    assert _counted(before) == {"grad_with_forward": 0, "checkpointed": 1}
    assert len(_loops(jaxpr.jaxpr)) == 2
    # logits, logits again, d hidden, d table
    assert len(_vocab_wide_dots(jaxpr.jaxpr)) == 4
    before = _lm_head_counters()
    jax.make_jaxpr(total)(hidden, table)
    assert _counted(before) == {"grad_with_forward": 0, "checkpointed": 0}


def test_train_step_counts_the_factory_path_once(rng):
    """The cell's recipe at a CPU size: one differentiated call of the
    factory's loss a traced step, none of the checkpointed path."""
    import apex_tpu.nn as nn
    from apex_tpu.models import GptModel
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.training import make_train_step

    nn.manual_seed(5)
    m = GptModel(vocab_size=V, hidden=E, layers=1, heads=4,
                 max_positions=16, dropout=0.0, attn_dropout=0.0,
                 output_hidden=True)
    opt = FusedAdam(list(m.parameters()), lr=1e-3)
    s = make_train_step(m, opt, make_chunked_lm_loss(vocab_size=V,
                                                     padding_idx=-1),
                        half_dtype=jnp.bfloat16, loss_scale=1.0)
    ids = jnp.asarray(rng.integers(0, V, (4, 16)))
    before = _lm_head_counters()
    assert np.isfinite(float(s(ids, ids)))
    assert _counted(before) == {"grad_with_forward": 1, "checkpointed": 0}
