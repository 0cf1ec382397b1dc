"""What every plain reference shares, whatever the model: the control's
rounding of matrix products one precision down, the optimizer's update,
per-leaf norms, and the arithmetic of a served token's gap.  Straight
``jax.numpy`` in float32 at ``highest`` matmul precision; it imports
nothing of the program.

``quant="int8"`` or ``"fp8"`` is the *control*: a reference with every
linear layer's matrix multiplications (forward, and both backward
products) computed on operands rounded to int8 or to float8 (per-row
absmax scaling; the step below bfloat16 that would tempt a later PR).
It exists to show that the comparison deciding ``correct`` fails when
it should.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _round_int8(x, axis):
    """``x`` rounded to 127 levels per slice along ``axis`` (absmax
    scaling)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _round_fp8(x, axis, dtype):
    """``x`` rounded to a float8 format after scaling each slice along
    ``axis`` to the format's range."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(dtype).astype(x.dtype) * scale


def _rounders(quant):
    """``(forward operand rounding, gradient rounding)`` of a control
    precision: int8 everywhere, or float8 e4m3 forward with e5m2
    gradients, as float8 training recipes do."""
    if quant == "int8":
        return _round_int8, _round_int8
    if quant == "fp8":
        return (lambda x, a: _round_fp8(x, a, jnp.float8_e4m3fn),
                lambda x, a: _round_fp8(x, a, jnp.float8_e5m2))
    raise ValueError(f"unknown control precision {quant!r}")


@functools.lru_cache(maxsize=None)
def _low_precision_matmul(quant):
    """``x (..., K) @ w (K, N)`` computed one precision down: both
    operands of the forward product, and of the two backward products
    (the gradient that arrives is rounded too), are rounded per row."""
    fwd_round, grad_round = _rounders(quant)

    @jax.custom_vjp
    def mm(x, w):
        return jnp.matmul(fwd_round(x, -1), fwd_round(w, 0), precision=HI)

    def fwd(x, w):
        xq, wq = fwd_round(x, -1), fwd_round(w, 0)
        return jnp.matmul(xq, wq, precision=HI), (xq, wq)

    def bwd(res, dy):
        xq, wq = res
        dyq = grad_round(dy, -1)
        dx = jnp.matmul(dyq, wq.T, precision=HI)
        k = xq.shape[-1]
        dw = jnp.matmul(xq.reshape(-1, k).T,
                        dyq.reshape(-1, dyq.shape[-1]), precision=HI)
        return dx, dw
    mm.defvjp(fwd, bwd)
    return mm


def straight_through(quant):
    """The control's rounding of attention's operands (queries, keys,
    probabilities, values; a cache kept one precision down): forward
    only, the gradient passes straight through."""
    if quant is None:
        return lambda x, axis: x
    fwd_round, _ = _rounders(quant)
    return lambda x, axis: x + jax.lax.stop_gradient(
        fwd_round(x, axis) - x)


def mm(x, w, quant):
    """``x (..., K) @ w (K, N)``."""
    if quant is None:
        return jnp.matmul(x, w, precision=HI)
    return _low_precision_matmul(quant)(x, w)


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps", "wd"))
def adamw_update(w, m, v, g, step, *, lr, b1, b2, eps, wd):
    """Adam with decoupled weight decay on every leaf, bias-corrected:
    ``p -= lr * (m_hat / (sqrt(v_hat) + eps) + wd * p)``."""
    bc1 = 1.0 - b1 ** step
    bc2 = 1.0 - b2 ** step

    def leaf(p, m_, v_, g_):
        m_ = b1 * m_ + (1.0 - b1) * g_
        v_ = b2 * v_ + (1.0 - b2) * g_ * g_
        upd = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps) + wd * p
        return p - lr * upd, m_, v_
    out = {k: leaf(w[k], m[k], v[k], g[k]) for k in w}
    return ({k: o[0] for k, o in out.items()},
            {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()})


def leaf_norms(tree) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for k, a in tree.items()}


def mean_loss_and_grads(grad_fn, w, ids, block_rows):
    """Mean next-token loss over ``ids (B, S)`` and its gradient, from
    ``grad_fn(w, rows) -> (summed loss, its gradient)`` called on blocks
    of ``block_rows`` sequences."""
    n_rows, s = ids.shape
    total = 0.0
    grads = None
    for r in range(0, n_rows, block_rows):
        l, g = grad_fn(w, ids[r:r + block_rows])
        total = total + l
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    n = n_rows * (s - 1)
    return total / n, jax.tree.map(lambda g: g / n, grads)


def follow_adamw(grad_fn, train, w0, batches, block_rows=4):
    """Follow ``len(batches)`` steps of AdamW (``train``: the
    configuration's recipe) from ``w0`` (float32 leaves).  Returns
    per-step losses, the per-leaf norms of the first gradient, and the
    per-leaf norms of the parameters' change after the last step."""
    hyper = dict(lr=train["lr"], b1=train["betas"][0], b2=train["betas"][1],
                 eps=train["eps"], wd=train["weight_decay"])
    w = w0
    m = jax.tree.map(jnp.zeros_like, w0)
    v = jax.tree.map(jnp.zeros_like, w0)
    losses, g1 = [], None
    for i, ids in enumerate(batches):
        loss, g = mean_loss_and_grads(grad_fn, w, ids, block_rows)
        losses.append(float(loss))
        if i == 0:
            g1 = {k: float(n) for k, n in leaf_norms(g).items()}
        w, m, v = adamw_update(w, m, v, g, jnp.float32(i + 1), **hyper)
    delta = {k: float(n) for k, n in leaf_norms(
        {k: w[k] - w0[k] for k in w}).items()}
    return {"losses": losses, "grad1_norms": g1, "delta_norms": delta}


def gaps_and_margins(lg, picked):
    """``lg (..., V)`` reference logits, ``picked (...)`` the token
    chosen at each position: the gap by which the picked token's logit
    lies below the best, and the margin of the best over the second
    best."""
    top2 = jax.lax.top_k(lg, 2)[0]
    mine = jnp.take_along_axis(lg, picked[..., None], axis=-1)[..., 0]
    return top2[..., 0] - mine, top2[..., 0] - top2[..., 1]
