"""The plain reference: GPT-2 as published, in straightforward
``jax.numpy`` and float32 at ``highest`` matmul precision.  No kernels,
no cache, no batching tricks; it imports nothing of the program and
takes only the benchmark's own weights (``pb.weights``).

Departures from the published model, both stated in the configuration
files and listed under ``reduced``: no q/k/v/out-projection biases,
dropout 0.

``quant="int8"`` or ``"fp8"`` is the *control*: the same reference with
every linear layer's matrix multiplications (forward, and both backward
products) computed on operands rounded to int8 or to float8 (per-row
absmax scaling; the step below bfloat16 that would tempt a later PR).  It exists to show
that the comparison deciding ``correct`` fails when it should.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


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
        return jnp.matmul(fwd_round(x, -1), fwd_round(w, 0), precision=_HI)

    def fwd(x, w):
        xq, wq = fwd_round(x, -1), fwd_round(w, 0)
        return jnp.matmul(xq, wq, precision=_HI), (xq, wq)

    def bwd(res, dy):
        xq, wq = res
        dyq = grad_round(dy, -1)
        dx = jnp.matmul(dyq, wq.T, precision=_HI)
        k = xq.shape[-1]
        dw = jnp.matmul(xq.reshape(-1, k).T,
                        dyq.reshape(-1, dyq.shape[-1]), precision=_HI)
        return dx, dw
    mm.defvjp(fwd, bwd)
    return mm


def _straight_through(quant):
    """The control's rounding of attention's operands (queries, keys,
    probabilities, values; a cache kept one precision down): forward
    only, the gradient passes straight through."""
    if quant is None:
        return lambda x, axis: x
    fwd_round, _ = _rounders(quant)
    return lambda x, axis: x + jax.lax.stop_gradient(
        fwd_round(x, axis) - x)


def _mm(x, w, quant):
    """``x (..., K) @ w (K, N)``."""
    if quant is None:
        return jnp.matmul(x, w, precision=_HI)
    return _low_precision_matmul(quant)(x, w)


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _block(cfg, w, i, x, quant):
    """One pre-LN block on ``x (B, S, E)``."""
    e, nh = cfg["n_embd"], cfg["n_head"]
    d = e // nh
    eps = cfg["layer_norm_epsilon"]
    h = f"h.{i}."
    b, s, _ = x.shape
    a = _ln(x, w[h + "ln_1.g"], w[h + "ln_1.b"], eps)
    qkv = _mm(a, w[h + "attn.c_attn.w"], quant)           # (B, S, 3E)
    q, k, v = (t.reshape(b, s, nh, d).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    low = _straight_through(quant)
    scores = jnp.einsum("bhqd,bhkd->bhqk", low(q, -1), low(k, -1),
                        precision=_HI) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", low(probs, -1), low(v, -1),
                   precision=_HI)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, e)
    x = x + _mm(o, w[h + "attn.c_proj.w"], quant)
    a = _ln(x, w[h + "ln_2.g"], w[h + "ln_2.b"], eps)
    m = _gelu_new(_mm(a, w[h + "mlp.c_fc.w"], quant)
                  + w[h + "mlp.c_fc.b"])
    return x + _mm(m, w[h + "mlp.c_proj.w"], quant) \
        + w[h + "mlp.c_proj.b"]


def hidden_states(cfg, w, ids, quant=None, remat=False):
    """Final-LayerNorm hidden states ``(B, S, E)`` for ``ids (B, S)``."""
    s = ids.shape[1]
    x = w["wte"][ids] + w["wpe"][jnp.arange(s)][None]
    for i in range(cfg["n_layer"]):
        f = functools.partial(_block, cfg, i=i, quant=quant)
        if remat:
            x = jax.checkpoint(lambda w_, x_, f=f: f(w_, x=x_))(w, x)
        else:
            x = f(w, x=x)
    return _ln(x, w["ln_f.g"], w["ln_f.b"], cfg["layer_norm_epsilon"])


def logits(cfg, w, ids, quant=None):
    """Tied-head logits ``(B, S, V)``."""
    return _mm(hidden_states(cfg, w, ids, quant), w["wte"].T, quant)


def lm_loss_sum(cfg, w, ids, quant=None):
    """Sum over the rows of ``ids (B, S)`` of the next-token
    cross-entropy (B * (S - 1) terms), one sequence's logits at a
    time."""
    hid = hidden_states(cfg, w, ids, quant, remat=True)

    def row(carry, hx):
        h, x = hx
        lg = _mm(h[:-1], w["wte"].T, quant)               # (S-1, V)
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, x[1:, None], axis=-1)[:, 0]
        return carry + jnp.sum(lse - picked), None
    total, _ = jax.lax.scan(jax.checkpoint(row), jnp.float32(0.0),
                            (hid, ids))
    return total


def _sizes_key(cfg) -> tuple:
    """The configuration's numbers, hashable: the key of the compiled
    reference programs."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float))))


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg_key, quant):
    cfg = dict(cfg_key)
    return jax.jit(jax.value_and_grad(
        lambda w, ids: lm_loss_sum(cfg, w, ids, quant)))


def loss_and_grads(cfg, w, ids, block_rows=4, quant=None):
    """Mean next-token loss over ``ids (B, S)`` and its gradient, in
    blocks of ``block_rows`` sequences so that float32 activations fit
    beside nothing else on one chip."""
    fn = _grad_fn(_sizes_key(cfg), quant)
    n_rows, s = ids.shape
    total = 0.0
    grads = None
    for r in range(0, n_rows, block_rows):
        l, g = fn(w, ids[r:r + block_rows])
        total = total + l
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    n = n_rows * (s - 1)
    return total / n, jax.tree.map(lambda g: g / n, grads)


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


def train_reference(cfg, w0, batches, block_rows=4, quant=None):
    """Follow ``len(batches)`` optimizer steps from ``w0`` (float32
    leaves).  Returns per-step losses, the per-leaf norms of the first
    gradient, and the per-leaf norms of the parameters' change after
    the last step."""
    tr = cfg["train"]
    hyper = dict(lr=tr["lr"], b1=tr["betas"][0], b2=tr["betas"][1],
                 eps=tr["eps"], wd=tr["weight_decay"])
    w = w0
    m = jax.tree.map(jnp.zeros_like, w0)
    v = jax.tree.map(jnp.zeros_like, w0)
    losses, g1 = [], None
    for i, ids in enumerate(batches):
        loss, g = loss_and_grads(cfg, w, ids, block_rows, quant)
        losses.append(float(loss))
        if i == 0:
            g1 = {k: float(n) for k, n in leaf_norms(g).items()}
        w, m, v = adamw_update(w, m, v, g, jnp.float32(i + 1), **hyper)
    delta = {k: float(n) for k, n in leaf_norms(
        {k: w[k] - w0[k] for k in w}).items()}
    return {"losses": losses, "grad1_norms": g1, "delta_norms": delta}


@functools.lru_cache(maxsize=None)
def _gap_fn(cfg_key, quant_pick):
    cfg = dict(cfg_key)

    def gaps(w, ids, picked):
        """``ids (R, S)``; ``picked (R, S)`` the token chosen after each
        position.  Gap at (r, i): the reference's best logit there minus
        the reference's logit of ``picked[r, i]``; margin: the
        reference's best minus its second best."""
        lg = logits(cfg, w, ids)
        if quant_pick is not None:
            picked = jnp.argmax(logits(cfg, w, ids, quant_pick), -1)
        top2 = jax.lax.top_k(lg, 2)[0]
        mine = jnp.take_along_axis(lg, picked[..., None], axis=-1)[..., 0]
        return top2[..., 0] - mine, top2[..., 0] - top2[..., 1]
    return jax.jit(gaps)


def served_token_gaps(cfg, w, ids, picked, control=None):
    """``(gaps, margins)`` per position: the gap by which the picked
    token's float32 reference logit lies below the reference's best, and
    the margin of the reference's best over its second best (how close
    the call was).  With ``control`` the picked tokens are replaced by
    the lower-precision reference's own first choices at the same
    positions (teacher-forced)."""
    return _gap_fn(_sizes_key(cfg), control)(w, ids, picked)
