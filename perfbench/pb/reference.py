"""The plain reference of the GPT-2 configurations (their ``reference``
key names this file): GPT-2 as published, in straightforward
``jax.numpy`` and float32 at ``highest`` matmul precision.  No kernels,
no cache, no batching tricks; it imports nothing of the program and
takes only the benchmark's own weights (``families/gpt.py``'s leaves).

Its entries are ``train_reference`` and ``served_token_gaps``; what any
model's reference needs (the control's rounding, the optimizer's
update, the gap arithmetic) is ``pb.refmath``.

Departures from the published model, both stated in the configuration
files and listed under ``reduced``: no q/k/v/out-projection biases,
dropout 0.

``quant="int8"`` or ``"fp8"`` is the *control* (``pb.refmath``): the
same reference with every linear layer's matrix multiplications
computed one precision down.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from pb.refmath import (HI as _HI, follow_adamw, gaps_and_margins, mm as _mm,
                        straight_through as _straight_through)


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


def train_reference(cfg, w0, batches, block_rows=4, quant=None):
    """Follow ``len(batches)`` optimizer steps from ``w0`` (float32
    leaves), in blocks of ``block_rows`` sequences so that float32
    activations fit beside nothing else on one chip
    (:func:`pb.refmath.follow_adamw` says what comes back)."""
    return follow_adamw(_grad_fn(_sizes_key(cfg), quant), cfg["train"], w0,
                        batches, block_rows)


@functools.lru_cache(maxsize=None)
def _gap_fn(cfg_key, quant_pick):
    cfg = dict(cfg_key)

    def gaps(w, ids, picked):
        """``ids (R, S)``; ``picked (R, S)`` the token chosen after each
        position.  Gap at (r, i): the reference's best logit there minus
        the reference's logit of ``picked[r, i]``; margin: the
        reference's best minus its second best.  ``w`` comes in the
        type it is served in and is widened here."""
        w = {k: a.astype(jnp.float32) for k, a in w.items()}
        lg = logits(cfg, w, ids)
        if quant_pick is not None:
            picked = jnp.argmax(logits(cfg, w, ids, quant_pick), -1)
        return gaps_and_margins(lg, picked)
    return jax.jit(gaps)


def served_token_gaps(cfg, w, ids, picked, control=None):
    """``w``: the benchmark's leaves in the type they are served in.
    ``(gaps, margins)`` per position: the gap by which the picked
    token's float32 reference logit lies below the reference's best, and
    the margin of the reference's best over its second best (how close
    the call was).  With ``control`` the picked tokens are replaced by
    the lower-precision reference's own first choices at the same
    positions (teacher-forced)."""
    return _gap_fn(_sizes_key(cfg), control)(w, ids, picked)
