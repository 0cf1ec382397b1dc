"""The plain reference of the added family (``biasgpt.py``): a pre-LN
decoder with biases on every projection, learned positions, tanh GELU
and a tied head over its slice of the vocabulary; float32 at ``highest``.
It imports nothing of the program; what every reference shares comes
from ``pb.refmath``."""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from pb.refmath import (HI, follow_adamw, gaps_and_margins, mm,
                        straight_through)


def _ln(x, g, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _block(cfg, w, i, x, quant):
    nh = cfg["num_attention_heads"]
    b, s, e = x.shape
    d = e // nh
    p = f"layers.{i}."
    a = _ln(x, w[p + "norm1.g"], w[p + "norm1.b"])
    qkv = mm(a, w[p + "attn.qkv.w"].T, quant) + w[p + "attn.qkv.b"]
    q, k, v = (t.reshape(b, s, nh, d).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    low = straight_through(quant)
    scores = jnp.einsum("bhqd,bhkd->bhqk", low(q, -1), low(k, -1),
                        precision=HI) / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", low(jax.nn.softmax(scores, -1), -1),
                   low(v, -1), precision=HI)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, e)
    x = x + mm(o, w[p + "attn.out.w"].T, quant) + w[p + "attn.out.b"]
    a = _ln(x, w[p + "norm2.g"], w[p + "norm2.b"])
    m = _gelu(mm(a, w[p + "mlp.up.w"].T, quant) + w[p + "mlp.up.b"])
    return x + mm(m, w[p + "mlp.down.w"].T, quant) + w[p + "mlp.down.b"]


def logits(cfg, w, ids, quant=None):
    w = {k: a.astype(jnp.float32) for k, a in w.items()}
    x = w["embed.tokens"][ids] \
        + w["embed.positions"][jnp.arange(ids.shape[1])][None]
    for i in range(cfg["num_hidden_layers"]):
        x = _block(cfg, w, i, x, quant)
    x = _ln(x, w["final_norm.g"], w["final_norm.b"])
    return mm(x, w["embed.tokens"].T, quant)


def _loss_sum(cfg, w, ids, quant):
    lg = logits(cfg, w, ids, quant)[:, :-1]
    picked = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - picked)


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg_json, quant):
    cfg = json.loads(cfg_json)
    return jax.jit(jax.value_and_grad(
        lambda w, ids: _loss_sum(cfg, w, ids, quant)))


def train_reference(cfg, w0, batches, block_rows=4, quant=None):
    return follow_adamw(_grad_fn(json.dumps(cfg, sort_keys=True), quant),
                        cfg["train"], w0, batches, block_rows)


@functools.lru_cache(maxsize=None)
def _gap_fn(cfg_json, control):
    cfg = json.loads(cfg_json)

    def gaps(w, ids, picked):
        lg = logits(cfg, w, ids)
        if control is not None:
            picked = jnp.argmax(logits(cfg, w, ids, control), -1)
        return gaps_and_margins(lg, picked)
    return jax.jit(gaps)


def served_token_gaps(cfg, w, ids, picked, control=None):
    return _gap_fn(json.dumps(cfg, sort_keys=True), control)(w, ids, picked)
