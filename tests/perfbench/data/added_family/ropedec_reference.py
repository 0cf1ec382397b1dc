"""The plain reference of the added rotary family (``ropedec.py``):
RMSNorm, rotary positions in the half-rotation convention, grouped keys
and values, a SiLU-gated feed-forward and an untied head; float32 at
``highest``.  It imports nothing of the program.  The family is trained
and not served, so ``train_reference`` is its one entry."""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from pb.refmath import HI, follow_adamw, mm, straight_through


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _rope(x, theta):
    """``x (B, H, S, D)`` rotated by its positions 0..S-1."""
    s, d = x.shape[-2], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _block(cfg, w, i, x, quant):
    nq, nkv = cfg["q_heads"], cfg["kv_heads"]
    b, s, e = x.shape
    d = e // nq
    a = _rms(x, w[f"{i}.attn_norm"], cfg["rms_eps"])

    def heads(name, n):
        return mm(a, w[f"{i}.{name}"].T, quant).reshape(b, s, n, d) \
            .transpose(0, 2, 1, 3)
    q = _rope(heads("wq", nq), cfg["rope_theta"])
    k = _rope(heads("wk", nkv), cfg["rope_theta"])
    v = heads("wv", nkv)
    k, v = (jnp.repeat(t, nq // nkv, axis=1) for t in (k, v))
    low = straight_through(quant)
    scores = jnp.einsum("bhqd,bhkd->bhqk", low(q, -1), low(k, -1),
                        precision=HI) / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", low(jax.nn.softmax(scores, -1), -1),
                   low(v, -1), precision=HI)
    x = x + mm(o.transpose(0, 2, 1, 3).reshape(b, s, e), w[f"{i}.wo"].T,
               quant)
    a = _rms(x, w[f"{i}.ffn_norm"], cfg["rms_eps"])
    gated = jax.nn.silu(mm(a, w[f"{i}.w_gate"].T, quant)) \
        * mm(a, w[f"{i}.w_up"].T, quant)
    return x + mm(gated, w[f"{i}.w_down"].T, quant)


def _loss_sum(cfg, w, ids, quant):
    x = w["embed"][ids]
    for i in range(cfg["depth"]):
        x = _block(cfg, w, i, x, quant)
    lg = mm(_rms(x, w["norm"], cfg["rms_eps"]), w["head"].T, quant)[:, :-1]
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
