"""YaRN rotary tables: the inverse frequencies, the cos/sin tables with
the attention factor on them, and the softmax scale, shared by the
models whose layers rotate by them (``latent_moe.py``: every layer;
``gqa_moe.py``: the full-attention layers, the window layers rotate by
plain RoPE)."""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

_f32 = jnp.float32


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, theta, factor, original_max, beta_fast, beta_slow):
    """The ``dim // 2`` inverse frequencies of YaRN: ``theta_i`` where a
    pair turns more than ``beta_fast`` times over the original context,
    ``theta_i / factor`` where it turns fewer than ``beta_slow`` times,
    a linear ramp between."""
    i = np.arange(0, dim, 2, dtype=np.float64)
    extra = 1.0 / theta ** (i / dim)

    def turns_at(n):            # the pair index that turns n times
        return dim * math.log(original_max / (n * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (extra / factor) * ramp + extra * (1.0 - ramp)


def yarn_tables(positions, dim, rope: dict):
    """cos/sin ``(..., dim)`` fp32 for ``positions (...,)``, halves
    duplicated (the rotate-half convention of ``llama.apply_rope``),
    times ``mscale / mscale_all_dim``'s ratio."""
    inv = jnp.asarray(yarn_inv_freq(
        dim, rope["rope_theta"], rope["factor"],
        rope["original_max_position_embeddings"], rope["beta_fast"],
        rope["beta_slow"]), _f32)
    ang = positions.astype(_f32)[..., None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)
    m = yarn_mscale(rope["factor"], rope.get("mscale", 1.0)) \
        / yarn_mscale(rope["factor"], rope.get("mscale_all_dim", 0.0) or 0.0)
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def yarn_softmax_scale(qk_dim, rope: dict) -> float:
    m = yarn_mscale(rope["factor"], rope.get("mscale_all_dim", 0.0) or 0.0)
    return qk_dim ** -0.5 * m * m
