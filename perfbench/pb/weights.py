"""Weights from the seed, made on the device in one jitted call.

The layout here is the benchmark's own (the published GPT-2 one: Conv1D
matrices are (in, out), ``c_attn`` is q | k | v with heads major inside
each).  The plain reference reads it directly; ``pb.sut`` converts it
into the program's layout.  Nothing here imports the program.
"""
from __future__ import annotations

import functools
import math


def leaf_shapes(cfg) -> dict:
    e, v, p = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    f = cfg.get("n_inner") or 4 * e
    shapes = {"wte": (v, e), "wpe": (p, e)}
    for i in range(cfg["n_layer"]):
        h = f"h.{i}."
        shapes.update({
            h + "ln_1.g": (e,), h + "ln_1.b": (e,),
            h + "attn.c_attn.w": (e, 3 * e),
            h + "attn.c_proj.w": (e, e),
            h + "ln_2.g": (e,), h + "ln_2.b": (e,),
            h + "mlp.c_fc.w": (e, f), h + "mlp.c_fc.b": (f,),
            h + "mlp.c_proj.w": (f, e), h + "mlp.c_proj.b": (e,),
        })
    shapes.update({"ln_f.g": (e,), "ln_f.b": (e,)})
    return shapes


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (the driver's are past
    2**31, which a 32-bit key constructor refuses)."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _draw(cfg, key, dtype):
    import jax
    import jax.numpy as jnp
    std = cfg.get("initializer_range", 0.02)
    out_std = std / math.sqrt(2 * cfg["n_layer"])
    shapes = leaf_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    leaves = {}
    for k, (name, shape) in zip(keys, shapes.items()):
        x = jax.random.normal(k, shape, jnp.float32)
        if name.endswith(".g"):
            x = 1.0 + std * x
        elif name.endswith("c_proj.w"):
            x = out_std * x
        else:
            x = std * x
        leaves[name] = x.astype(dtype)
    return leaves


@functools.lru_cache(maxsize=None)
def _maker(cfg_key, dtype_name, convert):
    import jax
    import jax.numpy as jnp
    cfg = dict(cfg_key)

    def make(key):
        leaves = _draw(cfg, key, jnp.dtype(dtype_name))
        return convert(leaves) if convert is not None else leaves
    return jax.jit(make)


def _cfg_key(cfg):
    return tuple(sorted((k, cfg[k]) for k in (
        "n_embd", "n_layer", "n_head", "vocab_size", "n_positions",
        "initializer_range") if k in cfg))


def make_weights(cfg, seed: int, dtype="float32", convert=None):
    """All leaves of ``cfg`` in one jitted call from ``seed``, in
    ``dtype`` (the type they are trained or served in).  ``convert``
    (hashable, traced inside the same call) maps the dict into another
    layout."""
    import jax.numpy as jnp
    fn = _maker(_cfg_key(cfg), jnp.dtype(dtype).name, convert)
    return fn(seed_key(seed))
