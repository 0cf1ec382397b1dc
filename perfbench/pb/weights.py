"""Weights from the seed, made on the device in one jitted call.

The leaves, their shapes and their draw are the family's
(``perfbench/families/<builder>.py``: ``draw``), in the benchmark's own
layout, which the plain reference reads directly; the family's
``to_program`` converts it into the program's layout inside the same
call.  Nothing here imports the program.
"""
from __future__ import annotations

import functools
import json


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (the driver's are past
    2**31, which a 32-bit key constructor refuses)."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _maker(family, cfg_json, dtype_name, convert):
    import jax
    import jax.numpy as jnp
    cfg = json.loads(cfg_json)

    def make(key):
        leaves = family.draw(cfg, key, jnp.dtype(dtype_name))
        return convert(leaves) if convert is not None else leaves
    return jax.jit(make)


def make_weights(family, cfg, seed: int, dtype="float32", convert=None):
    """All leaves of ``cfg`` in one jitted call from ``seed``, in
    ``dtype`` (the type they are trained or served in).  ``convert``
    (hashable, traced inside the same call) maps the dict into another
    layout."""
    import jax.numpy as jnp
    fn = _maker(family, json.dumps(cfg, sort_keys=True),
                jnp.dtype(dtype).name, convert)
    return fn(seed_key(seed))
