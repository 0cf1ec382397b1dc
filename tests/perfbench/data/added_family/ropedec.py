"""A third model family, added by a test as files alone, for the train
kind: an RMSNorm / gated-MLP decoder with rotary positions and grouped
keys and values (``apex_tpu.models.llama.LlamaModel``, the second
decoder ``make_train_step`` and the chunked loss take today).  Nothing
of it is GPT-2's: no position table, no bias, no LayerNorm, an untied
head, another class of the program, and leaves that are the program's
own layout already.  ``ServeEngine`` refuses blocks with rotary
positions, so this family has a train cell only; ``biasgpt.py`` is the
one that is served.  Not a configuration of the benchmark.
"""
from __future__ import annotations

import functools
import math

READS = ("vocab", "context", "width", "depth", "q_heads", "kv_heads",
         "ffn_width", "rope_theta", "rms_eps", "init_std")


def vocab(cfg) -> int:
    return cfg["vocab"]


def max_positions(cfg) -> int:
    return cfg["context"]


def tiny(cfg) -> dict:
    return dict(vocab=173, context=64, width=32, depth=2, q_heads=4,
                kv_heads=2, ffn_width=56)


def tiny_limits(kind: str, limits: dict) -> dict:
    """At a width of 32 the bf16 step's first gradient reads 2.4e-3 in
    the median leaf (the worst leaf 4.6e-3, the losses 1e-5): wider
    than the kind's limit for the median, which was read from GPT-2 at
    a width of 64."""
    return dict(limits, grad1_median_gap=6e-3)


_PER_LAYER = ("attn_norm", "wq", "wk", "wv", "wo", "ffn_norm", "w_gate",
              "w_up", "w_down")


def leaf_shapes(cfg) -> dict:
    e, f = cfg["width"], cfg["ffn_width"]
    d = e // cfg["q_heads"]
    kv = cfg["kv_heads"] * d
    per_layer = {"attn_norm": (e,), "wq": (e, e), "wk": (kv, e),
                 "wv": (kv, e), "wo": (e, e), "ffn_norm": (e,),
                 "w_gate": (f, e), "w_up": (f, e), "w_down": (e, f)}
    shapes = {"embed": (cfg["vocab"], e)}
    for i in range(cfg["depth"]):
        shapes.update({f"{i}.{k}": per_layer[k] for k in _PER_LAYER})
    shapes.update({"norm": (e,), "head": (cfg["vocab"], e)})
    return shapes


def draw(cfg, key, dtype):
    import jax
    import jax.numpy as jnp
    std = cfg["init_std"]
    shapes = leaf_shapes(cfg)
    leaves = {}
    for k, (name, shape) in zip(jax.random.split(key, len(shapes)),
                                shapes.items()):
        x = std * jax.random.normal(k, shape, jnp.float32)
        if name.endswith("norm"):
            x = 1.0 + x
        elif name.endswith(("wo", "w_down")):
            x = x / math.sqrt(2 * cfg["depth"])
        leaves[name] = x.astype(dtype)
    return leaves


def program_leaf_names(cfg) -> list:
    return list(leaf_shapes(cfg))


@functools.lru_cache(maxsize=None)
def _in_order(names: tuple):
    return lambda leaves: [leaves[n] for n in names]


def to_program(cfg):
    """The leaves are the program's (out, in) matrices already: only
    their order is the program's (the same function for the same sizes,
    as a jit's key wants)."""
    return _in_order(tuple(program_leaf_names(cfg)))


def model(cfg, **kw):
    from apex_tpu.models.llama import LlamaModel
    return LlamaModel(
        vocab_size=cfg["vocab"], hidden=cfg["width"], layers=cfg["depth"],
        heads=cfg["q_heads"], kv_heads=cfg["kv_heads"],
        intermediate=cfg["ffn_width"], max_positions=cfg["context"],
        rope_theta=cfg["rope_theta"], eps=cfg["rms_eps"], **kw)


# -- counts --------------------------------------------------------------------


def matmul_params(cfg) -> int:
    e, f = cfg["width"], cfg["ffn_width"]
    kv = cfg["kv_heads"] * (e // cfg["q_heads"])
    return cfg["depth"] * (2 * e * e + 2 * kv * e + 3 * e * f) \
        + cfg["vocab"] * e


def train_flops_per_token(cfg, seq_len: int) -> float:
    attn = 4.0 * cfg["depth"] * cfg["width"] * (seq_len + 1) / 2.0
    return 6.0 * matmul_params(cfg) + 3.0 * attn
