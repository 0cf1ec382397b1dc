"""A second model family, added by a test as a later PR would add one:
as files alone (``tests/perfbench/test_perfbench_harness.py`` copies this
to ``perfbench/families/biasgpt.py`` of a temporary checkout).  Not a
configuration of the benchmark.

The model is not GPT-2's: its keys are other keys, its attention carries
q/k/v and output-projection biases (in the program that selects the
materializing attention, not the flash kernels the GPT-2 cells time),
its feed-forward width is no multiple of four, its matrices are kept
(out, in), and it holds one shard of a vocabulary that is sliced over
``vocab_shards`` chips: the embedding, the head, the traffic's token ids
and the loss are over the slice.  The program's model is a ``GptModel``
all the same, because it is what both ``make_train_step`` with the
chunked loss and ``ServeEngine`` take today; the engine refuses blocks
with rotary positions, so an RMSNorm / gated-MLP decoder could be
trained here and not served.
"""
from __future__ import annotations

import functools
import math

READS = ("published_vocab_size", "vocab_shards", "max_position_embeddings",
         "hidden_size", "num_hidden_layers", "num_attention_heads",
         "intermediate_size", "init_std")


# -- sizes -------------------------------------------------------------------


def vocab(cfg) -> int:
    return cfg["published_vocab_size"] // cfg["vocab_shards"]


def max_positions(cfg) -> int:
    return cfg["max_position_embeddings"]


def tiny(cfg) -> dict:
    return dict(hidden_size=48, num_hidden_layers=2, num_attention_heads=4,
                intermediate_size=80, max_position_embeddings=128,
                published_vocab_size=1208)


def tiny_limits(kind: str, limits: dict) -> dict:
    """The fused q | k | v bias is one leaf of the program, and a key's
    bias has no gradient under softmax: float32 at ``highest`` reads
    exactly nought for that third and bf16 reads round-off, which Adam
    turns into a full step.  So the worst leaf's change is not judged
    for this family at its tiny sizes (it reads 0.18 in that leaf, 5e-4
    in the median leaf), and the median leaf's first gradient reads
    1.4e-3."""
    if kind != "train":
        return limits
    return dict(limits, delta3_gap=None, grad1_median_gap=3e-3)


# -- weights -------------------------------------------------------------------

_PER_LAYER = ("norm1.g", "norm1.b", "attn.qkv.w", "attn.out.w", "attn.qkv.b",
              "attn.out.b", "norm2.g", "norm2.b", "mlp.up.w", "mlp.up.b",
              "mlp.down.w", "mlp.down.b")


def leaf_shapes(cfg) -> dict:
    e, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = {"norm1.g": (e,), "norm1.b": (e,), "attn.qkv.w": (3 * e, e),
                 "attn.out.w": (e, e), "attn.qkv.b": (3 * e,),
                 "attn.out.b": (e,), "norm2.g": (e,), "norm2.b": (e,),
                 "mlp.up.w": (f, e), "mlp.up.b": (f,), "mlp.down.w": (e, f),
                 "mlp.down.b": (e,)}
    shapes = {"embed.tokens": (vocab(cfg), e),
              "embed.positions": (max_positions(cfg), e)}
    for i in range(cfg["num_hidden_layers"]):
        shapes.update({f"layers.{i}.{k}": per_layer[k] for k in _PER_LAYER})
    shapes.update({"final_norm.g": (e,), "final_norm.b": (e,)})
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
        if name.endswith(".g"):
            x = 1.0 + x
        elif name.endswith(("out.w", "down.w")):
            x = x / math.sqrt(2 * cfg["num_hidden_layers"])
        leaves[name] = x.astype(dtype)
    return leaves


def program_leaf_names(cfg) -> list:
    names = ["embed.tokens", "embed.positions"]
    for i in range(cfg["num_hidden_layers"]):
        names += [f"layers.{i}.{k}" for k in _PER_LAYER]
    return names + ["final_norm.g", "final_norm.b"]


@functools.lru_cache(maxsize=None)
def _converter(n_layer: int, n_head: int):
    names = program_leaf_names({"num_hidden_layers": n_layer})

    def convert(leaves):
        out = []
        for name in names:
            x = leaves[name]
            if ".attn.qkv." in name:
                # rows q | k | v, heads major -> the program's rows
                # interleaved [head, (q, k, v), d]
                d = x.shape[0] // (3 * n_head)
                x = x.reshape((3, n_head, d) + x.shape[1:]).swapaxes(0, 1) \
                     .reshape(x.shape)
            out.append(x)
        return out
    return convert


def to_program(cfg):
    return _converter(cfg["num_hidden_layers"], cfg["num_attention_heads"])


# -- the program's model -------------------------------------------------------


def model(cfg, **kw):
    from apex_tpu.models import GptModel
    return GptModel(
        vocab_size=vocab(cfg), hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        intermediate=cfg["intermediate_size"],
        max_positions=cfg["max_position_embeddings"], dropout=0.0,
        attn_dropout=0.0, attn_bias=True, **kw)


# -- counts --------------------------------------------------------------------


def matmul_params(cfg) -> int:
    e, f = cfg["hidden_size"], cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (4 * e * e + 2 * e * f) \
        + vocab(cfg) * e


def attn_flops_fwd(cfg, q_len, kv_len) -> float:
    return 4.0 * cfg["num_hidden_layers"] * cfg["hidden_size"] * q_len * kv_len


def train_flops_per_token(cfg, seq_len: int) -> float:
    return 6.0 * matmul_params(cfg) \
        + 3.0 * attn_flops_fwd(cfg, 1, (seq_len + 1) / 2.0)


def decode_step_flops(cfg, tick: dict) -> float:
    return 2.0 * matmul_params(cfg) * tick["decode_batch"] \
        + attn_flops_fwd(cfg, 1, tick["kv_tokens"])
