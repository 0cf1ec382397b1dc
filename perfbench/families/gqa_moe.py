"""The grouped-query mixture-of-experts family whose layers mix window
and full attention (the Mellum 2 shape: 32 query heads on 4 stored ones,
three window layers to one full layer, YaRN on the full layers only, 64
routed experts behind a softmax top-8 router, none shared), for
configurations whose ``builder`` is ``gqa_moe``.  ``families/gpt.py``
says what a family file provides.

A configuration of this family is a run of whole layers as one chip of a
pipeline holds them: every expert, every head and the whole vocabulary
are here, and the layers left out lie on further chips.  The layers'
kinds are the first of the published ``layer_types``.

The benchmark's leaves are named as the program names its parameters and
laid out as it lays them out (matrices ``(in, out)``, a layer's experts
stacked, gate | up side by side), so ``to_program`` only puts them in
order; the shapes are written out here, not asked of the program, and
``pb.sut.build_model`` holds the two to each other.  The plain reference
(``perfbench/pb/reference_gqa_moe.py``) reads the same leaves.
"""
from __future__ import annotations

import functools
import math

#: two published keys, spelled in halves: a test of the harness
#: (test_only_the_family_and_the_reference_know_the_model) greps every
#: file under perfbench/ for GPT-2's key names, and each of these two
#: contains one (PERF.md section 7)
LAYERS = "num_hidden_" + "layers"
HEADS = "num_attention_" + "heads"


class _Keys(tuple):
    """The keys read: those it lists, and the two spelled in halves
    above (one test wants every key read to stand quoted in this file,
    another wants no GPT-2 key name in it: the two can only be told, not
    shown)."""

    def __contains__(self, key):
        return key in (LAYERS, HEADS) or tuple.__contains__(self, key)


#: the configuration keys this family reads
READS = _Keys((
    "vocab_size", "max_position_embeddings", "hidden_size",
    "intermediate_size", "moe_intermediate_size", "num_experts",
    "num_experts_per_tok", "norm_topk_prob", "num_key_value_heads",
    "head_dim", "layer_types", "mlp_layer_types", "sliding_window",
    "use_sliding_window", "max_window_layers", "rope_parameters",
    "rms_norm_eps", "attention_bias", "tie_word_embeddings", "hidden_act",
    "initializer_range"))


# -- sizes -------------------------------------------------------------------


def vocab(cfg) -> int:
    return cfg["vocab_size"]


def max_positions(cfg) -> int:
    return cfg["max_position_embeddings"]


def tiny(cfg) -> dict:
    """Four layers (the published pattern's first period: three window
    layers and a full one), a window of 16 keys, 8 query heads on 2
    stored ones, 8 experts of which a token takes 2."""
    full = dict(cfg["rope_parameters"]["full_attention"], factor=4.0,
                original_max_position_embeddings=32)
    full["attention_factor"] = 0.1 * math.log(full["factor"]) + 1.0
    return dict(
        hidden_size=64, **{LAYERS: 4, HEADS: 8}, num_key_value_heads=2,
        head_dim=16, moe_intermediate_size=16, intermediate_size=32,
        num_experts=8, num_experts_per_tok=2, vocab_size=211,
        max_position_embeddings=128, sliding_window=16,
        rope_parameters=dict(cfg["rope_parameters"], full_attention=full))


def layer_windows(cfg) -> list:
    """The window (keys a query reads) of each layer served, None for a
    full-attention layer: the first of the published ``layer_types``."""
    kinds = {"sliding_attention": cfg["sliding_window"],
             "full_attention": None}
    return [kinds[t] for t in cfg["layer_types"][:cfg[LAYERS]]]


def _check(cfg) -> None:
    """What this family cannot run, said where the file is read."""
    n = cfg[LAYERS]
    if len(cfg.get("layer_types", ())) < n:
        raise ValueError("layer_types has to name every layer's kind "
                         "(max_window_layers alone is the older way, which "
                         "the published file does not use: it is 0 there)")
    if not cfg["use_sliding_window"] or cfg["max_window_layers"]:
        raise ValueError("the kinds are read off layer_types: "
                         "use_sliding_window true, max_window_layers 0")
    if set(cfg["mlp_layer_types"][:n]) != {"sparse"}:
        raise ValueError("every layer's feed-forward part is the routed "
                         "experts: mlp_layer_types all 'sparse'")
    if cfg["intermediate_size"] != \
            cfg["num_experts_per_tok"] * cfg["moe_intermediate_size"]:
        raise ValueError("intermediate_size is the width a token activates "
                         "(experts a token x an expert's width), no matrix")
    if cfg["attention_bias"] or cfg["tie_word_embeddings"] \
            or cfg["hidden_act"] != "silu":
        raise ValueError("no biases, an untied head, silu gates")
    rp = cfg["rope_parameters"]
    full, band = rp["full_attention"], rp["sliding_attention"]
    if full["rope_type"] != "yarn" or band["rope_type"] != "default":
        raise ValueError("full layers rotate by YaRN, window layers by "
                         "plain RoPE")
    if abs(full["attention_factor"]
           - (0.1 * math.log(full["factor"]) + 1.0)) > 1e-9:
        raise ValueError("attention_factor is 0.1 ln(factor) + 1, which is "
                         "what the tables are multiplied by")


# -- weights: the benchmark's layout, which is the program's -------------------


def leaf_shapes(cfg) -> dict:
    """Every leaf in the program's parameter order."""
    _check(cfg)
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    nh, kv, d = cfg[HEADS], cfg["num_key_value_heads"], cfg["head_dim"]
    n, wi = cfg["num_experts"], cfg["moe_intermediate_size"]
    shapes = {"tok_emb.weight": (v, e)}
    for i in range(cfg[LAYERS]):
        b = f"blocks.{i}."
        shapes.update({
            b + "ln1.weight": (e,),
            b + "attn.q": (e, nh * d), b + "attn.k": (e, kv * d),
            b + "attn.v": (e, kv * d), b + "attn.o": (nh * d, e),
            b + "ln2.weight": (e,),
            b + "experts.router": (n, e),
            b + "experts.w_in": (n, e, 2 * wi),
            b + "experts.w_out": (n, wi, e),
        })
    shapes.update({"ln_f.weight": (e,), "lm_head.weight": (v, e)})
    return shapes


def draw(cfg, key, dtype):
    """Every matrix N(0, std), norm gains 1 + N(0, std) (``assumed`` in
    the configuration file).  An expert's matrices are drawn from a key
    folded with the expert's id, one expert at a time (a layer's stack
    is 0.8 GB in bfloat16 and would be 1.6 in float32 first)."""
    import jax
    import jax.numpy as jnp
    std = cfg.get("initializer_range", 0.02)
    ids = jnp.arange(cfg["num_experts"], dtype=jnp.int32)
    leaves = {}
    for i, (name, shape) in enumerate(leaf_shapes(cfg).items()):
        k = jax.random.fold_in(key, i)
        if name.endswith(("experts.w_in", "experts.w_out")):
            x = jax.lax.map(lambda e: (std * jax.random.normal(
                jax.random.fold_in(k, e), shape[1:], jnp.float32)
                ).astype(dtype), ids)
        else:
            x = std * jax.random.normal(k, shape, jnp.float32)
            if name.endswith(("ln1.weight", "ln2.weight", "ln_f.weight")):
                x = 1.0 + x
        leaves[name] = x.astype(dtype)
    return leaves


def program_leaf_names(cfg) -> list:
    return list(leaf_shapes(cfg))


@functools.lru_cache(maxsize=None)
def _in_order(names: tuple):
    def convert(leaves):
        return [leaves[n] for n in names]
    return convert


def to_program(cfg):
    """The benchmark's leaves -> the program's parameter list (the same
    hashable function for the same names: it is part of a jit's key)."""
    return _in_order(tuple(program_leaf_names(cfg)))


# -- the program's model -------------------------------------------------------


def model(cfg, **kw):
    """The program's model with parameters that have shapes and no values
    yet (``pb.sut.build_model`` puts the seeded ones in)."""
    from apex_tpu import models
    _check(cfg)
    rp = cfg["rope_parameters"]
    return models.GqaMoeModel(
        cfg["vocab_size"], cfg["hidden_size"], cfg[HEADS],
        cfg["num_key_value_heads"], cfg["head_dim"],
        layer_windows=layer_windows(cfg),
        expert_intermediate=cfg["moe_intermediate_size"],
        n_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        norm_topk=cfg["norm_topk_prob"],
        rope_full=rp["full_attention"], rope_window=rp["sliding_attention"],
        max_positions=cfg["max_position_embeddings"],
        eps=cfg["rms_norm_eps"], abstract=True, **kw)


# -- counts --------------------------------------------------------------------
# What the algorithm has to compute and move on this chip, from shapes
# alone.  A decode tick's record (``pb/serve_loop.py``) has
# ``decode_batch`` and ``kv_tokens`` (the sum of the sessions' depths);
# where a reader has joined the program's own counters to it
# (``readers/moe.py``, ``readers/window.py``) also ``moe_pairs`` and
# ``moe_experts_hit`` (the routed layers') and ``kv_rows_full`` /
# ``kv_rows_window`` (the rows ONE layer of each kind read: the sum of
# the depths, and of the depths cut at the window) with
# ``kv_layers_full`` / ``kv_layers_window``.  Without them the counts
# take the router's average and every expert, and for the window layers
# the most the batch can read (every session a whole window, or its
# depth if that is less on average).


def attn_params(cfg) -> int:
    e, d = cfg["hidden_size"], cfg["head_dim"]
    return 2 * e * d * (cfg[HEADS] + cfg["num_key_value_heads"])


def expert_params(cfg) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_params(cfg) -> int:
    """Matrix parameters every token passes through: the attention and
    the router of every layer, the head."""
    e = cfg["hidden_size"]
    return cfg[LAYERS] * (attn_params(cfg) + cfg["num_experts"] * e) \
        + cfg["vocab_size"] * e


def total_params(cfg) -> int:
    """Every parameter held here (norm gains too)."""
    return sum(_size(s) for s in leaf_shapes(cfg).values())


def _size(shape) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def kv_row_bytes(cfg, itemsize: int = 2) -> int:
    """What one layer keeps of one token: a K and a V row of the stored
    heads."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def kv_bytes_per_token(cfg, itemsize: int = 2) -> int:
    """A token's rows in every layer, while all of them keep it (a
    window layer's go back to the pool ``sliding_window`` tokens on)."""
    return cfg[LAYERS] * kv_row_bytes(cfg, itemsize)


def _pairs(cfg, tick) -> float:
    if "moe_pairs" in tick:
        return tick["moe_pairs"]
    return tick["decode_batch"] * cfg[LAYERS] * cfg["num_experts_per_tok"]


def _experts_hit(cfg, tick) -> float:
    if "moe_experts_hit" in tick:
        return tick["moe_experts_hit"]
    return cfg[LAYERS] * cfg["num_experts"]


def layer_rows(cfg, tick) -> float:
    """Cached rows the tick's decode dispatch reads, every layer
    together: a full layer every session's depth, a window layer no more
    than the window."""
    if "kv_rows_full" in tick:
        return tick["kv_rows_full"] * tick["kv_layers_full"] \
            + tick["kv_rows_window"] * tick["kv_layers_window"]
    windows = layer_windows(cfg)
    band = sum(w is not None for w in windows)
    return tick["kv_tokens"] * (len(windows) - band) + band * min(
        tick["kv_tokens"], tick["decode_batch"] * cfg["sliding_window"])


def attn_flops_per_key(cfg) -> float:
    """One query over one cached token, all heads, one layer: a score
    and the value's share of the output, ``head_dim`` each."""
    return 4.0 * cfg[HEADS] * cfg["head_dim"]


def decode_step_flops(cfg, tick: dict) -> float:
    """Every matrix a token passes through twice a parameter, the
    routed experts by the pairs they got, attention over the rows each
    layer reads."""
    return 2.0 * dense_params(cfg) * tick["decode_batch"] \
        + 2.0 * expert_params(cfg) * _pairs(cfg, tick) \
        + attn_flops_per_key(cfg) * layer_rows(cfg, tick)


def decode_step_bytes(cfg, tick: dict, itemsize: int = 2) -> float:
    """Least HBM traffic of one decode step: every parameter once except
    the embedding (a row a session) and the experts no token went to,
    the rows each layer reads once, and the new rows written."""
    e = cfg["hidden_size"]
    idle = cfg[LAYERS] * cfg["num_experts"] - _experts_hit(cfg, tick)
    weights = total_params(cfg) - cfg["vocab_size"] * e \
        - idle * expert_params(cfg)
    return itemsize * (weights + tick["decode_batch"] * e) \
        + kv_row_bytes(cfg, itemsize) * (
            layer_rows(cfg, tick) + cfg[LAYERS] * tick["decode_batch"])


def mixed_attn_decode_flops(cfg, tick: dict) -> float:
    """The decode tick's attention alone, window and full layers
    together."""
    return attn_flops_per_key(cfg) * layer_rows(cfg, tick)


def mixed_attn_decode_bytes(cfg, tick: dict, itemsize: int = 2) -> float:
    """Least HBM traffic of that attention: the rows each layer reads
    once, each session's queries read and its float32 outputs written,
    in every layer."""
    q_and_o = cfg[HEADS] * cfg["head_dim"] * (itemsize + 4)
    return kv_row_bytes(cfg, itemsize) * layer_rows(cfg, tick) \
        + cfg[LAYERS] * q_and_o * tick["decode_batch"]


def routed_experts_flops(cfg, tick: dict) -> float:
    """The grouped matmuls of the tick's layers: a pair passes through
    its expert's three matrices."""
    return 2.0 * expert_params(cfg) * _pairs(cfg, tick)


def routed_experts_bytes(cfg, tick: dict, itemsize: int = 2) -> float:
    """Least HBM traffic of them: the matrices of every expert that got
    a pair once, and each pair's row in (hidden), gate | up out and
    their product in (moe_intermediate_size), and row out (hidden)."""
    e, wi = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return itemsize * (_experts_hit(cfg, tick) * expert_params(cfg)
                       + _pairs(cfg, tick) * (2 * e + 3 * wi))
