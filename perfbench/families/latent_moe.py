"""The latent-attention mixture-of-experts family (the DeepSeek-V3 shape:
MLA, YaRN rotary positions, routed experts with a shared one), for
configurations whose ``builder`` is ``latent_moe``.  ``families/gpt.py``
says what a family file provides.

A configuration of this family is one chip's share of an
expert-parallel deployment (guide ``model-configs``, section 4): the
router keeps its published width (``router_experts``) and experts a
token, ``experts_held`` are the ids of the routed experts whose matrices
live here (``n_routed_experts`` of them), the vocabulary is a slice, and
the layers left out lie on further chips.

The benchmark's leaves are named as the program names its parameters and
laid out as it lays them out (matrices ``(in, out)``, a layer's held
experts stacked, gate | up side by side), so ``to_program`` only puts
them in order; the shapes are written out here, not asked of the
program, and ``pb.sut.build_model`` holds the two to each other.  The
plain reference (``perfbench/pb/reference_latent_moe.py``) reads the
same leaves.
"""
from __future__ import annotations

import functools

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
    "intermediate_size", "moe_intermediate_size", "n_shared_experts",
    "n_routed_experts", "router_experts", "experts_held",
    "routed_scaling_factor", "kv_lora_rank", "q_lora_rank",
    "qk_rope_head_dim", "v_head_dim", "qk_nope_head_dim", "n_group",
    "topk_group", "num_experts_per_tok", "first_k_dense_replace",
    "norm_topk_prob", "rms_norm_eps", "rope_theta", "rope_scaling",
    "initializer_range", "num_nextn_predict_layers", "num_key_value_heads",
    "moe_layer_freq", "ep_size"))


def latent_width(cfg) -> int:
    """What the algorithm needs of a token's latent row (the program
    stores it padded to whole lane rows: ``serve/pool.py``)."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


# -- sizes -------------------------------------------------------------------


def vocab(cfg) -> int:
    return cfg["vocab_size"]


def max_positions(cfg) -> int:
    return cfg["max_position_embeddings"]


def tiny(cfg) -> dict:
    """Three layers (one dense), 16 experts in 4 groups of which 2 are
    kept and 4 experts chosen; half of group 0 is held, as in the
    configuration."""
    return dict(
        hidden_size=64, **{LAYERS: 3, HEADS: 4}, num_key_value_heads=4,
        q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=12, intermediate_size=96,
        moe_intermediate_size=16, router_experts=16, n_routed_experts=2,
        experts_held=[0, 1], n_group=4, topk_group=2, num_experts_per_tok=4,
        first_k_dense_replace=1, vocab_size=211, max_position_embeddings=128,
        rope_scaling=dict(cfg["rope_scaling"], factor=4.0,
                          original_max_position_embeddings=32))


def _check(cfg) -> None:
    """What this family cannot run, said where the file is read."""
    if len(cfg["experts_held"]) != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here: "
                         "it has to be the length of experts_held")
    if cfg["num_nextn_predict_layers"]:
        raise ValueError("multi-token prediction is not served: set "
                         "num_nextn_predict_layers to 0 and list it under "
                         "`reduced`")
    if cfg["num_key_value_heads"] != cfg[HEADS]:
        raise ValueError("latent attention keeps one latent row for all "
                         "heads: num_key_value_heads is the heads'")
    if cfg["moe_layer_freq"] != 1:
        raise ValueError("every layer after the leading dense ones is "
                         "routed: moe_layer_freq 1")
    if cfg["ep_size"] != 1:
        raise ValueError("ep_size is the source's (1: its file describes "
                         "the whole layer); the share served is "
                         "experts_held")


# -- weights: the benchmark's layout, which is the program's -------------------


def leaf_shapes(cfg) -> dict:
    """Every leaf in the program's parameter order."""
    _check(cfg)
    e, v, nh = cfg["hidden_size"], cfg["vocab_size"], cfg[HEADS]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    g, n, wi = (cfg["n_routed_experts"], cfg["router_experts"],
                cfg["moe_intermediate_size"])
    shapes = {"tok_emb.weight": (v, e)}
    for i in range(cfg[LAYERS]):
        b = f"blocks.{i}."
        routed = i >= cfg["first_k_dense_replace"]
        f = cfg["n_shared_experts"] * wi if routed \
            else cfg["intermediate_size"]
        shapes.update({
            b + "w_in": (e, 2 * f), b + "w_out": (f, e),
            b + "ln1.weight": (e,),
            b + "attn.q_a": (e, qr),
            b + "attn.q_b": (qr, nh * (nope + rope)),
            b + "attn.kv_a": (e, kr + rope),
            b + "attn.kv_b": (kr, nh * (nope + vd)),
            b + "attn.o": (nh * vd, e),
            b + "attn.q_norm.weight": (qr,),
            b + "attn.kv_norm.weight": (kr,),
            b + "ln2.weight": (e,),
        })
        if routed:
            shapes.update({
                b + "experts.router": (n, e),
                b + "experts.router_bias": (n,),
                b + "experts.w_in": (g, e, 2 * wi),
                b + "experts.w_out": (g, wi, e),
            })
    shapes.update({"ln_f.weight": (e,), "lm_head.weight": (v, e)})
    return shapes


def draw(cfg, key, dtype):
    """Every matrix N(0, std), norm gains 1 + N(0, std), the router's
    correction bias N(0, std) (``assumed`` in the configuration file).
    A held expert's matrices are drawn from a key folded with the
    expert's id, so that a share's experts are a slice of the draw of
    all ``router_experts``: two shares of one seed hold different
    experts of the same model."""
    import jax
    import jax.numpy as jnp
    std = cfg.get("initializer_range", 0.02)
    held = jnp.asarray(cfg["experts_held"], jnp.int32)
    shapes = leaf_shapes(cfg)
    leaves = {}
    for i, (name, shape) in enumerate(shapes.items()):
        k = jax.random.fold_in(key, i)
        if name.endswith(("experts.w_in", "experts.w_out")):
            x = std * jax.vmap(lambda e: jax.random.normal(
                jax.random.fold_in(k, e), shape[1:], jnp.float32))(held)
        else:
            x = std * jax.random.normal(k, shape, jnp.float32)
            if name.endswith("norm.weight") or name.endswith(
                    ("ln1.weight", "ln2.weight", "ln_f.weight")):
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
    yet (``pb.sut.build_model`` puts the seeded ones in): the full-size
    model cannot be drawn in float32 first."""
    from apex_tpu import models
    _check(cfg)
    return models.LatentMoeModel(
        cfg["vocab_size"], cfg["hidden_size"], cfg[LAYERS],
        cfg[HEADS], q_rank=cfg["q_lora_rank"],
        kv_rank=cfg["kv_lora_rank"], nope_dim=cfg["qk_nope_head_dim"],
        rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        dense_intermediate=cfg["intermediate_size"],
        expert_intermediate=cfg["moe_intermediate_size"],
        n_experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        n_shared=cfg["n_shared_experts"],
        route_scale=cfg["routed_scaling_factor"],
        norm_topk=cfg["norm_topk_prob"],
        first_dense=cfg["first_k_dense_replace"],
        experts_held=cfg["experts_held"],
        rope=dict(cfg["rope_scaling"], rope_theta=cfg["rope_theta"]),
        max_positions=cfg["max_position_embeddings"],
        eps=cfg["rms_norm_eps"], abstract=True, **kw)


# -- counts --------------------------------------------------------------------
# What the algorithm has to compute and move on THIS chip, from shapes
# alone.  A decode tick's record (``pb/serve_loop.py``) has
# ``decode_batch`` and ``kv_tokens``; where a reader has joined the
# program's own counters to it (``readers/moe.py``) also ``moe_pairs``
# (token-expert pairs that went to held experts, all routed layers) and
# ``moe_experts_hit`` (how often a held expert's matrices had to be
# streamed); without them the counts take what the router gives on
# average (pairs) and every held expert (matrices).


def routed_layers(cfg) -> int:
    return cfg[LAYERS] - cfg["first_k_dense_replace"]


def attn_params(cfg) -> int:
    e, nh = cfg["hidden_size"], cfg[HEADS]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return e * qr + qr * nh * (nope + rope) + e * (kr + rope) \
        + kr * nh * (nope + vd) + nh * vd * e


def expert_params(cfg) -> int:
    """One routed expert's three matrices (the shared expert's are
    ``n_shared_experts`` times as many)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_params(cfg) -> int:
    """Matrix parameters every token of this chip passes through: the
    attention of every layer, the leading dense FFNs, the shared experts
    and routers of the routed layers, the head."""
    e = cfg["hidden_size"]
    lr = routed_layers(cfg)
    return cfg[LAYERS] * attn_params(cfg) \
        + cfg["first_k_dense_replace"] * 3 * e * cfg["intermediate_size"] \
        + lr * (cfg["n_shared_experts"] * expert_params(cfg)
                + cfg["router_experts"] * e) \
        + cfg["vocab_size"] * e


def total_params(cfg) -> int:
    """Every parameter held here (norm gains and the router's bias
    too)."""
    return sum(_size(s) for s in leaf_shapes(cfg).values())


def _size(shape) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def _pairs(cfg, tick) -> float:
    """Token-expert pairs of the tick that went to held experts."""
    if "moe_pairs" in tick:
        return tick["moe_pairs"]
    return tick["decode_batch"] * routed_layers(cfg) \
        * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["router_experts"]


def _experts_hit(cfg, tick) -> float:
    if "moe_experts_hit" in tick:
        return tick["moe_experts_hit"]
    return routed_layers(cfg) * cfg["n_routed_experts"]


def attn_flops_per_key(cfg) -> float:
    """One query over one cached token, all heads, one layer, absorbed:
    the score against the latent row and the row's latent part taken
    into the output."""
    return 2.0 * cfg[HEADS] * (
        latent_width(cfg) + cfg["kv_lora_rank"])


def latent_bytes_per_token(cfg, itemsize: int = 2) -> int:
    return cfg[LAYERS] * latent_width(cfg) * itemsize


def decode_step_flops(cfg, tick: dict) -> float:
    """Every matrix a token passes through twice a parameter, the
    routed experts by the pairs they got, attention over each session's
    live depth."""
    return 2.0 * dense_params(cfg) * tick["decode_batch"] \
        + 2.0 * expert_params(cfg) * _pairs(cfg, tick) \
        + cfg[LAYERS] * attn_flops_per_key(cfg) \
        * tick["kv_tokens"]


def decode_step_bytes(cfg, tick: dict, itemsize: int = 2) -> float:
    """Least HBM traffic of one decode step: every parameter once except
    the embedding (a row a session) and the held experts no token went
    to, the batch's live latent rows once, and the new rows written."""
    e = cfg["hidden_size"]
    experts_held = routed_layers(cfg) * cfg["n_routed_experts"]
    weights = total_params(cfg) - cfg["vocab_size"] * e \
        - (experts_held - _experts_hit(cfg, tick)) * expert_params(cfg)
    return itemsize * (weights + tick["decode_batch"] * e) \
        + latent_bytes_per_token(cfg, itemsize) * (
            tick["kv_tokens"] + tick["decode_batch"])


def latent_attn_decode_flops(cfg, tick: dict) -> float:
    """The decode tick's attention alone: one query a session over its
    live latent rows, every layer."""
    return cfg[LAYERS] * attn_flops_per_key(cfg) \
        * tick["kv_tokens"]


def latent_attn_decode_bytes(cfg, tick: dict, itemsize: int = 2) -> float:
    """Least HBM traffic of that attention: the batch's live latent rows
    read once at their 576 elements, each session's absorbed queries
    read and its float32 latent outputs written, in every layer."""
    nh = cfg[HEADS]
    q_and_o = nh * (latent_width(cfg) * itemsize + cfg["kv_lora_rank"] * 4)
    return latent_bytes_per_token(cfg, itemsize) * tick["kv_tokens"] \
        + cfg[LAYERS] * q_and_o * tick["decode_batch"]


def routed_experts_flops(cfg, tick: dict) -> float:
    """The grouped matmuls of the tick's routed layers: a pair passes
    through its expert's three matrices."""
    return 2.0 * expert_params(cfg) * _pairs(cfg, tick)


def routed_experts_bytes(cfg, tick: dict, itemsize: int = 2) -> float:
    """Least HBM traffic of them: the matrices of every held expert that
    got a pair once, and each pair's row in (hidden), gate | up out and
    their product in (moe_intermediate_size), and row out (hidden)."""
    e, wi = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return itemsize * (_experts_hit(cfg, tick) * expert_params(cfg)
                       + _pairs(cfg, tick) * (2 * e + 3 * wi))
