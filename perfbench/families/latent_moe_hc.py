"""The latent-attention mixture-of-experts family with a residual of
several streams (a manifold-constrained hyper-connection, mHC), for
configurations whose ``builder`` is ``latent_moe_hc``.
``families/gpt.py`` says what a family file provides.

Everything but the residual is ``families/latent_moe.py``'s, which this
file takes as it is: sizes, the leaves of attention, the dense FFNs and
the routed experts, their seeded draw, the program's model and the
counts of a decode step's attention and experts.  What this file adds:

* three leaves a sublayer (attention, then the FFN, of every layer):
  ``hc_attn.phi`` / ``hc_ffn.phi`` ``(2n + n*n, n*hidden)`` (``(out,
  in)``, as the program lays it), ``.alpha (3,)`` and ``.bias (2n +
  n*n,)``, after the block's other leaves (the program's order);
* their draw: ``phi`` N(0, std) as every matrix, ``alpha`` 1 + N(0,
  std) as a gain, ``bias`` N(0, std), from a key of their own, so that
  the other leaves are drawn as the family without streams draws them
  (``assumed`` in the configuration file).  With ``std`` 0.02 a
  logit of the mapping has a spread of ``0.02 sqrt(n*hidden)`` (2.4 at
  the published widths): ``H_res`` is far from the identity and
  ``H_pre`` / ``H_post`` far from uniform;
* the counts of the mapping (``hyper_conn_*``), read by the metrics of
  the mHC kernels, and the step's counts with the mapping's parameters
  and operations in them.

The plain reference is ``perfbench/pb/reference_latent_moe_hc.py``.
"""
from __future__ import annotations

import os

from pb import cells

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: the family without streams, which this one extends
_plain = cells._module_at(_REPO, "families", "latent_moe")

LAYERS, HEADS = _plain.LAYERS, _plain.HEADS

#: the configuration keys this family reads (and the two the family
#: without streams spells in halves)
READS = _plain._Keys((
    "vocab_size", "max_position_embeddings", "hidden_size",
    "intermediate_size", "moe_intermediate_size", "n_shared_experts",
    "n_routed_experts", "router_experts", "experts_held",
    "routed_scaling_factor", "kv_lora_rank", "q_lora_rank",
    "qk_rope_head_dim", "v_head_dim", "qk_nope_head_dim", "n_group",
    "topk_group", "num_experts_per_tok", "first_k_dense_replace",
    "norm_topk_prob", "rms_norm_eps", "rope_theta", "rope_scaling",
    "initializer_range", "num_nextn_predict_layers", "num_key_value_heads",
    "moe_layer_freq", "ep_size", "hc_mult", "hc_sinkhorn_iters", "hc_eps",
    "mhc_h_res_clamp_min", "mhc_h_res_clamp_max"))

_SUBLAYERS = ("hc_attn", "hc_ffn")


# -- sizes -------------------------------------------------------------------


vocab = _plain.vocab
max_positions = _plain.max_positions


def tiny(cfg) -> dict:
    """Three layers (one dense), 8 experts all held, 2 a token, one
    group; a hidden size of one lane row, so that both tiers of the mHC
    kernels take the streams; the streams and the mapping as
    published."""
    return dict(
        hidden_size=128, **{LAYERS: 3, HEADS: 4}, num_key_value_heads=4,
        q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=12, intermediate_size=96,
        moe_intermediate_size=16, router_experts=8, n_routed_experts=8,
        experts_held=list(range(8)), n_group=1, topk_group=1,
        num_experts_per_tok=2, first_k_dense_replace=1, vocab_size=211,
        max_position_embeddings=128,
        rope_scaling=dict(cfg["rope_scaling"], factor=4.0,
                          original_max_position_embeddings=32))


def _check(cfg) -> None:
    if cfg["hc_mult"] < 2:
        raise ValueError("hc_mult counts the residual's streams: a "
                         "configuration of one stream is latent_moe's")
    if cfg["mhc_h_res_clamp_min"] != -cfg["mhc_h_res_clamp_max"]:
        raise ValueError("the program clamps the mapping's logits to a "
                         "range symmetric about 0")


def mapping_width(cfg) -> int:
    """Outputs of one sublayer's product: ``H_pre``, ``H_post`` and
    ``H_res``'s ``n*n``."""
    n = cfg["hc_mult"]
    return 2 * n + n * n


# -- weights: the benchmark's layout, which is the program's -------------------


def _mapping_shapes(cfg, block: int) -> dict:
    k, width = mapping_width(cfg), cfg["hc_mult"] * cfg["hidden_size"]
    out = {}
    for sub in _SUBLAYERS:
        p = f"blocks.{block}.{sub}."
        out.update({p + "phi": (k, width), p + "alpha": (3,),
                    p + "bias": (k,)})
    return out


def leaf_shapes(cfg) -> dict:
    """Every leaf in the program's parameter order: a block's mapping
    leaves after its others."""
    _check(cfg)
    shapes, last = {}, None
    for name, shape in _plain.leaf_shapes(cfg).items():
        block = int(name.split(".")[1]) if name.startswith("blocks.") \
            else None
        if last is not None and block != last:
            shapes.update(_mapping_shapes(cfg, last))
        shapes[name] = shape
        last = block
    return shapes


def draw(cfg, key, dtype):
    """The family without streams' draw of its leaves, and the mapping's
    leaves from a key of their own (module docstring)."""
    import jax
    std = cfg.get("initializer_range", 0.02)
    leaves = _plain.draw(cfg, key, dtype)
    own = jax.random.fold_in(key, 1 << 20)
    shapes = leaf_shapes(cfg)
    names = [n for n in shapes if n not in leaves]
    for i, name in enumerate(names):
        x = std * jax.random.normal(jax.random.fold_in(own, i), shapes[name])
        if name.endswith(".alpha"):
            x = 1.0 + x
        leaves[name] = x.astype(dtype)
    return leaves


def program_leaf_names(cfg) -> list:
    return list(leaf_shapes(cfg))


def to_program(cfg):
    """The benchmark's leaves -> the program's parameter list (the same
    hashable function for the same names)."""
    return _plain._in_order(tuple(program_leaf_names(cfg)))


# -- the program's model -------------------------------------------------------


def model(cfg, **kw):
    """The program's model with parameters that have shapes and no values
    yet, its residual ``hc_mult`` streams."""
    _check(cfg)
    return _plain.model(
        cfg, hc_mult=cfg["hc_mult"],
        hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"], hc_eps=cfg["hc_eps"],
        hc_clamp=cfg["mhc_h_res_clamp_max"], **kw)


# -- counts --------------------------------------------------------------------
# The tick's record carries, where the program counted them
# (``readers/hyper_conn.py`` joins them), ``hc_rows`` (the live rows of
# the decode dispatch) and ``hc_sublayers``; without them the counts take
# the batch and the configuration's own.
#
# The streams, ``h`` and a sublayer's output are no least bytes of HBM,
# as no family counts a one-stream model's residual: the decode
# program's streams are 14 MiB (256 rows of 4 x 3584 float32), and the
# v5e compiler gives both kernels at every sublayer their streams in the
# core's own memory (memory space 1 in the compiled program:
# tests/test_aot_tpu_compile.py holds it).  What has to come from HBM
# is each sublayer's mapping.

routed_layers = _plain.routed_layers
latent_attn_decode_flops = _plain.latent_attn_decode_flops
latent_attn_decode_bytes = _plain.latent_attn_decode_bytes
routed_experts_flops = _plain.routed_experts_flops
routed_experts_bytes = _plain.routed_experts_bytes


def sublayers(cfg) -> int:
    return 2 * cfg[LAYERS]


def mapping_params(cfg) -> int:
    """The mapping's parameters of every sublayer."""
    k = mapping_width(cfg)
    return sublayers(cfg) * (k * cfg["hc_mult"] * cfg["hidden_size"]
                             + 3 + k)


def hyper_conn_flops(cfg, tick: dict) -> float:
    """The mHC kernels of a decode tick: a row's norm (2nC), product
    with ``Phi`` (2nC a logit), ``h`` (2nC) and mix (2n^2 C + 2nC), and
    the Sinkhorn iterations (a sum and a division an entry, twice),
    at every sublayer."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    rows = tick.get("hc_rows", tick["decode_batch"])
    subs = tick.get("hc_sublayers", sublayers(cfg))
    per_row = 2 * n * c * (mapping_width(cfg) + 3 + n) \
        + 4 * n * n * cfg["hc_sinkhorn_iters"]
    return float(rows * subs * per_row)


def hyper_conn_bytes(cfg, tick: dict, itemsize: int = 2) -> float:
    """Least HBM traffic of them: each sublayer's ``Phi``, ``alpha`` and
    ``bias`` once a call (the streams stay on the chip's core: above)."""
    return float(itemsize * mapping_params(cfg))


def decode_step_flops(cfg, tick: dict) -> float:
    """The family without streams' step, and the mapping's operations."""
    return _plain.decode_step_flops(cfg, tick) + hyper_conn_flops(cfg, tick)


def decode_step_bytes(cfg, tick: dict, itemsize: int = 2) -> float:
    """The family without streams' least bytes of a step, and the
    mapping's parameters once."""
    return _plain.decode_step_bytes(cfg, tick, itemsize) \
        + hyper_conn_bytes(cfg, tick, itemsize)
