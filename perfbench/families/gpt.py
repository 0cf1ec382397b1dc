"""The GPT-2 family: what the harness asks of a model family, for
configurations whose ``builder`` is ``gpt``.

A family file is everything under ``perfbench/`` that knows one kind of
model; the kinds, the readers and ``pb/`` ask it and know none.  It
provides:

* sizes: ``vocab(cfg)`` (traffic draws its token ids below it),
  ``max_positions(cfg)`` (the longest sequence the reference pads to),
  ``tiny(cfg)`` (the overrides that make the configuration a CPU test)
  and ``READS``, the configuration keys it reads;
* weights: ``leaf_shapes`` and ``draw``, the benchmark's own leaves from
  a key (here the published GPT-2 layout: Conv1D matrices are (in, out),
  ``c_attn`` is q | k | v with heads major inside each), which the plain
  reference reads directly; ``program_leaf_names`` and ``to_program``,
  their order and layout in the program;
* the program's model: ``model(cfg, **kw)`` through ``apex_tpu``'s
  public entries (``pb.sut`` fills it with the seeded weights and builds
  the step or the engine around it);
* counts: what the algorithm has to compute and move, from shapes
  alone, whatever the program emits for it (recomputation, padding and
  copies do not count).  The step-level ones take the tick's record
  (``decode_batch``, ``kv_tokens`` and whatever else the serve loop
  counted), so that a family whose work depends on more than the batch
  and its depth can count it.

The plain reference is not here: it is the file the configuration's
``reference`` key names (``perfbench/pb/reference.py`` for GPT-2).
"""
from __future__ import annotations

import functools
import math

#: the configuration keys this family reads (the reference reads
#: ``layer_norm_epsilon`` besides); the other keys of a configuration
#: file are the source's, kept for the record, or notes
READS = ("vocab_size", "n_positions", "n_embd", "n_layer", "n_head",
         "n_inner", "initializer_range", "resid_pdrop", "embd_pdrop",
         "attn_pdrop", "attn_bias")


# -- sizes -------------------------------------------------------------------


def vocab(cfg) -> int:
    return cfg["vocab_size"]


def max_positions(cfg) -> int:
    return cfg["n_positions"]


def tiny(cfg) -> dict:
    return dict(n_embd=64, n_layer=2, n_head=4, vocab_size=211,
                n_positions=128, n_ctx=128)


def inner(cfg) -> int:
    return cfg.get("n_inner") or 4 * cfg["n_embd"]


# -- weights: the benchmark's layout, and the program's ------------------------


def leaf_shapes(cfg) -> dict:
    e, v, p = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    f = inner(cfg)
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


def draw(cfg, key, dtype):
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


def program_leaf_names(cfg) -> list:
    """The benchmark's leaf name for each entry of
    ``list(model.parameters())``, in the program's order."""
    names = ["wte", "wpe"]
    for i in range(cfg["n_layer"]):
        h = f"h.{i}."
        names += [h + "ln_1.g", h + "ln_1.b", h + "attn.c_attn.w",
                  h + "attn.c_proj.w", h + "ln_2.g", h + "ln_2.b",
                  h + "mlp.c_fc.w", h + "mlp.c_fc.b",
                  h + "mlp.c_proj.w", h + "mlp.c_proj.b"]
    return names + ["ln_f.g", "ln_f.b"]


@functools.lru_cache(maxsize=None)
def layout_converter(n_layer: int, n_head: int):
    cfg = {"n_layer": n_layer}
    names = program_leaf_names(cfg)

    def convert(leaves):
        out = []
        for name in names:
            x = leaves[name]
            if name.endswith("c_attn.w"):
                # (E, 3E) q|k|v, heads major -> the program's rows
                # interleaved [head, (q, k, v), d]: (3E, E)
                e = x.shape[0]
                d = e // n_head
                x = x.reshape(e, 3, n_head, d).transpose(2, 1, 3, 0) \
                     .reshape(3 * e, e)
            elif name.endswith(".w"):
                x = x.T                # Conv1D (in, out) -> Linear (out, in)
            out.append(x)
        return out
    return convert


def to_program(cfg):
    """The benchmark's leaves -> the program's parameter list (the same
    hashable function for the same sizes: it is part of a jit's key)."""
    return layout_converter(cfg["n_layer"], cfg["n_head"])


# -- the program's model -------------------------------------------------------


def model(cfg, **kw):
    from apex_tpu.models import GptModel
    if cfg["embd_pdrop"] != cfg["resid_pdrop"]:
        raise ValueError("the program has one dropout rate for embeddings "
                         "and residuals")
    return GptModel(
        vocab_size=cfg["vocab_size"], hidden=cfg["n_embd"],
        layers=cfg["n_layer"], heads=cfg["n_head"],
        intermediate=cfg.get("n_inner"),
        max_positions=cfg["n_positions"], dropout=cfg["resid_pdrop"],
        attn_dropout=cfg["attn_pdrop"], attn_bias=cfg["attn_bias"], **kw)


# -- counts --------------------------------------------------------------------


def matmul_params(cfg) -> int:
    """Parameters that sit in a matrix multiplication for every token:
    per layer qkv (E x 3E), attention output (E x E) and the two MLP
    matrices, plus the tied head (V x E) once.  Embedding lookups,
    biases and LayerNorms do no multiply-accumulate work."""
    e, f = cfg["n_embd"], inner(cfg)
    per_layer = 3 * e * e + e * e + 2 * e * f
    return cfg["n_layer"] * per_layer + cfg["vocab_size"] * e


def total_params(cfg, attn_bias=False) -> int:
    e, f, l = cfg["n_embd"], inner(cfg), cfg["n_layer"]
    per_layer = (3 * e * e + e * e + 2 * e * f      # matrices
                 + f + e                           # MLP biases
                 + 4 * e)                          # two LayerNorms
    if attn_bias:
        per_layer += 3 * e + e
    return (cfg["vocab_size"] * e + cfg["n_positions"] * e
            + l * per_layer + 2 * e)


def attn_flops_fwd(cfg, q_len: int, kv_len: float) -> float:
    """QK^T and PV for ``q_len`` queries that each see ``kv_len`` keys
    on average, all layers: 2 matmuls x 2 flops x E per (query, key)."""
    return 4.0 * cfg["n_layer"] * cfg["n_embd"] * q_len * kv_len


def train_flops_per_token(cfg, seq_len: int) -> float:
    """Forward + backward of one token of a ``seq_len`` causal sequence:
    6 x matmul parameters (2 forward, 4 backward) plus causal attention
    (a query at position i sees i + 1 keys: (seq_len + 1) / 2 on
    average), three times its forward cost."""
    causal_kv = (seq_len + 1) / 2.0
    return 6.0 * matmul_params(cfg) \
        + 3.0 * attn_flops_fwd(cfg, 1, causal_kv)


def flash_attn_flops_train(cfg, batch: int, seq_len: int) -> float:
    """Causal attention of one train step as the algorithm needs it:
    forward 2 matmuls (QK^T, PV), backward 4 (dV, dP, dQ, dK), over the
    causal half.  The scores a flash kernel recomputes in its backward
    pass are the kernel's own and are not counted."""
    return 3.0 * attn_flops_fwd(cfg, batch * seq_len, (seq_len + 1) / 2.0)


def flash_attn_bytes_train(cfg, batch: int, seq_len: int,
                           itemsize: int = 2) -> float:
    """Least HBM traffic of that attention: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv."""
    tensor = batch * seq_len * cfg["n_embd"] * itemsize
    return cfg["n_layer"] * 12.0 * tensor


def forward_flops(cfg, n_tokens: int, kv_len_sum: float) -> float:
    """Inference forward for ``n_tokens`` tokens whose queries see
    ``kv_len_sum`` keys in total (summed over the tokens)."""
    return 2.0 * matmul_params(cfg) * n_tokens \
        + attn_flops_fwd(cfg, 1, kv_len_sum)


def kv_bytes_per_token(cfg, itemsize: int = 2) -> int:
    return cfg["n_layer"] * 2 * cfg["n_embd"] * itemsize


def decode_step_min_bytes(cfg, live_kv_tokens: float, batch: float,
                          itemsize: int = 2) -> float:
    """Least HBM traffic of one decode step: every weight once, the
    batch's live KV rows once, and the new rows written."""
    weights = total_params(cfg) * itemsize
    return weights + kv_bytes_per_token(cfg, itemsize) * (
        live_kv_tokens + batch)


# the same, of one tick of the serve loop (``pb/serve_loop.py``'s record)


def decode_step_flops(cfg, tick: dict) -> float:
    return forward_flops(cfg, tick["decode_batch"], tick["kv_tokens"])


def decode_step_bytes(cfg, tick: dict) -> float:
    return decode_step_min_bytes(cfg, tick["kv_tokens"],
                                 tick["decode_batch"])


def paged_attn_decode_flops(cfg, tick: dict) -> float:
    """The decode tick's attention alone: one query a session over its
    live keys and values."""
    return attn_flops_fwd(cfg, 1, tick["kv_tokens"])


def paged_attn_decode_bytes(cfg, tick: dict, itemsize: int = 2) -> float:
    """Least HBM traffic of that attention: the batch's live K and V
    rows read once, each session's query read and output written, in
    every layer."""
    q_and_o = 2 * cfg["n_layer"] * cfg["n_embd"] * itemsize
    return kv_bytes_per_token(cfg, itemsize) * tick["kv_tokens"] \
        + q_and_o * tick["decode_batch"]
