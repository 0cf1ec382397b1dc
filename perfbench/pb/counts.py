"""Operations and bytes that the algorithm needs, from shapes alone.

These are the yardstick's own counts: what a GPT-2 block has to compute
for a token, whatever the program emits for it (recomputation, padding
and copies do not count).  ``cfg`` is a configuration file's dict
(``n_embd``, ``n_layer``, ``n_head``, ``vocab_size``, ``n_positions``).
"""
from __future__ import annotations


def inner(cfg) -> int:
    return cfg.get("n_inner") or 4 * cfg["n_embd"]


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


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """(least seconds, which bound) on a chip with these peaks."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
