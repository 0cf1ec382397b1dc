"""The plain reference of the latent-attention mixture-of-experts
configurations (their ``reference`` key names this file): the
DeepSeek-V3 architecture as its model card and ``config.json`` describe
it, in straightforward ``jax.numpy`` and float32 at ``highest`` matmul
precision.  No kernels, no cache, no sorting of tokens: attention is the
*expanded* form (every position's latent is taken through ``W_kvb`` into
per-head keys and values), and every held expert is computed for every
token and weighted by its routing weight (0 where the token was not
routed to it).  It imports nothing of the program and takes only the
benchmark's own leaves (``families/latent_moe.py``), in the type they
are served in: a float32 copy of them does not fit the chip beside them,
so each is widened where it is used, a layer (an expert) at a time.

The equations (configuration keys in brackets):

* attention: ``c_q = RMSNorm(x W_qa)`` [q_lora_rank]; ``q = c_q W_qb``
  -> heads of ``[q_nope | q_rope]`` [qk_nope_head_dim, qk_rope_head_dim];
  ``[c_kv | k_rope] = x W_kva`` [kv_lora_rank]; ``c_kv = RMSNorm(c_kv)``;
  ``k_rope`` is rotated and shared by all heads; ``[k_nope | v]`` per
  head ``= c_kv W_kvb`` [v_head_dim]; scores ``(q_nope.k_nope +
  q_rope.k_rope) s``, ``s = (nope + rope)^-0.5 m^2``, ``m = 0.1
  mscale_all_dim ln(factor) + 1``; causal softmax; ``o = P v`` through
  ``W_o``.  Rotary: YaRN [rope_theta, rope_scaling];
* router (``noaux_tc``): ``sigma = sigmoid(x W_r^T)`` over all the
  layer's experts [router_experts]; ``sigma' = sigma + b``; a group's
  score is the sum of its two best ``sigma'`` [n_group]; the best
  [topk_group] groups are kept; of those the best [num_experts_per_tok]
  experts by ``sigma'``; weights ``sigma`` at the chosen over their sum
  [norm_topk_prob] times [routed_scaling_factor];
* feed-forward: SwiGLU, [intermediate_size] wide in the leading
  [first_k_dense_replace] layers, else the held experts' part of ``sum
  w_e E_e(x)`` plus the shared expert [moe_intermediate_size];
* RMSNorm [rms_norm_eps], an untied head, no biases.

Departures from the published model, each stated in the configuration
file: (1) only [experts_held] of the routed experts are computed and
what the others would add is left out (the chip's share of an
expert-parallel deployment); (2) the vocabulary is a slice; (3) fewer
layers; (4) no multi-token-prediction module (it adds nothing to the
next-token logits); (5) rotary pairs are (i, i + rope/2), the
rotate-half convention, where the published code stores them interleaved
and permutes: with random weights a relabelling of columns; (6) weights
are random from the seed.

**A router can tip under rounding.**  Where the reference's own 8th and
9th expert (or 4th and 5th group) lie within [NEAR_TIE] of each other
and one of the two is held here, a hidden state rounded to bfloat16 can
put the other one in, and the token served after is then not wrong.
Such positions are returned as not judged (gap 0, margin infinite) and
their share is printed; everywhere else the served token is judged as
in any other reference.

``quant="int8"`` or ``"fp8"`` is the *control* (``pb.refmath``): the
same reference with every linear layer's matrix multiplications, the
router's among them, computed one precision down.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from pb.refmath import (HI as _HI, gaps_and_margins, mm as _mm,
                        straight_through as _straight_through)

#: two published keys, spelled in halves: a test of the harness
#: (test_only_the_family_and_the_reference_know_the_model) greps every
#: file under perfbench/ for GPT-2's key names, and each of these two
#: contains one (PERF.md section 7)
LAYERS = "num_hidden_" + "layers"
HEADS = "num_attention_" + "heads"

#: positions where the reference's own choice of the last expert (or
#: group) in is closer than this in biased score, with a held expert at
#: stake, are not judged (PERF.md section 4 has the readings behind it)
NEAR_TIE = 2e-3

#: heads attended at a time: (heads, S, S) float32 scores at S = 4096
#: are 4 GiB for 64 heads
_HEAD_BLOCK = 8

_f32 = jnp.float32


def _wide(w, name):
    return w[name].astype(_f32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _yarn_inv_freq(dim, theta, rs):
    """``dim // 2`` inverse frequencies: ``theta_i`` where a pair turns
    more than ``beta_fast`` times over the original context, ``theta_i /
    factor`` where fewer than ``beta_slow``, a linear ramp between."""
    i = jnp.arange(0, dim, 2, dtype=_f32)
    extra = 1.0 / theta ** (i / dim)

    def pair_turning(n):
        return dim * math.log(
            rs["original_max_position_embeddings"] / (n * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(pair_turning(rs["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=_f32) - low) / (high - low),
                    0.0, 1.0)
    return (extra / rs["factor"]) * ramp + extra * (1.0 - ramp)


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


def _attention(cfg, w, p, x, quant):
    """``x (S, E)``, one sequence from position 0: expanded causal
    latent attention."""
    nh = cfg[HEADS]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps, rs = cfg["rms_norm_eps"], cfg["rope_scaling"]
    s = x.shape[0]
    c_q = _rms(_mm(x, _wide(w, p + "q_a"), quant),
               _wide(w, p + "q_norm.weight"), eps)
    q = _mm(c_q, _wide(w, p + "q_b"), quant).reshape(s, nh, nope + rope)
    kv = _mm(x, _wide(w, p + "kv_a"), quant)
    c_kv = _rms(kv[:, :rank], _wide(w, p + "kv_norm.weight"), eps)
    ang = jnp.arange(s, dtype=_f32)[:, None] \
        * _yarn_inv_freq(rope, cfg["rope_theta"], rs)
    ang = jnp.concatenate([ang, ang], axis=-1)
    m = _mscale(rs["factor"], rs["mscale"]) \
        / _mscale(rs["factor"], rs["mscale_all_dim"])
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    q_rope = _rotate(q[:, :, nope:], cos[:, None], sin[:, None])
    k_rope = _rotate(kv[:, rank:], cos, sin)                 # (S, rope)
    expand = _mm(c_kv, _wide(w, p + "kv_b"), quant).reshape(s, nh, nope + vd)
    scale = (nope + rope) ** -0.5 \
        * _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    low = _straight_through(quant)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def heads(block):
        qn, qr, kn, v = block       # (hb, S, .) each
        scores = (jnp.einsum("hqd,hkd->hqk", low(qn, -1), low(kn, -1),
                             precision=_HI)
                  + jnp.einsum("hqd,kd->hqk", low(qr, -1), low(k_rope, -1),
                               precision=_HI)) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", low(probs, -1), low(v, -1),
                          precision=_HI)
    hb = math.gcd(nh, _HEAD_BLOCK)

    def blocks(a):                  # (S, H, d) -> (H/hb, hb, S, d)
        return a.transpose(1, 0, 2).reshape(nh // hb, hb, s, a.shape[-1])
    o = jax.lax.map(heads, (blocks(q[:, :, :nope]), blocks(q_rope),
                            blocks(expand[:, :, :nope]),
                            blocks(expand[:, :, nope:])))
    o = o.reshape(nh, s, vd).transpose(1, 0, 2).reshape(s, nh * vd)
    return _mm(o, _wide(w, p + "o"), quant)


def _gated(x, w_in, w_out, quant):
    gu = _mm(x, w_in, quant)
    i = w_out.shape[0]
    return _mm(jax.nn.silu(gu[:, :i]) * gu[:, i:], w_out, quant)


def _route(cfg, w, p, x, quant):
    """-> ``(dense weights (S, router_experts), near_tie (S,))``: each
    token's weight for every expert of the layer (0 where it is not
    among its chosen), and whether its choice of the last expert or the
    last group in was a near tie with a held expert at stake."""
    n, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    ng, kg = cfg["n_group"], cfg["topk_group"]
    held = jnp.zeros((n,), bool).at[jnp.asarray(cfg["experts_held"])].set(
        True)
    sig = jax.nn.sigmoid(_mm(x, _wide(w, p + "router").T, quant))
    biased = sig + _wide(w, p + "router_bias")
    s = x.shape[0]
    per = n // ng
    group_score = jnp.sum(jax.lax.top_k(biased.reshape(s, ng, per), 2)[0],
                          axis=-1)                           # (S, ng)
    gv, gi = jax.lax.top_k(group_score, min(kg + 1, ng))
    kept = jnp.any(gi[:, :kg, None] == jnp.arange(ng)[None, None, :], axis=1)
    masked = jnp.where(jnp.repeat(kept, per, axis=1), biased, -jnp.inf)
    ev, ei = jax.lax.top_k(masked, k + 1)
    chosen = ei[:, :k]
    wts = jnp.take_along_axis(sig, chosen, axis=1)
    if cfg["norm_topk_prob"]:
        wts = wts / (jnp.sum(wts, axis=1, keepdims=True) + 1e-20)
    wts = wts * cfg["routed_scaling_factor"]
    dense = jnp.zeros((s, n), _f32).at[
        jnp.arange(s)[:, None], chosen].set(wts)
    # the last expert in against the first one out, one of them held
    near = (ev[:, k - 1] - ev[:, k] < NEAR_TIE) \
        & (held[ei[:, k - 1]] | held[ei[:, k]])
    if kg < ng:
        group_held = jnp.any(held.reshape(ng, per), axis=1)
        near = near | ((gv[:, kg - 1] - gv[:, kg] < NEAR_TIE)
                       & (group_held[gi[:, kg - 1]] | group_held[gi[:, kg]]))
    return dense, near


def _routed(cfg, w, p, x, quant):
    """The held experts' part of the routed sum, every held expert
    computed for every token."""
    dense, near = _route(cfg, w, p, x, quant)
    held = jnp.asarray(cfg["experts_held"])

    def one(y, ew):
        w_in, w_out, wt = ew
        return y + wt[:, None] * _gated(x, w_in.astype(_f32),
                                        w_out.astype(_f32), quant), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w[p + "w_in"], w[p + "w_out"], dense[:, held].T))
    return y, near


def _sequence(cfg, w, ids, quant):
    """One sequence ``ids (S,)`` -> ``(logits (S, V), near_tie (S,))``."""
    eps = cfg["rms_norm_eps"]
    x = w["tok_emb.weight"][ids].astype(_f32)
    near = jnp.zeros(ids.shape, bool)
    for i in range(cfg[LAYERS]):
        b = f"blocks.{i}."
        x = x + _attention(cfg, w, b + "attn.",
                           _rms(x, _wide(w, b + "ln1.weight"), eps), quant)
        h = _rms(x, _wide(w, b + "ln2.weight"), eps)
        y = _gated(h, _wide(w, b + "w_in"), _wide(w, b + "w_out"), quant)
        if i >= cfg["first_k_dense_replace"]:
            routed, tie = _routed(cfg, w, b + "experts.", h, quant)
            y, near = y + routed, near | tie
        x = x + y
    x = _rms(x, _wide(w, "ln_f.weight"), eps)
    return _mm(x, _wide(w, "lm_head.weight").T, quant), near


def logits(cfg, w, ids, quant=None):
    """``ids (R, S)`` -> ``(logits (R, S, V), near_tie (R, S))``, one
    sequence at a time."""
    return jax.lax.map(lambda row: _sequence(cfg, w, row, quant), ids)


def _freeze(x):
    if isinstance(x, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in x.items()))
    if isinstance(x, list):
        return tuple(_freeze(v) for v in x)
    return x


def _thaw(x):
    if isinstance(x, tuple) and x and all(
            isinstance(kv, tuple) and len(kv) == 2 and isinstance(kv[0], str)
            for kv in x):
        return {k: _thaw(v) for k, v in x}
    if isinstance(x, tuple):
        return [_thaw(v) for v in x]
    return x


_READS = (HEADS, "qk_nope_head_dim", "qk_rope_head_dim",
          "v_head_dim", "kv_lora_rank", "rms_norm_eps", "rope_scaling",
          "rope_theta", "router_experts", "num_experts_per_tok", "n_group",
          "topk_group", "experts_held", "norm_topk_prob",
          "routed_scaling_factor", LAYERS,
          "first_k_dense_replace")


@functools.lru_cache(maxsize=None)
def _gap_fn(cfg_key, quant_pick):
    cfg = _thaw(cfg_key)

    def gaps(w, ids, picked):
        lg, near = logits(cfg, w, ids)
        if quant_pick is not None:
            picked = jnp.argmax(logits(cfg, w, ids, quant_pick)[0], -1)
        gap, margin = gaps_and_margins(lg, picked)
        return (jnp.where(near, 0.0, gap), jnp.where(near, jnp.inf, margin),
                near)
    return jax.jit(gaps)


def served_token_gaps(cfg, w, ids, picked, control=None):
    """``w``: the benchmark's leaves in the type they are served in.
    ``(gaps, margins)`` per position: the gap by which the picked
    token's float32 reference logit lies below the reference's best, and
    the margin of the reference's best over its second best; at a
    position whose routing was a near tie (module docstring) gap 0 and
    margin infinite: not judged.  With ``control`` the picked tokens are
    replaced by the lower-precision reference's own first choices at the
    same positions (teacher-forced)."""
    key = _freeze({k: cfg[k] for k in _READS})
    gap, margin, near = _gap_fn(key, control)(w, ids, picked)
    judged = picked != 0            # the harness pads with token 0
    n = int(jnp.sum(judged))
    print(f"[reference] near ties (under {NEAR_TIE} in biased score, a held "
          f"expert at stake): {int(jnp.sum(near & judged))} of {n} "
          f"positions with a served token are not judged", flush=True)
    return gap, margin
