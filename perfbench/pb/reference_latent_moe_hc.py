"""The plain reference of the latent-attention mixture-of-experts
configurations whose residual is several streams mixed by a
manifold-constrained hyper-connection (their ``reference`` key names this
file): the architecture its ``config.json`` and the mHC paper (DeepSeek-AI,
arXiv:2512.24880; hyper-connections: Zhu et al., arXiv:2409.19606)
describe, in straightforward ``jax.numpy`` and float32 at ``highest``
matmul precision.  No kernels, no cache, no sorting of tokens: attention
is the *expanded* form and every held expert is computed for every token
and weighted by its routing weight (0 where the token was not routed to
it).  It imports nothing of the program and takes only the benchmark's
own leaves (``families/latent_moe_hc.py``), in the type they are served
in, each widened where it is used.

The equations (configuration keys in brackets):

* the residual: a token's state is ``X (n, C)`` [hc_mult, hidden_size],
  float32; ``X[i]`` of the first layer is the token's embedding for
  every ``i``, and what the final norm is handed is ``sum_i X[i]``;
* each sublayer ``F`` (attention after ``ln1``; the feed-forward after
  ``ln2``) has its own ``Phi``, ``alpha`` and ``b``::

      r       = vec(X) / sqrt(mean(vec(X)^2) + eps)   [hc_eps]
      [p|q|R] = r Phi^T                                (n, n, n*n)
      H_pre   = sigmoid(alpha_pre p + b_pre)
      H_post  = 2 sigmoid(alpha_post q + b_post)
      M       = exp(clamp(alpha_res mat(R) + b_res))
                [mhc_h_res_clamp_min, mhc_h_res_clamp_max]
      repeat [hc_sinkhorn_iters]: M = M / (rowsum(M) + eps);
                                  M = M / (colsum(M) + eps)
      X'[i]   = sum_j M[i, j] X[j] + H_post[i] F(sum_i H_pre[i] X[i])

* attention (MLA): ``c_q = RMSNorm(x W_qa)`` [q_lora_rank]; ``q = c_q
  W_qb`` -> heads of ``[q_nope | q_rope]`` [qk_nope_head_dim,
  qk_rope_head_dim]; ``[c_kv | k_rope] = x W_kva`` [kv_lora_rank];
  ``c_kv = RMSNorm(c_kv)``; ``k_rope`` rotated and shared by all heads;
  ``[k_nope | v]`` per head ``= c_kv W_kvb`` [v_head_dim]; scores
  ``(q_nope.k_nope + q_rope.k_rope) s``, ``s = (nope + rope)^-0.5 m^2``,
  ``m = 0.1 mscale_all_dim ln(factor) + 1``; causal softmax; ``o = P v``
  through ``W_o``.  Rotary: YaRN [rope_theta, rope_scaling];
* router (``noaux_tc``): ``sigma = sigmoid(x W_r^T)`` over the layer's
  experts [router_experts]; ``sigma' = sigma + b``; a group's score is
  the sum of its two best ``sigma'`` [n_group], the best [topk_group]
  groups are kept, of those the best [num_experts_per_tok] experts by
  ``sigma'``; weights ``sigma`` at the chosen over their sum
  [norm_topk_prob] times [routed_scaling_factor];
* feed-forward: SwiGLU, [intermediate_size] wide in the leading
  [first_k_dense_replace] layers, else the held experts' part of ``sum
  w_e E_e(x)`` plus the shared expert [moe_intermediate_size];
* RMSNorm [rms_norm_eps], an untied head, no biases.

Departures from the published model, each stated in the configuration
file: (1) fewer layers; (2) no multi-token-prediction module (it adds
nothing to the next-token logits); (3) rotary pairs are (i, i + rope/2),
the rotate-half convention; (4) the mapping's RMSNorm has no gain (a gain
folds into ``Phi``), Sinkhorn normalises rows first, and the ends of the
stack are the hyper-connections paper's (the three choices the
configuration does not fix); (5) weights are random from the seed.

**A router can tip under rounding.**  Where the reference's own last
expert in and first expert out lie within [NEAR_TIE] of each other in
biased score in some routed layer, a hidden state rounded on the way can
put the other one in, and the token served after is then not wrong.
Such positions are returned as not judged (gap 0, margin infinite) and
their share is printed.

``quant="int8"`` or ``"fp8"`` is the *control* (``pb.refmath``): the
same reference with every matrix product, the mapping's and the router's
among them, computed one precision down.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from pb.refmath import (HI as _HI, gaps_and_margins, mm as _mm,
                        straight_through as _straight_through)

#: two published keys, spelled in halves (as every reference of a
#: latent family spells them)
LAYERS = "num_hidden_" + "layers"
HEADS = "num_attention_" + "heads"

#: positions where the reference's own last expert in leads its first
#: one out by less than this in biased score, in some routed layer, are
#: not judged (PERF.md section 4 has the readings behind it)
NEAR_TIE = 2e-3

#: heads attended at a time, and positions of the head taken at a time
_HEAD_BLOCK = 8
_HEAD_ROWS = 1024

_f32 = jnp.float32


def _wide(w, name):
    return w[name].astype(_f32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _yarn_inv_freq(dim, theta, rs):
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
    k_rope = _rotate(kv[:, rank:], cos, sin)
    expand = _mm(c_kv, _wide(w, p + "kv_b"), quant).reshape(s, nh, nope + vd)
    scale = (nope + rope) ** -0.5 \
        * _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    low = _straight_through(quant)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def heads(block):
        qn, qr, kn, v = block
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
    """-> ``(dense weights (S, router_experts), lead (S,))``: each
    token's weight for every expert of the layer (0 where it is not
    among its chosen), and by how much its last expert in leads its
    first one out in biased score (infinite where neither is held, or
    its last group in leads its first one out by more)."""
    n, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    ng, kg = cfg["n_group"], cfg["topk_group"]
    held = jnp.zeros((n,), bool).at[jnp.asarray(cfg["experts_held"])].set(
        True)
    sig = jax.nn.sigmoid(_mm(x, _wide(w, p + "router").T, quant))
    biased = sig + _wide(w, p + "router_bias")
    s = x.shape[0]
    per = n // ng
    group_score = jnp.sum(jax.lax.top_k(biased.reshape(s, ng, per), 2)[0],
                          axis=-1)
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
    lead = jnp.where(held[ei[:, k - 1]] | held[ei[:, k]],
                     ev[:, k - 1] - ev[:, k], jnp.inf)
    if kg < ng:
        group_held = jnp.any(held.reshape(ng, per), axis=1)
        lead = jnp.minimum(lead, jnp.where(
            group_held[gi[:, kg - 1]] | group_held[gi[:, kg]],
            gv[:, kg - 1] - gv[:, kg], jnp.inf))
    return dense, lead


def _routed(cfg, w, p, x, quant):
    """The held experts' part of the routed sum, every held expert
    computed for every token."""
    dense, lead = _route(cfg, w, p, x, quant)
    held = jnp.asarray(cfg["experts_held"])

    def one(y, ew):
        w_in, w_out, wt = ew
        return y + wt[:, None] * _gated(x, w_in.astype(_f32),
                                        w_out.astype(_f32), quant), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w[p + "w_in"], w[p + "w_out"], dense[:, held].T))
    return y, lead


# -- the residual streams --------------------------------------------------------


def _mapping(cfg, w, p, x, quant):
    """The streams ``x (S, n*C)`` -> ``(h (S, C), H_post (S, n), H_res
    (S, n, n))`` of the sublayer whose leaves start with ``p``."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    eps = cfg["hc_eps"]
    s = x.shape[0]
    r = x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    lg = _mm(r, _wide(w, p + "phi").T, quant)                # (S, 2n + n*n)
    alpha, b = _wide(w, p + "alpha"), _wide(w, p + "bias")
    h_pre = jax.nn.sigmoid(alpha[0] * lg[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * lg[:, n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(
        alpha[2] * lg[:, 2 * n:].reshape(s, n, n) + b[2 * n:].reshape(n, n),
        cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]))
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)   # rows first
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    h = jnp.einsum("si,sic->sc", h_pre, x.reshape(s, n, c), precision=_HI)
    return h, h_post, m


def _mix(x, y, h_post, h_res):
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``."""
    s, n = h_post.shape
    streams = x.reshape(s, n, -1)
    out = jnp.einsum("sij,sjc->sic", h_res, streams, precision=_HI) \
        + h_post[:, :, None] * y[:, None, :]
    return out.reshape(s, -1)


def _hidden(cfg, w, ids, quant):
    """One sequence ``ids (S,)`` -> ``(the final norm's output (S, E),
    lead (S,))``: ``lead`` the least, over the routed layers, of the
    position's last expert in over its first one out."""
    eps, n = cfg["rms_norm_eps"], cfg["hc_mult"]
    x = jnp.tile(w["tok_emb.weight"][ids].astype(_f32), (1, n))
    lead = jnp.full(ids.shape, jnp.inf, _f32)
    for i in range(cfg[LAYERS]):
        b = f"blocks.{i}."
        h, h_post, h_res = _mapping(cfg, w, b + "hc_attn.", x, quant)
        y = _attention(cfg, w, b + "attn.",
                       _rms(h, _wide(w, b + "ln1.weight"), eps), quant)
        x = _mix(x, y, h_post, h_res)
        h, h_post, h_res = _mapping(cfg, w, b + "hc_ffn.", x, quant)
        h = _rms(h, _wide(w, b + "ln2.weight"), eps)
        y = _gated(h, _wide(w, b + "w_in"), _wide(w, b + "w_out"), quant)
        if i >= cfg["first_k_dense_replace"]:
            routed, t = _routed(cfg, w, b + "experts.", h, quant)
            y, lead = y + routed, jnp.minimum(lead, t)
        x = _mix(x, y, h_post, h_res)
    x = jnp.sum(x.reshape(x.shape[0], n, -1), axis=1)
    return _rms(x, _wide(w, "ln_f.weight"), eps), lead


def _head(w, x, quant):
    return _mm(x, _wide(w, "lm_head.weight").T, quant)


def logits(cfg, w, ids, quant=None):
    """``ids (R, S)`` -> ``(logits (R, S, V), lead (R, S))``, one
    sequence at a time (for sizes whose logits fit; the comparison below
    never holds a whole sequence's)."""
    def one(row):
        x, lead = _hidden(cfg, w, row, quant)
        return _head(w, x, quant), lead
    return jax.lax.map(one, ids)


def _judge(cfg, w, row, picked, quant_pick):
    """One sequence -> ``(gap (S,), margin (S,), lead (S,))``, the head
    taken ``_HEAD_ROWS`` positions at a time."""
    x, lead = _hidden(cfg, w, row, None)
    r = math.gcd(row.shape[0], _HEAD_ROWS)
    xs = x.reshape(-1, r, x.shape[-1])
    if quant_pick is None:
        def part(blk):
            return gaps_and_margins(_head(w, blk[0], None), blk[1])
        gap, margin = jax.lax.map(part, (xs, picked.reshape(-1, r)))
    else:
        xq, _ = _hidden(cfg, w, row, quant_pick)

        def part(blk):
            return gaps_and_margins(
                _head(w, blk[0], None),
                jnp.argmax(_head(w, blk[1], quant_pick), -1))
        gap, margin = jax.lax.map(part, (xs, xq.reshape(xs.shape)))
    return gap.reshape(-1), margin.reshape(-1), lead


_READS = (HEADS, LAYERS, "qk_nope_head_dim", "qk_rope_head_dim",
          "v_head_dim", "kv_lora_rank", "rms_norm_eps", "rope_scaling",
          "rope_theta", "router_experts", "num_experts_per_tok", "n_group",
          "topk_group", "experts_held", "norm_topk_prob",
          "routed_scaling_factor", "first_k_dense_replace", "hc_mult",
          "hidden_size", "hc_eps", "hc_sinkhorn_iters",
          "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")


@functools.lru_cache(maxsize=None)
def _gap_fn(cfg_json, quant_pick):
    cfg = json.loads(cfg_json)

    def gaps(w, ids, picked):
        return jax.lax.map(
            lambda rp: _judge(cfg, w, rp[0], rp[1], quant_pick),
            (ids, picked))
    return jax.jit(gaps)


def gaps_margins_leads(cfg, w, ids, picked, control=None):
    """``(gaps, margins, leads)`` per position, nothing left out:
    ``leads`` the least lead, over the routed layers, of the position's
    last expert in over its first one out."""
    key = json.dumps({k: cfg[k] for k in _READS}, sort_keys=True)
    return _gap_fn(key, control)(w, ids, picked)


def served_token_gaps(cfg, w, ids, picked, control=None):
    """``w``: the benchmark's leaves in the type they are served in.
    ``(gaps, margins)`` per position: the gap by which the picked
    token's float32 reference logit lies below the reference's best, and
    the margin of the reference's best over its second best; at a
    position whose routing was a near tie in some layer (module
    docstring) gap 0 and margin infinite: not judged.  With ``control``
    the picked tokens are replaced by the lower-precision reference's own
    first choices at the same positions (teacher-forced)."""
    gap, margin, lead = gaps_margins_leads(cfg, w, ids, picked, control)
    near = lead < NEAR_TIE
    judged = picked != 0            # the harness pads with token 0
    n = int(jnp.sum(judged))
    print(f"[reference] near ties (under {NEAR_TIE} in biased score, a held "
          f"expert at stake): {int(jnp.sum(near & judged))} of {n} "
          f"positions with a served token are not judged", flush=True)
    return jnp.where(near, 0.0, gap), jnp.where(near, jnp.inf, margin)
