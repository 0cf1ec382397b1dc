"""The plain reference of the grouped-query mixture-of-experts
configurations whose layers mix window and full attention (their
``reference`` key names this file): the Mellum 2 architecture as its
``config.json`` declares it, in straightforward ``jax.numpy`` and
float32 at ``highest`` matmul precision.  No kernels, no cache, no
sorting of tokens, no batching: attention is full causal attention over
the whole sequence with the band as a mask, keys and values of a stored
head used by each of its query heads, and every expert is computed for
every token and weighted by its routing weight (0 where the token was
not routed to it).  It imports nothing of the program and takes only the
benchmark's own leaves (``families/gqa_moe.py``), in the type they are
served in: a float32 copy of them does not fit the chip beside them, so
each is widened where it is used, an expert at a time.

The equations (configuration keys in brackets):

* block: ``h = x + Attn_l(RMSNorm(x)); y = h + MoE(RMSNorm(h))``
  [rms_norm_eps], no biases [attention_bias], an untied head
  [tie_word_embeddings];
* attention, layer l: ``q = h W_q`` -> heads of [head_dim]; ``k = h
  W_k``, ``v = h W_v`` -> [num_key_value_heads] stored heads; query head
  ``i`` reads stored head ``i // (heads / stored heads)``; ``q`` and
  ``k`` rotated (rotate-half) by the tables of the layer's kind
  [layer_types, rope_parameters]: plain RoPE in a ``sliding_attention``
  layer, YaRN (the ramp blend of ``theta_i`` and ``theta_i / factor``
  between the pairs that turn ``beta_fast`` times and ``beta_slow``
  times in ``original_max_position_embeddings`` positions) with cos and
  sin times ``attention_factor`` in a ``full_attention`` layer; scores
  ``q.k / sqrt(head_dim)``; softmax over the keys ``s <= p`` and, in a
  ``sliding_attention`` layer, ``s > p - sliding_window``
  [sliding_window: that many keys, the query's own among them]; ``o =
  (P v) W_o``;
* router: ``p = softmax(h W_r^T)`` over all [num_experts]; the
  [num_experts_per_tok] largest; weights ``p_e`` over the sum of the
  chosen [norm_topk_prob];
* feed-forward: ``sum_e w_e W_down,e (silu(W_gate,e h) * W_up,e h)``
  [moe_intermediate_size, hidden_act]; no shared expert.

Departures from the published model, each stated in the configuration
file: (1) fewer layers, the first of ``layer_types``; (2) rotary pairs
are (i, i + head_dim/2), the rotate-half convention; (3) no query or key
normalisation and no multi-token-prediction head (the configuration
declares neither); (4) weights are random from the seed.

**A router can tip under rounding.**  Where, in any layer, the
reference's own last expert in and first expert out lie within
[NEAR_TIE] in probability, a hidden state rounded to bfloat16 can put the
other one in, and the token served after is then not wrong.  Every
expert is held here, so every such tie is at stake.  Such positions are
returned as not judged (gap 0, margin infinite) and their share is
printed.  **The threshold is 0: the rule is off and every position is
judged.**  The chip's readings (PERF.md section 4) say it buys nothing
here: the two experts of a tie have all but equal, small, renormalised
weights, so a tipped choice moves the layer's output by little, and
judging everything reads 4.7e-5 .. 8.4e-5 where leaving out half the
positions (2e-4) reads 2.9e-5 .. 7.2e-5, with the int8 control at 1.2e-3
and above either way.  ``gaps_margins_ties`` returns each position's
least lead, so a reading at any threshold takes one pass.

``quant="int8"`` or ``"fp8"`` is the *control* (``pb.refmath``): the
same reference with every linear layer's matrix multiplications, the
router's among them, and attention's operands computed one precision
down.
"""
from __future__ import annotations

import functools
import json
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

#: positions where, in some layer, the reference's own last expert in
#: and first expert out are closer than this in probability are not
#: judged; 0: every position is judged (PERF.md section 4 has the
#: readings at 0 .. 4e-4 behind it)
NEAR_TIE = 0.0

#: query heads attended at a time (all of one stored head): (heads, S, S)
#: float32 scores at S = 6144 are 151 MiB a head
_HEAD_BLOCK = 4
#: positions taken through the head at a time: (S, V) float32 logits at
#: S = 6144 and V = 98,304 are 2.4 GB
_HEAD_ROWS = 1024

_f32 = jnp.float32


def _wide(w, name):
    return w[name].astype(_f32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _yarn_inv_freq(dim, rp):
    """``dim // 2`` inverse frequencies: ``theta_i`` where a pair turns
    more than ``beta_fast`` times over the original context, ``theta_i /
    factor`` where fewer than ``beta_slow``, a linear ramp between."""
    theta = rp["rope_theta"]
    i = jnp.arange(0, dim, 2, dtype=_f32)
    extra = 1.0 / theta ** (i / dim)

    def pair_turning(n):
        return dim * math.log(
            rp["original_max_position_embeddings"] / (n * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(pair_turning(rp["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(rp["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=_f32) - low) / (high - low),
                    0.0, 1.0)
    return (extra / rp["factor"]) * ramp + extra * (1.0 - ramp)


def _tables(cfg, kind, s):
    """cos/sin ``(S, head_dim)`` of a layer of ``kind``, halves
    duplicated."""
    d = cfg["head_dim"]
    rp = cfg["rope_parameters"][kind]
    if rp["rope_type"] == "yarn":
        inv, m = _yarn_inv_freq(d, rp), rp["attention_factor"]
    else:
        inv = 1.0 / rp["rope_theta"] ** (jnp.arange(0, d, 2, dtype=_f32) / d)
        m = 1.0
    ang = jnp.arange(s, dtype=_f32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


def _attention(cfg, w, p, kind, x, quant):
    """``x (S, E)``, one sequence from position 0: causal attention, the
    band a mask where the layer is of the window kind."""
    nh, kv, d = cfg[HEADS], cfg["num_key_value_heads"], cfg["head_dim"]
    s = x.shape[0]
    cos, sin = _tables(cfg, kind, s)
    q = _mm(x, _wide(w, p + "q"), quant).reshape(s, nh, d)
    k = _mm(x, _wide(w, p + "k"), quant).reshape(s, kv, d)
    v = _mm(x, _wide(w, p + "v"), quant).reshape(s, kv, d)
    q = _rotate(q, cos[:, None], sin[:, None])
    k = _rotate(k, cos[:, None], sin[:, None])
    # every query head gets its stored head's keys and values
    k = jnp.repeat(k, nh // kv, axis=1)
    v = jnp.repeat(v, nh // kv, axis=1)
    pos = jnp.arange(s)
    seen = pos[None, :] <= pos[:, None]
    if kind == "sliding_attention":
        seen = seen & (pos[None, :] > pos[:, None] - cfg["sliding_window"])
    low = _straight_through(quant)

    def heads(block):
        qh, kh, vh = block          # (hb, S, d) each
        scores = jnp.einsum("hqd,hkd->hqk", low(qh, -1), low(kh, -1),
                            precision=_HI) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", low(probs, -1), low(vh, -1),
                          precision=_HI)
    hb = math.gcd(nh, _HEAD_BLOCK)

    def blocks(a):                  # (S, H, d) -> (H/hb, hb, S, d)
        return a.transpose(1, 0, 2).reshape(nh // hb, hb, s, d)
    o = jax.lax.map(heads, (blocks(q), blocks(k), blocks(v)))
    o = o.reshape(nh, s, d).transpose(1, 0, 2).reshape(s, nh * d)
    return _mm(o, _wide(w, p + "o"), quant)


def _route(cfg, w, p, x, quant):
    """-> ``(dense weights (S, num_experts), tie (S,))``: each token's
    weight for every expert (0 where it is not among its chosen), and by
    how much probability its last expert in leads the first one out."""
    n, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    prob = jax.nn.softmax(_mm(x, _wide(w, p + "router").T, quant), axis=-1)
    top, chosen = jax.lax.top_k(prob, k + 1)
    wts = top[:, :k]
    if cfg["norm_topk_prob"]:
        wts = wts / jnp.sum(wts, axis=1, keepdims=True)
    dense = jnp.zeros((x.shape[0], n), _f32).at[
        jnp.arange(x.shape[0])[:, None], chosen[:, :k]].set(wts)
    return dense, top[:, k - 1] - top[:, k]


def _experts(cfg, w, p, x, quant):
    """The routed sum, every expert computed for every token."""
    dense, tie = _route(cfg, w, p, x, quant)
    wi = cfg["moe_intermediate_size"]

    def one(y, ew):
        w_in, w_out, wt = ew
        gu = _mm(x, w_in.astype(_f32), quant)
        return y + wt[:, None] * _mm(
            jax.nn.silu(gu[:, :wi]) * gu[:, wi:], w_out.astype(_f32),
            quant), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w[p + "w_in"], w[p + "w_out"], dense.T))
    return y, tie


def _hidden(cfg, w, ids, quant):
    """One sequence ``ids (S,)`` -> ``(final hidden state after its norm
    (S, E), tie (S,))``: ``tie`` the least lead, over the layers, of a
    token's last expert in over its first one out."""
    eps = cfg["rms_norm_eps"]
    x = w["tok_emb.weight"][ids].astype(_f32)
    tie = jnp.full(ids.shape, jnp.inf, _f32)
    for i in range(cfg[LAYERS]):
        b = f"blocks.{i}."
        x = x + _attention(cfg, w, b + "attn.", cfg["layer_types"][i],
                           _rms(x, _wide(w, b + "ln1.weight"), eps), quant)
        y, t = _experts(cfg, w, b + "experts.",
                        _rms(x, _wide(w, b + "ln2.weight"), eps), quant)
        x, tie = x + y, jnp.minimum(tie, t)
    return _rms(x, _wide(w, "ln_f.weight"), eps), tie


def _head(w, x, quant):
    return _mm(x, _wide(w, "lm_head.weight").T, quant)


def logits(cfg, w, ids, quant=None):
    """``ids (R, S)`` -> ``(logits (R, S, V), tie (R, S))``, one sequence
    at a time (for sizes whose logits fit; the comparison below never
    holds a whole sequence's)."""
    def one(row):
        x, tie = _hidden(cfg, w, row, quant)
        return _head(w, x, quant), tie
    return jax.lax.map(one, ids)


def _rows_of(s):
    return math.gcd(s, _HEAD_ROWS)


def _judge(cfg, w, row, picked, quant_pick):
    """One sequence -> ``(gap (S,), margin (S,), tie (S,))``, the head
    taken ``_HEAD_ROWS`` positions at a time."""
    x, tie = _hidden(cfg, w, row, None)
    r = _rows_of(row.shape[0])
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
    return gap.reshape(-1), margin.reshape(-1), tie


_READS = (HEADS, LAYERS, "num_key_value_heads", "head_dim", "layer_types",
          "sliding_window", "rope_parameters", "rms_norm_eps", "num_experts",
          "num_experts_per_tok", "norm_topk_prob", "moe_intermediate_size")


@functools.lru_cache(maxsize=None)
def _gap_fn(cfg_json, quant_pick):
    cfg = json.loads(cfg_json)

    def gaps(w, ids, picked):
        return jax.lax.map(
            lambda rp: _judge(cfg, w, rp[0], rp[1], quant_pick),
            (ids, picked))
    return jax.jit(gaps)


def gaps_margins_ties(cfg, w, ids, picked, control=None):
    """``(gaps, margins, ties)`` per position, nothing left out: ``ties``
    is the least lead in probability, over the layers, of the position's
    last expert in over its first one out (the readings tool judges one
    pass at several values of ``NEAR_TIE`` from it)."""
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
    gap, margin, tie = gaps_margins_ties(cfg, w, ids, picked, control)
    near = tie < NEAR_TIE
    judged = picked != 0            # the harness pads with token 0
    n = int(jnp.sum(judged))
    print(f"[reference] near ties (the last expert in leads the first one "
          f"out by under {NEAR_TIE} in probability, in some layer): "
          f"{int(jnp.sum(near & judged))} of {n} positions with a served "
          f"token are not judged", flush=True)
    return jnp.where(near, 0.0, gap), jnp.where(near, jnp.inf, margin)
