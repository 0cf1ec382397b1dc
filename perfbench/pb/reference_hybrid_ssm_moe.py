"""The plain reference of the state-space / attention / mixture-of-experts
hybrid configurations (their ``reference`` key names this file): the
Nemotron-H architecture as its ``config.json`` declares it, in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul
precision.  No kernels, no cache, no sorting of tokens, no batching, no
chunked form: **the recurrence is computed as it is written, one position
after another** (a ``lax.scan`` over time), the convolution is a sum of
shifted copies of the sequence, attention is full causal attention with
the stored heads' keys and values repeated to every query head, and every
held expert is computed for every token and weighted by its routing
weight (0 where the token was not routed to it).  It imports nothing of
the program and takes only the benchmark's own leaves
(``families/hybrid_ssm_moe.py``), in the type they are served in: each is
widened where it is used, an expert at a time.

The equations (configuration keys in brackets).  Every layer: ``x <- x +
Mixer_l(RMSNorm(x))`` [layer_norm_epsilon], its mixer the l-th character
of [hybrid_override_pattern]; no biases on linear layers, a final
RMSNorm, an untied head.

* ``M``, Mamba-2 ([mamba_num_heads] heads of [mamba_head_dim] channels;
  [n_groups] groups of ``B`` and ``C`` of [ssm_state_size]): ``[z | xBC |
  dt] = u W_in``; ``xBC_t <- silu(b + sum_j w_j xBC_{t-K+1+j})``
  [conv_kernel] taps with a bias, zeros before the sequence's start;
  ``xBC -> x, B, C``, head ``h`` reads group ``h // (heads / groups)``;
  ``dt_t = softplus(dt_t + dt_bias)``; ``a_t = exp(dt_t A)``, ``A =
  -exp(A_log)``, a scalar a head; ``H_t = a_t H_{t-1} + dt_t x_t (x)
  B_t``, ``H`` (channels x state) a head, zeros before the start; ``y_t =
  H_t C_t + D x_t``; ``y <- RMSNorm_groups(y * silu(z))``: the gate
  first, then an RMS norm over each group's channels, with a weight;
  ``out = y W_out``.
* ``E``, experts: ``s = sigmoid(h W_r^T)`` over all [router_experts];
  the [num_experts_per_tok] largest of ``s + b`` (the correction bias);
  weights ``s_e`` over the sum of the chosen [norm_topk_prob] times
  [routed_scaling_factor]; an expert is not gated: ``W_down relu(W_up
  h)^2`` [mlp_hidden_act, moe_intermediate_size]; the shared expert the
  same [moe_shared_expert_intermediate_size], added unweighted.  Only the
  experts of [experts_held] are here; what the others would add is left
  out, as in the program.
* ``*``, attention: ``q = h W_q`` -> heads of [head_dim]; ``k, v`` ->
  [num_key_value_heads] stored heads; query head ``i`` reads stored head
  ``i // (heads / stored heads)``; scores ``q.k / sqrt(head_dim)``,
  causal, float32 softmax; ``o = (P v) W_o``.  Nothing is rotated.

Departures from the published model, each stated in the configuration
file: (1) fewer layers, the first of the pattern; (2) a share of the
experts and of the vocabulary; (3) weights are random from the seed.

**A router can tip under rounding.**  Where, in any layer, the
reference's own last expert in and first expert out lie within
[NEAR_TIE] in biased score and one of the two is held here, a hidden
state rounded to bfloat16 can put the other one in, and the token served
after is then not wrong.  Such positions are returned as not judged (gap
0, margin infinite) and their share is printed.  ``gaps_margins_ties``
returns each position's least lead, so a reading at any threshold takes
one pass (PERF.md section 4 has the readings behind the value).

``quant="int8"`` or ``"fp8"`` is the *control* (``pb.refmath``): the same
reference with every linear layer's matrix multiplications, the router's
among them, and attention's operands computed one precision down.
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
#: and first expert out are closer than this in biased score, one of
#: them held, are not judged (PERF.md section 4 has the readings at 0 ..
#: 1.6e-2 behind it); 0: every position is judged
NEAR_TIE = 1e-3

#: query heads attended at a time: (heads, S, S) float32 scores at S =
#: 4096 are 67 MiB a head
_HEAD_BLOCK = 4
#: positions taken through the head at a time
_HEAD_ROWS = 1024

_f32 = jnp.float32


def _wide(w, name):
    return w[name].astype(_f32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _mamba(cfg, w, p, u, quant, n=None):
    """``u (S, E)``, one sequence from position 0 -> ``(out (S, E), H)``:
    ``H (heads, channels, state)`` as the last position leaves it, or
    the ``n``-th where ``n`` is given (the positions after it step
    nothing: their ``dt`` is 0).  Under the control ``H`` is kept in
    bfloat16 between positions, the precision below the one the
    configuration states for it."""
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n_state = cfg["n_groups"], cfg["ssm_state_size"]
    taps = cfg["conv_kernel"]
    inner, gn = heads * hd, groups * n_state
    s = u.shape[0]
    zxd = _mm(u, _wide(w, p + "in_proj"), quant)
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:2 * inner + 2 * gn], \
        zxd[:, 2 * inner + 2 * gn:]
    taps_w = _wide(w, p + "conv_w")
    conv = _wide(w, p + "conv_b") + sum(
        taps_w[j] * jnp.pad(xbc, ((taps - 1 - j, 0), (0, 0)))[:s]
        for j in range(taps))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(s, heads, hd)
    # each head gets its group's B and C
    b = jnp.repeat(xbc[:, inner:inner + gn].reshape(s, groups, n_state),
                   heads // groups, axis=1)
    c = jnp.repeat(xbc[:, inner + gn:].reshape(s, groups, n_state),
                   heads // groups, axis=1)
    dt = jax.nn.softplus(dt + _wide(w, p + "dt_bias"))       # (S, heads)
    if n is not None:
        dt = jnp.where((jnp.arange(s) < n)[:, None], dt, 0.0)
    a = -jnp.exp(_wide(w, p + "a_log"))

    def step(h, t):
        x_t, b_t, c_t, dt_t = t
        h = jnp.exp(dt_t * a)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        if quant is not None:
            # (a cast there and back is removed as an identity)
            h = jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)
    last, y = jax.lax.scan(step, jnp.zeros((heads, hd, n_state), _f32),
                           (x, b, c, dt))
    y = y + _wide(w, p + "d")[None, :, None] * x
    y = y.reshape(s, inner) * jax.nn.silu(z)
    y = _rms(y.reshape(s, groups, -1), 1.0, cfg["layer_norm_epsilon"])
    y = y.reshape(s, inner) * _wide(w, p + "norm")
    return _mm(y, _wide(w, p + "out_proj"), quant), last


def _attention(cfg, w, p, x, quant):
    """``x (S, E)``, one sequence from position 0: full causal
    attention, nothing rotated."""
    nh, kv, d = cfg[HEADS], cfg["num_key_value_heads"], cfg["head_dim"]
    s = x.shape[0]
    q = _mm(x, _wide(w, p + "q"), quant).reshape(s, nh, d)
    k = _mm(x, _wide(w, p + "k"), quant).reshape(s, kv, d)
    v = _mm(x, _wide(w, p + "v"), quant).reshape(s, kv, d)
    # every query head gets its stored head's keys and values
    k = jnp.repeat(k, nh // kv, axis=1)
    v = jnp.repeat(v, nh // kv, axis=1)
    pos = jnp.arange(s)
    seen = pos[None, :] <= pos[:, None]
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


def _relu2(x, w_in, w_out, quant):
    return _mm(jnp.square(jax.nn.relu(_mm(x, w_in, quant))), w_out, quant)


def _route(cfg, w, p, x, quant):
    """-> ``(dense weights (S, router_experts), tie (S,))``: each token's
    weight for every expert of the layer (0 where it is not among its
    chosen), and by how much biased score its last expert in leads the
    first one out (infinite where neither is held here)."""
    n, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    held = jnp.zeros((n,), bool).at[jnp.asarray(cfg["experts_held"])].set(
        True)
    sig = jax.nn.sigmoid(_mm(x, _wide(w, p + "router").T, quant))
    ev, ei = jax.lax.top_k(sig + _wide(w, p + "router_bias"), k + 1)
    chosen = ei[:, :k]
    wts = jnp.take_along_axis(sig, chosen, axis=1)
    if cfg["norm_topk_prob"]:
        wts = wts / jnp.sum(wts, axis=1, keepdims=True)
    wts = wts * cfg["routed_scaling_factor"]
    dense = jnp.zeros((x.shape[0], n), _f32).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(wts)
    at_stake = held[ei[:, k - 1]] | held[ei[:, k]]
    return dense, jnp.where(at_stake, ev[:, k - 1] - ev[:, k], jnp.inf)


def _experts(cfg, w, p, x, quant):
    """The shared expert plus the held experts' part of the routed sum,
    every held expert computed for every token."""
    dense, tie = _route(cfg, w, p, x, quant)
    held = jnp.asarray(cfg["experts_held"])

    def one(y, ew):
        w_in, w_out, wt = ew
        # a routed expert's input matrix lies (out, in)
        return y + wt[:, None] * _relu2(x, w_in.astype(_f32).T,
                                        w_out.astype(_f32), quant), None
    shared = _relu2(x, _wide(w, p[:-len("experts.")] + "w_in"),
                    _wide(w, p[:-len("experts.")] + "w_out"), quant)
    y, _ = jax.lax.scan(one, shared,
                        (w[p + "w_in"], w[p + "w_out"], dense[:, held].T))
    return y, tie


def _hidden(cfg, w, ids, quant):
    """One sequence ``ids (S,)`` -> ``(final hidden state after its norm
    (S, E), tie (S,))``: ``tie`` the least lead, over the expert layers,
    of a token's last expert in over its first one out."""
    eps = cfg["layer_norm_epsilon"]
    x = w["tok_emb.weight"][ids].astype(_f32)
    tie = jnp.full(ids.shape, jnp.inf, _f32)
    for i, kind in enumerate(cfg["hybrid_override_pattern"][:cfg[LAYERS]]):
        b = f"blocks.{i}."
        h = _rms(x, _wide(w, b + "ln1.weight"), eps)
        if kind == "M":
            x = x + _mamba(cfg, w, b + "mixer.", h, quant)[0]
        elif kind == "*":
            x = x + _attention(cfg, w, b + "attn.", h, quant)
        else:
            y, t = _experts(cfg, w, b + "experts.", h, quant)
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


def _judge(cfg, w, row, picked, quant_pick):
    """One sequence -> ``(gap (S,), margin (S,), tie (S,))``, the head
    taken ``_HEAD_ROWS`` positions at a time."""
    x, tie = _hidden(cfg, w, row, None)
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
    return gap.reshape(-1), margin.reshape(-1), tie


def _states_before_a_router(cfg, w, row, n, quant):
    """One sequence -> ``H (layers, heads, channels, state)`` after its
    first ``n`` positions, of the state-space layers that no expert layer
    precedes: what they are handed no router has touched, so no tipped
    expert stands between the program's state and this one."""
    eps = cfg["layer_norm_epsilon"]
    x = w["tok_emb.weight"][row].astype(_f32)
    states = []
    for i, kind in enumerate(clean_state_layers(cfg, upto=True)):
        b = f"blocks.{i}."
        h = _rms(x, _wide(w, b + "ln1.weight"), eps)
        if kind == "M":
            y, last = _mamba(cfg, w, b + "mixer.", h, quant, n)
            states.append(last)
        else:
            y = _attention(cfg, w, b + "attn.", h, quant)
        x = x + y
    return jnp.stack(states)


def clean_state_layers(cfg, upto=False):
    """The state-space layers that no expert layer precedes (their places
    in the model); ``upto``: the kinds of all layers before the first
    expert layer instead."""
    served = cfg["hybrid_override_pattern"][:cfg[LAYERS]]
    head = served.split("E")[0]
    return head if upto else [i for i, kind in enumerate(head)
                              if kind == "M"]


_READS = (HEADS, LAYERS, "hybrid_override_pattern", "mamba_num_heads",
          "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel",
          "num_key_value_heads", "head_dim", "layer_norm_epsilon",
          "router_experts", "experts_held", "num_experts_per_tok",
          "norm_topk_prob", "routed_scaling_factor")


@functools.lru_cache(maxsize=None)
def _gap_fn(cfg_json, quant_pick):
    cfg = json.loads(cfg_json)

    def gaps(w, ids, picked):
        return jax.lax.map(
            lambda rp: _judge(cfg, w, rp[0], rp[1], quant_pick),
            (ids, picked))
    return jax.jit(gaps)


@functools.lru_cache(maxsize=None)
def _state_fn(cfg_json, quant):
    cfg = json.loads(cfg_json)

    def states(w, ids, lengths):
        return jax.lax.map(
            lambda rn: _states_before_a_router(cfg, w, rn[0], rn[1], quant),
            (ids, lengths))
    return jax.jit(states)


def session_states(cfg, w, ids, lengths, control=None):
    """``ids (R, S)``, ``lengths (R,)`` -> ``H (R, layers, heads,
    channels, state)``: what each sequence's first ``lengths`` positions
    leave in the state-space layers of :func:`clean_state_layers`, the
    recurrence one position after another in float32.  With ``control``
    the lower-precision reference's (its state kept in bfloat16)."""
    key = json.dumps({k: cfg[k] for k in _READS}, sort_keys=True)
    return _state_fn(key, control)(w, ids, lengths)


def gaps_margins_ties(cfg, w, ids, picked, control=None):
    """``(gaps, margins, ties)`` per position, nothing left out: ``ties``
    is the least lead in biased score, over the expert layers, of the
    position's last expert in over its first one out where one of them is
    held (a readings tool judges one pass at several values of
    ``NEAR_TIE`` from it)."""
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
          f"out by under {NEAR_TIE} in biased score, one of them held): "
          f"{int(jnp.sum(near & judged))} of {n} positions with a served "
          f"token are not judged", flush=True)
    return jnp.where(near, 0.0, gap), jnp.where(near, jnp.inf, margin)
