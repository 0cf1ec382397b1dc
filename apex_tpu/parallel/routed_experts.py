"""Routed experts with a chip's share: one device's part of an
expert-parallel feed-forward layer.

The router scores every token over ALL the layer's experts, as
published; this device holds ``experts_held`` of them and computes the
part of the result that its own experts give, for the tokens routed to
them.  Nothing is dropped (no capacity), and what the absent experts
would add is left out: on one device the layer runs without its
exchange, and the parts that all the shares give add up to the whole
layer (``tests/test_serve_latent_moe.py`` holds it to that).  Contrast
``parallel/expert_parallel.switch_moe``: one expert a device, top-1/2,
capacity-dropping, a dense one-hot dispatch.

* :func:`group_limited_route` — sigmoid scores in float32, a per-expert
  correction bias for the choice only, the best ``topk_group`` of
  ``n_group`` groups by the sum of their two best biased scores, the
  best ``top_k`` experts of those groups, weights from the unbiased
  scores, normalised and scaled.
* :func:`softmax_topk_route` — float32 logits, a softmax over ALL the
  experts, the best ``top_k`` of them, their probabilities normalised
  over the chosen: no bias, no groups.
* :class:`RoutedExperts` — the router, the held experts' matrices
  stacked (gated: gate | up and down, ``act(gate) * up``; or not gated:
  one input matrix and down, ``act(up)``), and the dispatch: token-expert
  pairs that go to held experts are sorted by expert and taken through
  two grouped matmuls (``kernels/grouped_matmul.py``: ``routed_experts``
  in a device trace); the result comes back with the pairs each held
  expert got.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.grouped_matmul import (TILE_ROWS, grouped_matmul, takes_tiles,
                                      tile_layout)
from ..nn.modules import Module
from ..nn.parameter import Parameter

_f32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def group_limited_route(x, w_router, bias, *, n_group, topk_group, top_k,
                        norm_topk=True, scale=1.0):
    """``x (T, E)`` -> ``(experts (T, top_k) int32, weights (T, top_k)
    fp32)``.  ``w_router (n_experts, E)``, ``bias (n_experts,)``.  The
    scores are computed in float32 whatever ``x`` is: where two biased
    scores lie within a rounding of each other a lower precision picks
    another expert."""
    sig = jax.nn.sigmoid(jnp.matmul(
        x.astype(_f32), w_router.astype(_f32).T, precision=_HI))
    biased = sig + bias.astype(_f32)
    t, n = biased.shape
    per = n // n_group
    group_score = jnp.sum(jax.lax.top_k(
        biased.reshape(t, n_group, per), 2)[0], axis=-1)     # (T, n_group)
    keep = jax.lax.top_k(group_score, topk_group)[1]         # (T, topk_group)
    kept = jnp.any(keep[:, :, None] == jnp.arange(n_group)[None, None, :],
                   axis=1)                                   # (T, n_group)
    masked = jnp.where(jnp.repeat(kept, per, axis=1), biased, -jnp.inf)
    experts = jax.lax.top_k(masked, top_k)[1].astype(jnp.int32)
    w = jnp.take_along_axis(sig, experts, axis=1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return experts, w * scale


def softmax_topk_route(x, w_router, *, top_k, norm_topk=True, scale=1.0):
    """``x (T, E)`` -> ``(experts (T, top_k) int32, weights (T, top_k)
    fp32)``: ``p = softmax(x W_r^T)`` over all ``n_experts``, the
    ``top_k`` largest, ``p_e / sum of the chosen p`` where ``norm_topk``
    (softmax-then-top-k renormalised and top-k-then-softmax are the same
    numbers).  Float32 at ``highest`` whatever ``x`` is, as
    :func:`group_limited_route`."""
    p = jax.nn.softmax(jnp.matmul(
        x.astype(_f32), w_router.astype(_f32).T, precision=_HI), axis=-1)
    w, experts = jax.lax.top_k(p, top_k)
    if norm_topk:
        w = w / jnp.sum(w, axis=1, keepdims=True)
    return experts.astype(jnp.int32), w * scale


#: an expert's activation: SwiGLU's gate, or the squared relu of an
#: expert that is not gated
ACTS = {"silu": jax.nn.silu, "relu2": lambda h: jnp.square(jax.nn.relu(h))}


class RoutedExperts(Module):
    """``n_experts`` routed experts of which this device holds
    ``experts_held`` (ids; default all).  ``forward(ctx, x (T, E))`` ->
    ``(y (T, E), pairs (len(experts_held),) int32)``: the held experts'
    part of the layer's routed sum, and the token-expert pairs each of
    them got.  ``gated`` (SwiGLU): ``w_in (G, E, 2*I)`` is gate | up,
    kept ``(in, out)``, and an expert is ``W_out (act(gate) * up)``; not
    gated: ``w_in (G, I, E)``, kept ``(out, in)`` as ``w_out (G, I, E)``
    lies, and an expert is ``W_out act(W_in x)`` (an intermediate width
    that is no whole number of lane rows, as 1856, is then nowhere a
    minor dimension: the device keeps a ``(E, 1856)`` matrix the other
    way round and hands a kernel that wants it row-major a copy, 0.3 GB
    a layer a step at the widths of PERF.md section 4's fourth
    configuration).  ``act``: one of
    :data:`ACTS`.  ``score``: how the router scores, ``"sigmoid"``
    (:func:`group_limited_route`, with its correction bias) or
    ``"softmax"`` (:func:`softmax_topk_route`: no bias, no groups)."""

    def __init__(self, hidden, intermediate, n_experts, top_k, *,
                 n_group=1, topk_group=1, scale=1.0, norm_topk=True,
                 experts_held=None, score="sigmoid", gated=True,
                 act="silu", init=None):
        """``init(shape, fan_in) -> Parameter`` draws (or only declares)
        a parameter; ``fan_in`` None marks a bias."""
        super().__init__()
        if score not in ("sigmoid", "softmax"):
            raise ValueError(f"score is 'sigmoid' or 'softmax', "
                             f"got {score!r}")
        if act not in ACTS:
            raise ValueError(f"act is one of {sorted(ACTS)}, got {act!r}")
        self.gated, self.act = bool(gated), act
        if score == "softmax" and (n_group, topk_group) != (1, 1):
            raise ValueError("the softmax router chooses among all "
                             "experts: it has no groups")
        self.score = score
        held = tuple(range(n_experts)) if experts_held is None \
            else tuple(int(e) for e in experts_held)
        if len(set(held)) != len(held) or \
                not all(0 <= e < n_experts for e in held):
            raise ValueError(f"experts_held {held} are not distinct ids "
                             f"below {n_experts}")
        if n_experts % n_group or topk_group > n_group \
                or top_k > topk_group * (n_experts // n_group):
            raise ValueError(
                f"{n_experts} experts do not split into {n_group} groups "
                f"of which {topk_group} give {top_k} experts")
        self.hidden, self.intermediate = hidden, intermediate
        self.n_experts, self.top_k = n_experts, top_k
        self.n_group, self.topk_group = n_group, topk_group
        self.scale, self.norm_topk = scale, norm_topk
        self.experts_held = held
        # expert id -> its place among the held ones; len(held): elsewhere
        self._local = np.full((n_experts,), len(held), np.int32)
        self._local[list(held)] = np.arange(len(held), dtype=np.int32)
        init = init or _normal_init
        g = len(held)
        self.router = init((n_experts, hidden), hidden)
        self.router_bias = init((n_experts,), None) \
            if score == "sigmoid" else None
        self.w_in = init((g, hidden, 2 * intermediate) if gated
                         else (g, intermediate, hidden), hidden)
        self.w_out = init((g, intermediate, hidden), intermediate)

    def route(self, ctx, x):
        if self.score == "softmax":
            return softmax_topk_route(
                x, ctx.value(self.router), top_k=self.top_k,
                norm_topk=self.norm_topk, scale=self.scale)
        return group_limited_route(
            x, ctx.value(self.router), ctx.value(self.router_bias),
            n_group=self.n_group, topk_group=self.topk_group,
            top_k=self.top_k, norm_topk=self.norm_topk, scale=self.scale)

    def forward(self, ctx, x, live=None):
        """``live (T,)``: rows that are real; the others (a batch
        bucket's padding, a chunk's tail) go to no expert."""
        t, e = x.shape
        g, k = len(self.experts_held), self.top_k
        experts, weights = self.route(ctx, x)                # (T, k)
        w_in, w_out = ctx.value(self.w_in), ctx.value(self.w_out)
        # rows meet the matrices in the type the matrices are stored in
        dt = w_in.dtype
        tile = TILE_ROWS \
            if takes_tiles(e, w_in.shape[2 if self.gated else 1], dt) \
            and takes_tiles(w_out.shape[1], e, dt) else 1
        # a token's experts are distinct: at most min(k, g) of them here
        group = jnp.asarray(self._local)[experts]
        if live is not None:
            group = jnp.where(live[:, None], group, g)
        lay = tile_layout(group.reshape(-1), g, t * min(k, g), tile)
        token_of_row = jnp.maximum(lay.pair_of_row, 0) // k
        xs = x.astype(dt)[token_of_row]                      # (M, E)
        up = grouped_matmul(xs, w_in, lay, transposed=not self.gated)
        i = w_out.shape[1]
        h = ACTS[self.act](up[:, :i].astype(_f32))
        if self.gated:
            h = h * up[:, i:].astype(_f32)
        ys = grouped_matmul(h.astype(dt), w_out, lay)        # (M, E)
        # each token's held pairs, read back from their rows (selected,
        # not multiplied by zero: a row no pair holds is undefined)
        row = lay.row_of_pair.reshape(t, k)
        part = jnp.where((row >= 0)[:, :, None],
                         ys[jnp.maximum(row, 0)].astype(_f32), 0.0)
        y = jnp.sum(weights[:, :, None] * part, axis=1)
        return y.astype(x.dtype), lay.sizes


def _normal_init(shape, fan_in) -> Parameter:
    """A parameter drawn N(0, 1/fan_in); zeros where ``fan_in`` is None
    (a bias)."""
    from ..nn.modules import _next_key
    if fan_in is None:
        return Parameter(jnp.zeros(shape, _f32))
    return Parameter(jax.random.normal(_next_key(), shape, _f32)
                     / np.sqrt(fan_in))
