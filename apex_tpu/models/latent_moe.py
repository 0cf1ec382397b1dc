"""Latent-attention mixture-of-experts decoder family (the DeepSeek-V3
shape): multi-head latent attention (MLA) with YaRN rotary positions,
leading dense SwiGLU layers, then layers of routed experts plus a shared
expert, RMSNorm pre-norm, no biases, an untied head.

**Attention.** ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` -> ``heads``
of ``[q_nope | q_rope]``; ``[c_kv | k_rope] = x W_kva``, ``c_kv =
RMSNorm(c_kv)``; ``k_rope`` is rotated and shared by all heads;
``[k_nope | v]`` per head ``= c_kv W_kvb``; scores ``(q_nope.k_nope +
q_rope.k_rope) * s``; causal softmax; ``o = P v`` through ``W_o``.
What a token leaves behind is ``c_kv`` after its norm and ``k_rope``
after rotation.  Matrices are read in the type they are stored in; the
residual stream and what is added to it are float32 (:func:`_mm`).  :meth:`LatentAttention.forward` is that *expanded*
form over a whole sequence; the serve path
(:meth:`LatentAttention.absorbed`) folds ``W_kvb``'s key half into the
query and its value half into the output, so that a cached row is read
once for all heads (``kernels/latent_attention.py``).  Prefill chunks
and decode both attend in absorbed form.

**Feed-forward.** Every block has a dense gated matrix pair (the wide
FFN of a leading dense layer, or the shared expert); a routed block adds
:class:`~apex_tpu.parallel.routed_experts.RoutedExperts`, told which
experts this device holds.

A block follows the serve engine's layer protocol (``serve/kernels.py``):
``cache_rows``, ``chunk_rows``, ``read_decode``, ``read_chunk``,
``finish``.  ``abstract=True`` builds the model with parameters that
have shapes and no values (weights are published into it afterwards):
the full-size model cannot be drawn in float32 first.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import nn
from ..kernels.latent_attention import latent_attend, latent_decode_attention
from ..kernels.paged_attention import gather_kv
from ..nn.parameter import Parameter, abstract_parameter
from ..normalization import FusedRMSNorm
from ..parallel.routed_experts import RoutedExperts
from .llama import apply_rope
from .yarn import (yarn_inv_freq, yarn_mscale,  # noqa: F401 (re-exported)
                   yarn_softmax_scale, yarn_tables)

_f32 = jnp.float32


# ---------------------------------------------------------------------------
# Modules
# ---------------------------------------------------------------------------


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def _mm(x, w):
    """``x @ w`` with operands in the weights' type and a float32 result:
    the residual stream and everything added to it stay float32 (a
    router reads the stream, and a bfloat16 stream tips it where
    float32 weights' worth of rounding would not), while every matrix
    is read as it is stored."""
    return jnp.matmul(x.astype(w.dtype), w, preferred_element_type=_f32)


class _NormTo(FusedRMSNorm):
    """The final norm: its output goes to the head in the weights'
    type."""

    def forward(self, ctx, x):
        return super().forward(ctx, x).astype(ctx.value(self.weight).dtype)


def _gated(x, w_in, w_out):
    """SwiGLU through ``w_in (E, 2I)`` = gate | up and ``w_out (I, E)``."""
    gu = _mm(x, w_in)
    i = w_out.shape[0]
    return _mm(jax.nn.silu(gu[..., :i]) * gu[..., i:], w_out)


class _Table(nn.Module):
    def __init__(self, weight: Parameter):
        super().__init__()
        self.weight = weight


class LatentAttention(nn.Module):
    def __init__(self, hidden, heads, q_rank, kv_rank, nope_dim, rope_dim,
                 v_dim, rope, eps, init):
        super().__init__()
        self.heads, self.kv_rank = heads, kv_rank
        self.nope_dim, self.rope_dim, self.v_dim = nope_dim, rope_dim, v_dim
        self.rope = dict(rope)
        self.scaling = yarn_softmax_scale(nope_dim + rope_dim, rope)
        #: stored width of a token's row: c_kv | k_rope | zeros to a whole
        #: number of lane rows (serve/pool.py says why)
        self.row_width = _lanes(kv_rank + rope_dim)
        self.q_a = init((hidden, q_rank), hidden)
        self.q_norm = FusedRMSNorm(q_rank, eps=eps)
        self.q_b = init((q_rank, heads * (nope_dim + rope_dim)), q_rank)
        self.kv_a = init((hidden, kv_rank + rope_dim), hidden)
        self.kv_norm = FusedRMSNorm(kv_rank, eps=eps)
        self.kv_b = init((kv_rank, heads * (nope_dim + v_dim)), kv_rank)
        self.o = init((heads * v_dim, hidden), heads * v_dim)

    def _project(self, ctx, h, positions):
        """``h (B, S, E)`` at ``positions (B, S)`` -> ``q_nope (B, S, H,
        nope)``, ``q_rope (B, S, H, rope)`` rotated, ``c_kv (B, S,
        rank)`` normed, ``k_rope (B, S, rope)`` rotated."""
        b, s, _ = h.shape
        c_q = self.q_norm.forward(ctx, _mm(h, ctx.value(self.q_a)))
        q = _mm(c_q, ctx.value(self.q_b)).reshape(
            b, s, self.heads, self.nope_dim + self.rope_dim)
        kv = _mm(h, ctx.value(self.kv_a))
        c_kv = self.kv_norm.forward(ctx, kv[..., :self.kv_rank])
        cos, sin = yarn_tables(jnp.clip(positions, 0), self.rope_dim,
                               self.rope)
        q_rope = apply_rope(q[..., self.nope_dim:], cos[:, :, None],
                            sin[:, :, None])
        k_rope = apply_rope(kv[..., self.kv_rank:], cos, sin)
        return q[..., :self.nope_dim], q_rope, c_kv, k_rope

    def _kv_b(self, ctx):
        w = ctx.value(self.kv_b).reshape(
            self.kv_rank, self.heads, self.nope_dim + self.v_dim)
        return w[..., :self.nope_dim], w[..., self.nope_dim:]

    def forward(self, ctx, h, positions):
        """Expanded causal attention over a whole sequence (no cache)."""
        b, s, _ = h.shape
        q_nope, q_rope, c_kv, k_rope = self._project(ctx, h, positions)
        w_k, w_v = self._kv_b(ctx)
        c_kv = c_kv.astype(w_k.dtype)
        k_nope = jnp.einsum("bsr,rhn->bshn", c_kv, w_k,
                            preferred_element_type=_f32)
        v = jnp.einsum("bsr,rhv->bshv", c_kv, w_v,
                       preferred_element_type=_f32)
        scores = (jnp.einsum("bqhn,bshn->bhqs", q_nope, k_nope,
                             preferred_element_type=_f32)
                  + jnp.einsum("bqhr,bsr->bhqs", q_rope, k_rope,
                               preferred_element_type=_f32)) * self.scaling
        causal = positions[:, None, :, None] >= positions[:, None, None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
        o = jnp.einsum("bhqs,bshv->bqhv", probs, v,
                       preferred_element_type=_f32)
        return _mm(o.reshape(b, s, -1), ctx.value(self.o))

    def absorbed(self, ctx, h, positions):
        """The serve path's projections: ``q (B, H, S, W)`` =
        ``[q_nope W_k^T | q_rope | 0]`` and the token's row ``(B, S, W)``
        = ``[c_kv | k_rope | 0]``, ``W`` = :attr:`row_width`."""
        q_nope, q_rope, c_kv, k_rope = self._project(ctx, h, positions)
        w_k, _ = self._kv_b(ctx)
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope.astype(w_k.dtype), w_k,
                           preferred_element_type=_f32)
        pad = self.row_width - self.kv_rank - self.rope_dim
        q = jnp.concatenate(
            [q_lat, q_rope, jnp.zeros(q_lat.shape[:-1] + (pad,), _f32)],
            axis=-1)
        row = jnp.concatenate(
            [c_kv, k_rope, jnp.zeros(c_kv.shape[:-1] + (pad,), _f32)],
            axis=-1)
        # queries meet the rows in the type the rows are stored in
        return jnp.swapaxes(q, 1, 2).astype(w_k.dtype), row

    def output(self, ctx, o_lat):
        """``o_lat (B, S, H, rank)``, the probabilities times the latent
        rows, through ``W_kvb``'s value half and ``W_o``."""
        _, w_v = self._kv_b(ctx)
        o = jnp.einsum("bshr,rhv->bshv", o_lat.astype(w_v.dtype), w_v,
                       preferred_element_type=_f32)
        return _mm(o.reshape(o.shape[:2] + (-1,)), ctx.value(self.o))


class LatentMoeBlock(nn.Module):
    """RMSNorm -> latent attention -> residual, RMSNorm -> dense gated
    FFN (+ routed experts' held part) -> residual."""

    def __init__(self, hidden, attn: LatentAttention, dense_intermediate,
                 experts, eps, init):
        super().__init__()
        self.ln1 = FusedRMSNorm(hidden, eps=eps)
        self.attn = attn
        self.ln2 = FusedRMSNorm(hidden, eps=eps)
        self.w_in = init((hidden, 2 * dense_intermediate), hidden)
        self.w_out = init((dense_intermediate, hidden), dense_intermediate)
        self.experts = experts

    def _ffn(self, ctx, h, live=None):
        """-> ``(y, pairs)``: ``pairs`` the token-expert pairs each held
        expert got from the ``live`` rows (the others go to no expert),
        None for a dense layer."""
        y = _gated(h, ctx.value(self.w_in), ctx.value(self.w_out))
        if self.experts is None:
            return y, None
        routed, pairs = self.experts.forward(
            ctx, h.reshape(-1, h.shape[-1]),
            None if live is None else live.reshape(-1))
        return y + routed.reshape(h.shape), pairs

    def forward(self, ctx, x, positions):
        x = x + self.attn.forward(ctx, self.ln1.forward(ctx, x), positions)
        return x + self._ffn(ctx, self.ln2.forward(ctx, x))[0]

    # -- the serve engine's layer protocol (serve/kernels.py) --------------

    @property
    def cache_rows(self):
        """One row a token, read by every head alike."""
        return 1, 1, self.attn.row_width

    def chunk_rows(self, ctx, x, positions):
        q, row = self.attn.absorbed(ctx, self.ln1.forward(ctx, x), positions)
        return q, (row,)

    def read_decode(self, q, pool, layer, tables, positions, window):
        return latent_decode_attention(
            q[:, :, 0], pool, layer, tables, positions, self.attn.scaling,
            self.attn.kv_rank, window)[:, None]

    def read_chunk(self, q, pool, layer, tables, positions, window):
        rows, = gather_kv(pool, layer, tables)
        return latent_attend(q, rows, positions, self.attn.scaling,
                             self.attn.kv_rank, window)

    def finish(self, ctx, x, o, live):
        x = x + self.attn.output(ctx, o)
        y, pairs = self._ffn(ctx, self.ln2.forward(ctx, x), live)
        return x + y, pairs


class LatentMoeModel(nn.Module):
    """Token embedding -> ``first_dense`` dense blocks, then routed
    blocks -> RMSNorm -> untied head.  ``forward(ids (B, S)) -> logits
    (B, S, V)``.  ``experts_held``: the routed experts this device holds
    in every routed layer (ids; default all of them)."""

    def __init__(self, vocab_size, hidden, layers, heads, *, q_rank,
                 kv_rank, nope_dim, rope_dim, v_dim, dense_intermediate,
                 expert_intermediate, n_experts, top_k, n_group=1,
                 topk_group=1, n_shared=1, route_scale=1.0, norm_topk=True,
                 first_dense=1, experts_held=None, rope=None,
                 max_positions=4096, eps=1e-6, dtype=_f32, abstract=False):
        super().__init__()
        rope = rope or dict(rope_theta=10000.0, factor=1.0,
                            original_max_position_embeddings=max_positions,
                            beta_fast=32, beta_slow=1)

        def init(shape, fan_in):
            if abstract:
                return abstract_parameter(shape, dtype)
            if fan_in is None:
                return Parameter(jnp.zeros(shape, dtype))
            return Parameter((jax.random.normal(
                nn.modules._next_key(), shape, _f32)
                / math.sqrt(fan_in)).astype(dtype))

        self.vocab_size, self.hidden = vocab_size, hidden
        self.max_positions = max_positions
        self.tok_emb = _Table(init((vocab_size, hidden), hidden))
        blocks = []
        for i in range(layers):
            routed = i >= first_dense
            experts = RoutedExperts(
                hidden, expert_intermediate, n_experts, top_k,
                n_group=n_group, topk_group=topk_group, scale=route_scale,
                norm_topk=norm_topk, experts_held=experts_held,
                init=init) if routed else None
            blocks.append(LatentMoeBlock(
                hidden,
                LatentAttention(hidden, heads, q_rank, kv_rank, nope_dim,
                                rope_dim, v_dim, rope, eps, init),
                n_shared * expert_intermediate if routed
                else dense_intermediate, experts, eps, init))
        self.blocks = nn.ModuleList(blocks)
        self.ln_f = _NormTo(hidden, eps=eps)
        self.lm_head = _Table(init((vocab_size, hidden), hidden))

    def forward(self, ctx, input_ids):
        b, s = input_ids.shape
        if s > self.max_positions:
            raise ValueError(f"sequence length {s} exceeds max_positions "
                             f"{self.max_positions}")
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        x = ctx.value(self.tok_emb.weight)[input_ids]
        for blk in self.blocks:
            x = blk.forward(ctx, x, pos)
        x = self.ln_f.forward(ctx, x)
        return jnp.matmul(x, ctx.value(self.lm_head.weight).T)

    def _mask_pad_logits(self, logits):
        return logits               # the vocabulary is not padded
