"""Grouped-query mixture-of-experts decoder family whose layers mix
window and full attention (the Mellum 2 / Gemma-style layer pattern with
a Qwen-MoE feed-forward): rotary grouped-query attention, each layer of
one *kind* — it reads the last ``window`` keys, or all of them — with
the rotary tables of its kind, and routed experts behind a softmax
top-k router as the whole feed-forward part (no shared expert, no dense
layer).  RMSNorm pre-norm, no biases, an untied head.

**Attention, layer l.**  ``q = h W_q`` -> ``heads`` of ``head_dim``;
``k = h W_k``, ``v = h W_v`` -> ``kv_heads`` of ``head_dim``; query head
``i`` reads stored head ``i // (heads / kv_heads)``; ``q`` and ``k`` are
rotated (rotate-half) by the layer's tables: plain RoPE, or YaRN with its
attention factor on cos and sin (``models/yarn.py``), so that a YaRN
layer's scores carry the factor's square; scores ``q.k / sqrt(head_dim)``,
float32 softmax over the keys ``s <= p`` and, in a window layer, ``s > p
- window`` (``window`` keys, the query's own among them); ``o = (P v)
W_o``.  What a token leaves behind is its rotated ``k`` and its ``v`` of
the stored heads.

**Feed-forward.**  :class:`~apex_tpu.parallel.routed_experts.RoutedExperts`
with ``score="softmax"``, told which experts this device holds (default:
all of them).

Matrices are read in the type they are stored in; the residual stream
and what is added to it are float32 (``latent_moe._mm`` says why: a
router reads the stream).  A block follows the serve engine's layer
protocol (``serve/kernels.py``): ``cache_rows``, ``window``,
``chunk_rows``, ``read_decode``, ``read_chunk``, ``finish``; the engine
groups the layers by the first two, so the window layers' blocks retire
and the full layers' do not.  ``abstract=True`` builds the model with
parameters that have shapes and no values.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import nn
from ..kernels.paged_attention import (attend, gather_kv,
                                       paged_decode_attention)
from ..nn.parameter import Parameter, abstract_parameter
from ..normalization import FusedRMSNorm
from ..parallel.routed_experts import RoutedExperts
from .latent_moe import _mm, _NormTo, _Table
from .llama import apply_rope, rope_tables
from .yarn import yarn_tables

_f32 = jnp.float32


class GqaAttention(nn.Module):
    """``window``: the keys a query reads (None: all).  ``rope``: the
    layer's rotary parameters, ``{"rope_theta": ...}`` for plain RoPE or
    the YaRN block (``factor``, ``original_max_position_embeddings``,
    ``beta_fast``, ``beta_slow`` beside it); None: nothing is rotated
    (a model whose other layers carry the order)."""

    def __init__(self, hidden, heads, kv_heads, head_dim, window, rope, init):
        super().__init__()
        if heads % kv_heads:
            raise ValueError(f"{heads} query heads do not share "
                             f"{kv_heads} stored heads evenly")
        self.heads, self.kv_heads, self.head_dim = heads, kv_heads, head_dim
        self.window = window
        self.rope = None if rope is None else dict(rope)
        self.scaling = head_dim ** -0.5
        self.q = init((hidden, heads * head_dim), hidden)
        self.k = init((hidden, kv_heads * head_dim), hidden)
        self.v = init((hidden, kv_heads * head_dim), hidden)
        self.o = init((heads * head_dim, hidden), heads * head_dim)

    def tables(self, positions):
        """cos/sin ``(..., head_dim)`` of this layer's kind."""
        if "factor" in self.rope:
            return yarn_tables(positions, self.head_dim, self.rope)
        return rope_tables(positions, self.head_dim, self.rope["rope_theta"])

    def project(self, ctx, h, positions):
        """``h (B, S, E)`` at ``positions (B, S)`` -> ``q (B, S, H, D)``
        and ``k (B, S, KV, D)`` rotated, ``v (B, S, KV, D)``; float32."""
        b, s, _ = h.shape
        d = self.head_dim
        if self.rope is not None:
            cos, sin = self.tables(jnp.clip(positions, 0))
            cos, sin = cos[:, :, None], sin[:, :, None]
        q = _mm(h, ctx.value(self.q)).reshape(b, s, self.heads, d)
        k = _mm(h, ctx.value(self.k)).reshape(b, s, self.kv_heads, d)
        v = _mm(h, ctx.value(self.v)).reshape(b, s, self.kv_heads, d)
        if self.rope is None:
            return q, k, v
        return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v

    def forward(self, ctx, h, positions):
        """Causal (banded) attention over a whole sequence, no cache:
        the chunk path's ``attend`` with the sequence as its own view."""
        b, s, _ = h.shape
        q, k, v = self.project(ctx, h, positions)
        o = attend(jnp.swapaxes(q, 1, 2), k.reshape(b, s, -1),
                   v.reshape(b, s, -1), positions, self.scaling, self.window)
        return _mm(o, ctx.value(self.o))


class GqaRows:
    """What a block whose ``attn`` is a :class:`GqaAttention` behind
    ``ln1`` keeps of a token and how its queries read it: the rows'
    half of the serve engine's layer protocol (``serve/kernels.py``)."""

    @property
    def cache_rows(self):
        """A K and a V row a token, the stored heads side by side."""
        return 2, self.attn.kv_heads, self.attn.head_dim

    @property
    def window(self):
        return self.attn.window

    def chunk_rows(self, ctx, x, positions):
        q, k, v = self.attn.project(ctx, self.ln1.forward(ctx, x), positions)
        b, s = x.shape[:2]
        # queries meet the rows in the type the rows are stored in
        dt = ctx.value(self.attn.k).dtype
        return jnp.swapaxes(q, 1, 2).astype(dt), \
            (k.reshape(b, s, -1), v.reshape(b, s, -1))

    def read_decode(self, q, pool, layer, tables, positions, window):
        return paged_decode_attention(
            q[:, :, 0], pool, layer, tables, positions, self.attn.scaling,
            window)[:, None]

    def read_chunk(self, q, pool, layer, tables, positions, window):
        k, v = gather_kv(pool, layer, tables)
        return attend(q, k, v, positions, self.attn.scaling, window)


class GqaMoeBlock(GqaRows, nn.Module):
    """RMSNorm -> grouped-query attention -> residual, RMSNorm -> routed
    experts -> residual."""

    def __init__(self, hidden, attn: GqaAttention, experts: RoutedExperts,
                 eps):
        super().__init__()
        self.ln1 = FusedRMSNorm(hidden, eps=eps)
        self.attn = attn
        self.ln2 = FusedRMSNorm(hidden, eps=eps)
        self.experts = experts

    def _ffn(self, ctx, h, live=None):
        """-> ``(y, pairs)``: the held experts' part of the routed sum
        and the pairs each of them got from the ``live`` rows."""
        y, pairs = self.experts.forward(
            ctx, h.reshape(-1, h.shape[-1]),
            None if live is None else live.reshape(-1))
        return y.reshape(h.shape), pairs

    def forward(self, ctx, x, positions):
        x = x + self.attn.forward(ctx, self.ln1.forward(ctx, x), positions)
        return x + self._ffn(ctx, self.ln2.forward(ctx, x))[0]

    # -- the serve engine's layer protocol: the rows are GqaRows' ----------

    def finish(self, ctx, x, o, live):
        x = x + _mm(o, ctx.value(self.attn.o))
        y, pairs = self._ffn(ctx, self.ln2.forward(ctx, x), live)
        return x + y, pairs


class GqaMoeModel(nn.Module):
    """Token embedding -> blocks -> RMSNorm -> untied head.
    ``forward(ids (B, S)) -> logits (B, S, V)``.  ``layer_windows``: a
    window (or None) a layer, which also counts the layers;
    ``rope_full`` / ``rope_window``: the rotary parameters of the two
    kinds (:class:`GqaAttention`).  Every layer holds all its experts."""

    def __init__(self, vocab_size, hidden, heads, kv_heads, head_dim, *,
                 layer_windows, expert_intermediate, n_experts, top_k,
                 rope_full, rope_window=None, norm_topk=True,
                 max_positions=4096, eps=1e-6,
                 dtype=_f32, abstract=False):
        super().__init__()

        def init(shape, fan_in):
            if abstract:
                return abstract_parameter(shape, dtype)
            return Parameter((jax.random.normal(
                nn.modules._next_key(), shape, _f32)
                / math.sqrt(fan_in)).astype(dtype))

        self.vocab_size, self.hidden = vocab_size, hidden
        self.max_positions = max_positions
        self.tok_emb = _Table(init((vocab_size, hidden), hidden))
        self.blocks = nn.ModuleList([GqaMoeBlock(
            hidden,
            GqaAttention(hidden, heads, kv_heads, head_dim, window,
                         rope_full if window is None
                         else (rope_window or rope_full), init),
            RoutedExperts(hidden, expert_intermediate, n_experts, top_k,
                          norm_topk=norm_topk, score="softmax", init=init),
            eps) for window in layer_windows])
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
