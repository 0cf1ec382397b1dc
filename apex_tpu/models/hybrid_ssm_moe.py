"""State-space / attention / mixture-of-experts hybrid decoder family (the
Nemotron-H shape): every layer is ONE mixer behind one RMSNorm, ``x <- x +
Mixer_l(RMSNorm(x))``, its kind the l-th character of a pattern string:
``M`` a Mamba-2 state-space mixer, ``*`` grouped-query attention without
rotary positions (the state-space layers carry the order), ``E`` routed
experts that are not gated plus one shared expert.  No biases on linear
layers, a final RMSNorm, an untied head.

**``M``, Mamba-2.**  ``[z | xBC | dt] = u W_in``; ``xBC_t <- silu(b +
sum_j w_j * xBC_{t-K+1+j})``, a causal depthwise convolution ``K`` wide
with zeros before the sequence's start; ``xBC -> x (heads x P), B (G x
N), C (G x N)``, head ``h`` reads group ``h // (heads / G)``; ``dt_t =
softplus(dt_t + dt_bias)``, ``a_t = exp(dt_t A)``, ``A = -exp(A_log)``, a
scalar a head; ``H_t = a_t H_{t-1} + dt_t x_t (x) B_t``; ``y_t = H_t C_t
+ D x_t``; ``y <- RMSNorm_groups(y * silu(z))``, the gate first and then
an RMS norm over each group's channels, with a weight; ``out = y
W_out``.  What a *session* leaves behind is ``H`` (float32, ``(N, heads *
P)``: ``kernels/ssm.py`` says why that way round) and the convolution's
last ``K - 1`` inputs; nothing is kept of a token.

**``E``, experts.**  :class:`~apex_tpu.parallel.routed_experts.RoutedExperts`
with ``gated=False, act="relu2"`` (``W_down relu(W_up h)^2``), told which
experts this device holds, plus the shared expert, the same form and
added unweighted.  Keeps nothing.

**``*``, attention.**  :class:`~apex_tpu.models.gqa_moe.GqaAttention` with
``rope=None``; keeps a K and a V row of its stored heads.

Matrices are read in the type they are stored in; the residual stream
and what is added to it are float32 (``latent_moe._mm`` says why),
softplus, decay and state float32.  A block follows the serve engine's
layer protocol (``serve/kernels.py``): an attention block its rows half
(``cache_rows``, ``chunk_rows``, ``read_decode``, ``read_chunk``), the
other two its state half (``cache_rows = None``, ``state``, ``step``,
``chunk``), all of them ``finish``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import nn
from ..kernels.ssm import ssm_chunk_scan, ssm_state_update
from ..nn.parameter import Parameter, abstract_parameter
from ..normalization import FusedRMSNorm
from ..parallel.routed_experts import ACTS, RoutedExperts
from .gqa_moe import GqaAttention, GqaRows
from .latent_moe import _mm, _NormTo, _Table

_f32 = jnp.float32


class MambaMixer(nn.Module):
    """The Mamba-2 mixer of the module docstring.  ``heads`` of ``head_dim``
    channels, ``groups`` of ``B``/``C`` of ``state_size``, a convolution
    ``conv_kernel`` wide, the chunked form ``chunk_size`` positions a
    chunk."""

    def __init__(self, hidden, heads, head_dim, groups, state_size,
                 conv_kernel, chunk_size, eps, init):
        super().__init__()
        if heads % groups:
            raise ValueError(f"{heads} heads do not share {groups} groups "
                             f"evenly")
        self.heads, self.head_dim, self.groups = heads, head_dim, groups
        self.state_size, self.conv_kernel = state_size, conv_kernel
        self.chunk_size, self.eps = chunk_size, eps
        self.inner = heads * head_dim
        self.conv_dim = self.inner + 2 * groups * state_size
        self.in_proj = init((hidden, 2 * self.inner
                             + 2 * groups * state_size + heads), hidden)
        self.conv_w = init((conv_kernel, self.conv_dim), conv_kernel)
        self.conv_b = init((self.conv_dim,), None)
        self.dt_bias = init((heads,), None)
        self.a_log = init((heads,), None)
        self.d = init((heads,), 0)
        self.norm = init((self.inner,), 0)
        self.out_proj = init((self.inner, hidden), self.inner)

    @property
    def state(self):
        """What a session leaves behind: ``H`` and the convolution's
        last inputs, the latter in the type the matrices are stored in
        (they are rounded to it before they are convolved, in a chunk
        and in a step alike)."""
        return (((self.state_size, self.inner), jnp.dtype(_f32)),
                ((self.conv_kernel - 1, self.conv_dim),
                 jnp.dtype(self.in_proj.data.dtype)))

    def _split(self, ctx, u):
        """``u (T, E)`` -> ``z (T, inner)``, ``xBC (T, conv_dim)`` rounded
        to the type it is kept in, ``dt (T, heads)`` after its bias and
        softplus."""
        w = ctx.value(self.in_proj)
        zxd = _mm(u, w)
        z, xbc, dt = jnp.split(
            zxd, [self.inner, self.inner + self.conv_dim], axis=-1)
        dt = jax.nn.softplus(dt + ctx.value(self.dt_bias).astype(_f32))
        return z, xbc.astype(w.dtype), dt

    def _conv(self, ctx, window):
        """``window (K, T, conv_dim)``: row ``t``'s input and the ``K -
        1`` before it, oldest first -> ``x (T, heads, P)``, ``B``, ``C
        (T, G, N)``, float32."""
        w = ctx.value(self.conv_w).astype(_f32)
        acc = ctx.value(self.conv_b).astype(_f32) + sum(
            w[j] * window[j].astype(_f32) for j in range(self.conv_kernel))
        xbc = jax.nn.silu(acc)
        gn = self.groups * self.state_size
        t = xbc.shape[0]
        return (xbc[:, :self.inner].reshape(t, self.heads, self.head_dim),
                xbc[:, self.inner:self.inner + gn].reshape(
                    t, self.groups, self.state_size),
                xbc[:, self.inner + gn:].reshape(
                    t, self.groups, self.state_size))

    def _a(self, ctx):
        return -jnp.exp(ctx.value(self.a_log).astype(_f32))

    def _out(self, ctx, y, x, z):
        """``y (T, heads, P)`` = ``H C`` -> the mixer's output: the skip
        ``D x``, the gate, the norm over each group's channels, ``W_out``."""
        t = y.shape[0]
        y = y + ctx.value(self.d).astype(_f32)[None, :, None] * x
        y = y.reshape(t, self.inner) * jax.nn.silu(z)
        g = y.reshape(t, self.groups, -1)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                              + self.eps)
        y = g.reshape(t, self.inner) * ctx.value(self.norm).astype(_f32)
        return _mm(y, ctx.value(self.out_proj))

    def step(self, ctx, u, state, conv, layer, slots):
        """One position of ``B`` sessions: ``u (B, E)``; ``state`` and
        ``conv`` the buffers of every session's state, ``(layers, slots)``
        before the shapes of :attr:`state`; -> ``(out (B, E), state,
        conv)``."""
        z, xbc, dt = self._split(ctx, u)
        before = conv[layer, slots]                          # (B, K-1, C)
        window = jnp.concatenate([before, xbc[:, None]], axis=1)
        conv = conv.at[layer, slots].set(window[:, 1:])
        x, b, c = self._conv(ctx, jnp.swapaxes(window, 0, 1))
        p = self.head_dim
        y, state = ssm_state_update(
            state, layer, slots,
            jnp.repeat(jnp.exp(dt * self._a(ctx)), p, axis=1),
            jnp.repeat(dt, p, axis=1) * x.reshape(-1, self.inner), b, c)
        return self._out(ctx, y.reshape(x.shape), x, z), state, conv

    def chunk(self, ctx, u, h0, before, n_real):
        """``Q`` positions of one session: ``u (Q, E)`` of which the
        first ``n_real`` are real; ``h0 (N, inner)`` and ``before (K - 1,
        conv_dim)`` what the session left behind (zeros at its start);
        -> ``(out (Q, E), hT, after)``.  A row past ``n_real`` leaves the
        state alone (its ``dt`` is 0) and ``after`` is the last ``K - 1``
        *real* inputs."""
        q, k = u.shape[0], self.conv_kernel
        z, xbc, dt = self._split(ctx, u)
        dt = jnp.where((jnp.arange(q) < n_real)[:, None], dt, 0.0)
        ext = jnp.concatenate([before, xbc], axis=0)         # (K-1+Q, C)
        after = jax.lax.dynamic_slice_in_dim(ext, n_real, k - 1, axis=0)
        x, b, c = self._conv(
            ctx, jnp.stack([ext[j:j + q] for j in range(k)]))
        y, h = ssm_chunk_scan(x, dt, self._a(ctx), b, c, h0,
                              chunk=self.chunk_size,
                              dtype=ctx.value(self.in_proj).dtype)
        return self._out(ctx, y, x, z), h, after

    def forward(self, ctx, u):
        """A whole sequence from its start, no cache: ``u (B, S, E)``."""
        h0 = jnp.zeros((self.state_size, self.inner), _f32)
        before = jnp.zeros((self.conv_kernel - 1, self.conv_dim),
                           ctx.value(self.in_proj).dtype)
        return jax.vmap(lambda row: self.chunk(
            ctx, row, h0, before, row.shape[0])[0])(u)


class _Mixer(nn.Module):
    """One pre-norm residual: ``ln1`` before the mixer, the residual
    added by ``finish``."""

    def __init__(self, hidden, eps):
        super().__init__()
        self.ln1 = FusedRMSNorm(hidden, eps=eps)

    def forward(self, ctx, x, positions):
        return x + self.mix(ctx, self.ln1.forward(ctx, x), positions)


class HybridAttnBlock(GqaRows, _Mixer):
    """``*``: RMSNorm -> grouped-query attention, nothing rotated ->
    residual.  Its rows and readers are :class:`GqaRows`'."""

    def __init__(self, hidden, attn: GqaAttention, eps):
        super().__init__(hidden, eps)
        self.attn = attn

    def mix(self, ctx, h, positions):
        return self.attn.forward(ctx, h, positions)

    def finish(self, ctx, x, o, live):
        return x + _mm(o, ctx.value(self.attn.o)), None


class _StateBlock(_Mixer):
    """The state half of the layer protocol, for a block that keeps
    nothing of a token: :attr:`state` says what it keeps of a session
    (nothing, here), ``step`` and ``chunk`` take the group's buffers, the
    layer's place in them and the sessions' slots
    (``serve/kernels.py:StateRef``) and give the buffers back."""
    cache_rows = None
    state = ()

    def finish(self, ctx, x, o, live):
        y, counted = o
        return x + y.reshape(x.shape), counted


class HybridMambaBlock(_StateBlock):
    """``M``: RMSNorm -> Mamba-2 mixer -> residual."""

    def __init__(self, hidden, mixer: MambaMixer, eps):
        super().__init__(hidden, eps)
        self.mixer = mixer

    @property
    def state(self):
        return self.mixer.state

    def mix(self, ctx, h, positions):
        return self.mixer.forward(ctx, h)

    def step(self, ctx, x, state, live):
        (h, conv), layer, slots = state
        o, h, conv = self.mixer.step(ctx, self.ln1.forward(ctx, x), h, conv,
                                     layer, slots)
        return (o, None), (h, conv)

    def chunk(self, ctx, x, state, n_real, first):
        (h, conv), layer, slots = state
        slot = slots[0]
        # a slot starts from zero: the chunk at the session's start reads
        # zeros and not what the slot's last session left
        h0 = jnp.where(first, 0.0, h[layer, slot])
        before = jnp.where(first, jnp.zeros((), conv.dtype),
                           conv[layer, slot])
        o, hT, after = self.mixer.chunk(ctx, self.ln1.forward(ctx, x), h0,
                                        before, n_real)
        return (o, None), (h.at[layer, slot].set(hT),
                           conv.at[layer, slot].set(after))


class HybridExpertBlock(_StateBlock):
    """``E``: RMSNorm -> routed experts (not gated) + the shared expert
    -> residual.  Keeps nothing."""

    def __init__(self, hidden, experts: RoutedExperts, shared_intermediate,
                 eps, init):
        super().__init__(hidden, eps)
        self.experts = experts
        self.w_in = init((hidden, shared_intermediate), hidden)
        self.w_out = init((shared_intermediate, hidden), shared_intermediate)

    def _ffn(self, ctx, h, live=None):
        """``h (T, E)`` -> ``(y, pairs)``: the shared expert plus the held
        experts' part of the routed sum, and the pairs each of them got
        from the ``live`` rows."""
        act = ACTS[self.experts.act]
        y = _mm(act(_mm(h, ctx.value(self.w_in))), ctx.value(self.w_out))
        routed, pairs = self.experts.forward(ctx, h, live)
        return y + routed, pairs

    def mix(self, ctx, h, positions):
        return self._ffn(ctx, h.reshape(-1, h.shape[-1]))[0].reshape(h.shape)

    def step(self, ctx, x, state, live):
        return self._ffn(ctx, self.ln1.forward(ctx, x), live), ()

    def chunk(self, ctx, x, state, n_real, first):
        return self._ffn(ctx, self.ln1.forward(ctx, x),
                         jnp.arange(x.shape[0]) < n_real), ()


class HybridSsmMoeModel(nn.Module):
    """Token embedding -> blocks by ``pattern`` -> RMSNorm -> untied head.
    ``forward(ids (B, S)) -> logits (B, S, V)``.  ``pattern``: a
    character a layer (module docstring).  ``experts_held``: the routed
    experts this device holds in every ``E`` layer (ids; default all)."""

    def __init__(self, vocab_size, hidden, pattern, *, heads, kv_heads,
                 head_dim, mamba_heads, mamba_head_dim, ssm_groups,
                 ssm_state, conv_kernel=4, chunk_size=128,
                 expert_intermediate, shared_intermediate, n_experts, top_k,
                 n_group=1, topk_group=1, route_scale=1.0, norm_topk=True,
                 experts_held=None, max_positions=4096, eps=1e-5,
                 dtype=_f32, abstract=False):
        super().__init__()
        if set(pattern) - set("ME*"):
            raise ValueError(f"a layer is M, E or *; the pattern is "
                             f"{pattern!r}")

        def init(shape, fan_in):
            if abstract:
                return abstract_parameter(shape, dtype)
            if not fan_in:      # None: a bias, zeros; 0: a gain, ones
                return Parameter(jnp.full(shape, 0.0 if fan_in is None
                                          else 1.0, dtype))
            return Parameter((jax.random.normal(
                nn.modules._next_key(), shape, _f32)
                / math.sqrt(fan_in)).astype(dtype))

        def block(kind):
            if kind == "M":
                return HybridMambaBlock(hidden, MambaMixer(
                    hidden, mamba_heads, mamba_head_dim, ssm_groups,
                    ssm_state, conv_kernel, chunk_size, eps, init), eps)
            if kind == "*":
                return HybridAttnBlock(hidden, GqaAttention(
                    hidden, heads, kv_heads, head_dim, None, None, init),
                    eps)
            return HybridExpertBlock(hidden, RoutedExperts(
                hidden, expert_intermediate, n_experts, top_k,
                n_group=n_group, topk_group=topk_group, scale=route_scale,
                norm_topk=norm_topk, experts_held=experts_held, gated=False,
                act="relu2", init=init), shared_intermediate, eps, init)

        self.vocab_size, self.hidden = vocab_size, hidden
        self.pattern, self.max_positions = pattern, max_positions
        self.tok_emb = _Table(init((vocab_size, hidden), hidden))
        self.blocks = nn.ModuleList([block(kind) for kind in pattern])
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
