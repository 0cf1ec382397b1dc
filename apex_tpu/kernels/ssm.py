"""ssm: the recurrence of a Mamba-2 state-space layer.

A head keeps a state ``H (P, N)`` (``P`` the head's channels, ``N`` the
state size); with ``a_t = exp(dt_t A)`` a scalar a head,

    H_t = a_t H_{t-1} + dt_t x_t (x) B_t        y_t = H_t C_t

``B_t`` and ``C_t`` are shared by the heads of a group (head ``h`` reads
group ``h // (heads / groups)``).  What is added to ``y`` beside it (the
skip ``D x``), the convolution before and the gate and norm after are the
layer's (``models/hybrid_ssm_moe.py``).

**The state's layout** is ``(N, heads*P)``: the state size on the
sublanes, every head's channels side by side on the lanes, the heads of a
group next to each other.  What varies along a row (the decay and the
input, a channel) is then a row vector, broadcast down the sublanes for
nothing; what varies down a column (``B`` and ``C``, a group) is a column
broadcast along the lanes, once a group; and ``y`` is a sum down the
sublanes, vector adds but for the last eight rows.  With the state size
on the lanes ``y`` would be a reduction along the lanes of every
register.

* :func:`ssm_state_update` — one step for ``B`` sessions (the decode
  tick): the registered ``ssm_state_update`` kernel.  The sessions'
  states lie in one buffer ``(layers, slots, N, heads*P)``, a row a
  *slot*; the slot ids arrive by scalar prefetch, each session's state is
  read once and written once where it lies (the buffer is aliased in and
  out).  The XLA tier gathers the slots' states, updates them and
  scatters them back.
* :func:`ssm_chunk_scan` — many steps of one session (a prefill chunk)
  in the matmul form: within a chunk of ``chunk`` positions the masked
  decay matrix times ``C B^T`` times the inputs, between chunks the
  carried state.  Plain XLA, under the scope ``ssm_chunk_scan``.

``tests/test_ssm_kernels.py`` holds both to the recurrence as written,
one position after another.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import choose, register_kernel

_f32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# The decode step
# ---------------------------------------------------------------------------


def _columns(bc):
    """``bc (2G, N)`` -> ``(N, 2G)``: each group's ``B`` and ``C`` as a
    column.  A product with the identity in the form the MXU takes
    natively (both operands contracted on their minor dimension); at
    ``HIGHEST`` every product is by one or by nought, so the columns are
    the rows' own numbers."""
    n = bc.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)).astype(_f32)
    return jax.lax.dot_general(eye, bc, (((1,), (1,)), ((), ())),
                               preferred_element_type=_f32, precision=_HI)


def _update_kernel(layer_ref, slots_ref, row_ref, bc_ref, h_ref, y_ref,
                   o_ref, *, groups):
    """One session (grid step ``b``): ``h_ref``/``o_ref (N, heads*P)`` its
    state where it lies, ``row_ref (2, heads*P)`` the decay and the input
    a channel, ``bc_ref (2G, N)`` the groups' ``B`` then ``C``."""
    del layer_ref, slots_ref
    cols = _columns(bc_ref[...])                            # (N, 2G)
    n, width = h_ref.shape
    gw = width // groups
    for g in range(groups):
        at = slice(g * gw, (g + 1) * gw)
        b = jnp.broadcast_to(cols[:, g:g + 1], (n, gw))
        c = jnp.broadcast_to(cols[:, groups + g:groups + g + 1], (n, gw))
        h = h_ref[:, at] * row_ref[0:1, at] + b * row_ref[1:2, at]
        o_ref[:, at] = h
        y_ref[:, at] = jnp.sum(h * c, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _update_call(layer, slots, rows, bc, state, *, interpret):
    """The kernel's call, jitted with the layer as an operand so that a
    program's per-layer calls share one trace and one lowering."""
    b = rows.shape[0]
    _, _, n, width = state.shape
    groups = bc.shape[1] // 2

    def at_state(i, layer, slots):
        return layer[0], slots[i], 0, 0

    def at_row(i, layer, slots):
        return i, 0, 0
    y, state = pl.pallas_call(
        functools.partial(_update_kernel, groups=groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[pl.BlockSpec((None, 2, width), at_row),
                      pl.BlockSpec((None, 2 * groups, n), at_row),
                      pl.BlockSpec((None, None, n, width), at_state)],
            out_specs=[pl.BlockSpec((None, 1, width), at_row),
                       pl.BlockSpec((None, None, n, width), at_state)]),
        out_shape=[jax.ShapeDtypeStruct((b, 1, width), _f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # (layer, slots, rows, bc, state): the state goes out where it
        # came in
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a session's state in and out, two of each in flight
            vmem_limit_bytes=max(32 << 20, 6 * n * width * 4)),
        interpret=interpret,
        name="ssm_state_update",
    )(layer, slots, rows, bc, state)
    return y[:, 0], state


def _update_xla(state, layer, slots, decay, dtx, b, c):
    """The XLA tier (the declared fallback): the slots' states gathered,
    updated and scattered back."""
    groups = b.shape[1]
    h = state[layer, slots]                                 # (B, N, heads*P)
    gw = h.shape[2] // groups

    def columns(m):                                         # (B, G, N)
        return jnp.repeat(jnp.swapaxes(m, 1, 2), gw, axis=2)
    h = h * decay[:, None, :] + columns(b) * dtx[:, None, :]
    y = jnp.sum(h * columns(c), axis=1)
    return y, state.at[layer, slots].set(h)


def ssm_state_update(state, layer, slots, decay, dtx, b, c):
    """One step of the recurrence for ``B`` sessions.

    ``state (layers, slots, N, heads*P)`` float32, the buffer of every
    session's state (module docstring); ``layer``: which of its layers
    (a Python int); ``slots (B,)`` int32: each session's row of it (rows
    may repeat only where what is computed for them is thrown away: a
    batch bucket's padding, all on the null slot).  ``decay (B,
    heads*P)`` = ``a_t`` and ``dtx (B, heads*P)`` = ``dt_t x_t``, a
    channel; ``b``, ``c (B, G, N)``.  All float32.  Returns ``(y (B,
    heads*P), state)``: ``y_t = H_t C_t``, and the buffer with the
    sessions' states stepped."""
    mode = kernel_mode(state, b)
    if mode is None:
        return _update_xla(state, layer, slots, decay, dtx, b, c)
    return _update_call(
        jnp.full((1,), layer, jnp.int32), slots.astype(jnp.int32),
        jnp.stack([decay, dtx], axis=1), jnp.concatenate([b, c], axis=1),
        state, interpret=mode == "interpret")


def kernel_mode(state, b):
    """The rule: the mode the kernel runs in, or ``None`` for the XLA
    tier.  The kernel reads and writes each live session's state once,
    where the XLA tier writes a gathered copy, reads it, and writes the
    update twice (PERF.md section 6, PR 35, has the chip's reading of
    both), so it is taken wherever its tiles fit: a float32 state of
    whole sublane tiles whose groups are whole lane rows wide."""
    _, _, n, width = state.shape
    groups = b.shape[1]
    return choose("ssm_state_update", fits=(
        state.dtype == _f32 and n % 8 == 0 and width % groups == 0
        and (width // groups) % 128 == 0))


# ---------------------------------------------------------------------------
# A chunk of positions
# ---------------------------------------------------------------------------


def ssm_chunk_scan(x, dt, a, b, c, h0, *, chunk, dtype=_f32):
    """``Q`` steps of one session in the matmul form.

    ``x (Q, heads, P)``, ``dt (Q, heads)`` (0 on a row that is no real
    position: it then leaves the state alone), ``a (heads,)`` negative,
    ``b``, ``c (Q, G, N)``, ``h0 (N, heads*P)`` the state before the
    first row; all float32.  Matrix products take their operands in
    ``dtype`` (the type the layer's matrices are stored in) and
    accumulate in float32; the decay's sums and exponentials, and the
    state, are float32.  Returns ``(y (Q, heads, P), hT (N, heads*P))``.

    Within a chunk of ``chunk`` rows ``y_l = sum_{s<=l} exp(A_l - A_s)
    (C_l . B_s) dt_s x_s`` with ``A`` the running sum of ``dt a``, plus
    what the state entering the chunk gives, ``exp(A_l) C_l H``; the
    state leaving it is ``exp(A_last) H + sum_s exp(A_last - A_s) dt_s
    x_s (x) B_s``."""
    with jax.named_scope("ssm_chunk_scan"):
        q, heads, p = x.shape
        groups, n = b.shape[1:]
        r = heads // groups
        pad = -q % chunk
        if pad:
            x, dt, b, c = (jnp.pad(m, ((0, pad),) + ((0, 0),) * (m.ndim - 1))
                           for m in (x, dt, b, c))
        nc = (q + pad) // chunk
        cs = jnp.cumsum((dt * a).reshape(nc, chunk, heads), axis=1)
        xs = (x * dt[..., None]).reshape(nc, chunk, groups, r, p)
        bq = b.reshape(nc, chunk, groups, n).astype(dtype)
        cq = c.reshape(nc, chunk, groups, n).astype(dtype)
        csg = cs.reshape(nc, chunk, groups, r)
        # within the chunk: the masked decay matrix times C B^T
        rows = jnp.arange(chunk)
        seen = (rows[:, None] >= rows[None, :])[None, :, :, None, None]
        decay = jnp.exp(jnp.where(
            seen, csg[:, :, None] - csg[:, None, :], -jnp.inf))
        cb = jnp.einsum("clgn,csgn->clsg", cq, bq,
                        preferred_element_type=_f32)
        y = jnp.einsum("clsgr,csgrp->clgrp",
                       (decay * cb[..., None]).astype(dtype),
                       xs.astype(dtype), preferred_element_type=_f32)
        # what each chunk adds to the state, and the state entering each
        to_end = jnp.exp(csg[:, -1:] - csg)                  # (nc, L, G, R)
        added = jnp.einsum("csgn,csgrp->cngrp", bq,
                           (xs * to_end[..., None]).astype(dtype),
                           preferred_element_type=_f32)
        whole = jnp.exp(csg[:, -1])                          # (nc, G, R)
        h = h0.reshape(n, groups, r, p)
        entering = []
        for i in range(nc):
            entering.append(h)
            h = h * whole[i][None, :, :, None] + added[i]
        from_start = jnp.exp(csg)                            # (nc, L, G, R)
        y = y + from_start[..., None] * jnp.einsum(
            "clgn,cngrp->clgrp", cq, jnp.stack(entering).astype(dtype),
            preferred_element_type=_f32)
        return y.reshape(nc * chunk, heads, p)[:q], h.reshape(n, heads * p)


def _audit_programs():
    sds = jax.ShapeDtypeStruct
    state = sds((1, 3, 16, 256), _f32)
    row, col = sds((2, 256), _f32), sds((2, 4, 16), _f32)
    ex = (state, sds((2,), jnp.int32), row, row, col, col)

    def _pallas(state, slots, decay, dtx, b, c):
        return _update_call(
            jnp.zeros((1,), jnp.int32), slots, jnp.stack([decay, dtx], 1),
            jnp.concatenate([b, c], 1), state, interpret=False)

    def _xla(state, slots, decay, dtx, b, c):
        return _update_xla(state, 0, slots, decay, dtx, b, c)

    return [("pallas", _pallas, ex), ("xla", _xla, ex)]


register_kernel(
    "ssm_state_update",
    xla_fallback="apex_tpu.kernels.ssm._update_xla",
    doc="One step of a Mamba-2 recurrence: each session's state in place",
    audit_programs=_audit_programs)
