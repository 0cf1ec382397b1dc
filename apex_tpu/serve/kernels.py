"""Paged-attention program bodies: the traced code the serve engine
submits through the one-runtime executor.

Everything here is shape-static by construction — operand shapes are
functions of the POOL geometry (layers/heads/block_size/head_dim) and
the BUCKET dims (batch, blocks, chunk) baked into the builder, never of
live request state.  Request state (which sessions, at which positions,
holding which blocks) enters as *traced integer arrays* (tokens,
positions, block tables), so session churn re-dispatches the same
compiled program instead of retracing — the serving analogue of the
step-cache keying discipline, enforced by the SERVE-SHAPE lint rule.

Every layer of a servable model follows ONE protocol
(:func:`_through_blocks`), which has two halves.  A layer that keeps
something of a *token* is handed the chunk and its positions and gives
back its queries and the row(s) to store; it says what it keeps, for how
long (``window``), and how a query reads it.  Layers that keep the same
rows for the same length share a *cache group* (:func:`cache_groups`): a
pool buffer and a block table a session of their own, so a model whose
layers mix window and full attention keeps the band's blocks for the one
kind and every block for the other.  A layer that keeps something of a
*session* (``cache_rows = None``: a state-space layer's state, which is
read, updated and written back every step whatever the depth; or nothing
at all, as a feed-forward layer of its own) says what (``state``) and
takes one step of a batch or one chunk of a session with the buffers in
hand; layers of equal ``state`` share a *state group*
(:func:`state_groups`): buffers ``(layers of the group, slots, *shape)``,
a row a *slot* that a session holds from admission to ``finish``.
``GptBlock`` keeps a K
and a V row of all heads (its learned positions were added at the
embedding) and reuses the model's own decode pieces —
``_chunk_qkv`` (LN1 + interleaved QKV projection), ``_attn_mlp_tail``
(out-proj + residual + FFN), the fp32 score product + ``-1e30`` mask +
softmax of ``decode_chunk``, the int8-aware ``gather_rows`` embedding
lookup — so the paged path cannot drift numerically from the
contiguous-cache path it is parity-tested against (tests/test_serve.py,
tests/test_serve_paged.py); a latent block (``models/latent_moe.py``)
rotates by the positions and keeps one latent row a token, read by all
heads alike; a grouped-query block (``models/gqa_moe.py``) rotates by
its own kind's tables and keeps a K and a V row of its stored heads; a
state-space block (``models/hybrid_ssm_moe.py``) keeps a float32 state
and its convolution's last inputs a session.
What is here is the index plumbing, and it keeps each pool where it lies
(``serve/pool.py``: ``(layers of the group, streams, num_blocks,
block_size, width)``, row-major on the device):

* **write** — each layer sets its fresh rows in the donated pool in
  place, position -> (physical block, offset), one contiguous row each
  (:func:`write_rows`), BEFORE that layer attends: the write-then-read
  of ``decode_chunk``, so a query finds its own key where every other
  key is;
* **read, decode** — one query row a session: through the block table
  (``paged_decode_attention`` / ``latent_decode_attention``: Pallas
  kernels that DMA the live blocks, each with a per-layer gather as its
  XLA tier);
* **read, prefill and speculative verify** — many query rows, few
  sessions: a gathered view of those sessions' blocks, one layer at a
  time, in the pool's dtype.

No program holds a view of all layers, an fp32 copy of the pool's rows,
or the pool in another layout, and none copies or widens a state buffer
(tests/test_aot_tpu_compile.py pins it on the compiled v5e programs).

Dead batch rows (bucket padding) are encoded as ``position == -1``:
their tables are all-null (reads see zeros the mask excludes), their
embedding lookups clip to row 0 (outputs discarded), and their KV write
targets are redirected past the pool so ``mode="drop"`` discards the
write — padding never touches the null block's zeros.  Their slot is the
*null slot*, the last row of every state buffer, which they read and
write and no session holds.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..inference import QuantKV, absmax_int8, gather_rows
from ..kernels.paged_attention import ring_entry
from ..nn.modules import Ctx

_f32 = jnp.float32


class CacheGroup(NamedTuple):
    """Layers that keep the same rows of a token for the same length."""
    rows: Tuple[int, int, int]     # (streams, heads, head_dim) a token
    window: Optional[int]          # keys a query reads; None: all of them
    layers: Tuple[int, ...]        # the model's layers, in order

    @property
    def name(self) -> str:
        return "full" if self.window is None else f"window{self.window}"


class StateGroup(NamedTuple):
    """Layers that keep the same state of a session."""
    state: Tuple[tuple, ...]       # ((shape, dtype), ...) a session
    layers: Tuple[int, ...]        # the model's layers, in order


class StateRef(NamedTuple):
    """What a layer of a state group is handed: the group's buffers
    ``(layers of the group, slots, *shape)``, one for each entry of the
    layer's ``state``; its place in them; and the slot of each session of
    the dispatch."""
    bufs: tuple
    layer: int
    slots: jax.Array               # (B,) int32


def _where(groups, n_layers):
    where = [None] * n_layers
    for g, grp in enumerate(groups):
        for at, layer in enumerate(grp.layers):
            where[layer] = (g, at)
    return where


def cache_groups(model, window=None):
    """``(groups, where)``: the model's layers that keep rows of a token,
    grouped by what they keep (``blk.cache_rows``) and for how long
    (``blk.window``; a layer that declares none takes ``window``, the
    engine-wide default), groups without a window first, and for each
    such layer ``(its group, its place in that group's pool)`` (None for
    a layer that keeps no rows: :func:`state_groups`)."""
    keys = [None if blk.cache_rows is None else
            (tuple(blk.cache_rows), getattr(blk, "window", None) or window)
            for blk in model.blocks]
    order = sorted(dict.fromkeys(k for k in keys if k is not None),
                   key=lambda k: k[1] is not None)
    groups = [CacheGroup(rows, w, tuple(
        i for i, k in enumerate(keys) if k == (rows, w)))
        for rows, w in order]
    return groups, _where(groups, len(keys))


def state_groups(model):
    """``(groups, where)``: the model's layers that keep a state of a
    session (``blk.cache_rows is None`` and ``blk.state``, a tuple of
    ``(shape, dtype)``, not empty), grouped by that state in the order
    the model meets them, and for each such layer ``(its group, its place
    in that group's buffers)``.  A layer that keeps neither rows nor a
    state (an expert layer that is a layer of its own) is in no group:
    it rides the state half of the protocol and is handed no buffer."""
    keys = [tuple((tuple(shape), jnp.dtype(dt).name)
                  for shape, dt in blk.state)
            if blk.cache_rows is None else () for blk in model.blocks]
    groups = [StateGroup(state, tuple(
        i for i, k in enumerate(keys) if k == state))
        for state in dict.fromkeys(k for k in keys if k)]
    return groups, _where(groups, len(keys))


def _ctx(params, vals):
    return Ctx(env={id(p): v for p, v in zip(params, vals)},
               stats_out={}, training=False)


# ---------------------------------------------------------------------------
# Pool writes: position -> (physical block, offset), rows set in place
# ---------------------------------------------------------------------------


def row_targets(tables, positions, live, block_size, num_blocks, streams,
                ring=False):
    """Where the rows of logical ``positions (B, Q)`` live in one layer
    of the pool: index arrays ``(stream, block, offset)``, each
    ``(streams*B*Q,)`` — the first stream's rows, then the next's.
    ``ring``: the tables are a window group's (logical block ``i`` at
    entry ``i mod width``: ``kernels/paged_attention.py``).  Rows that
    are not ``live (B, Q)`` (bucket padding, a chunk's zero-padded tail)
    point past the pool, so :func:`write_rows` drops them — padding
    never touches the null block's zeros."""
    p = jnp.clip(positions, 0)
    width = tables.shape[1]
    entry = ring_entry(p // block_size, width) if ring \
        else jnp.minimum(p // block_size, width - 1)
    tgt = jnp.take_along_axis(tables, entry, axis=1)
    tgt = jnp.where(live, tgt, num_blocks).reshape(-1)
    stream = jnp.repeat(jnp.arange(streams, dtype=tgt.dtype), tgt.shape[0])
    return stream, jnp.tile(tgt, streams), \
        jnp.tile((p % block_size).reshape(-1), streams)


def write_rows(pool, layer, targets, rows):
    """Set the fresh rows of one layer in the (donated) pool, in place:
    ``rows``, one ``(B, Q, width)`` array a stream (a GPT block's K and
    V; a latent block's one row), go to ``pool[layer, stream, block,
    offset, :]`` (``targets`` from :func:`row_targets`), each a
    contiguous row.  Rows whose block id points past the pool are
    dropped.  QuantKV pools quantize per position and head (absmax over
    the head's columns — identical stored bytes to the contiguous int8
    cache's write path)."""
    width = rows[0].shape[-1]
    rows = jnp.concatenate([r.reshape(-1, width) for r in rows])
    at = (layer,) + tuple(targets)
    if isinstance(pool, QuantKV):
        h = pool.scale.shape[-1]
        q, scale = absmax_int8(rows.reshape(-1, h, width // h).astype(_f32),
                               -1, pool.scale.dtype)
        return QuantKV(
            pool.q.at[at].set(q.reshape(-1, width), mode="drop"),
            pool.scale.at[at].set(scale[..., 0], mode="drop"))
    return pool.at[at].set(rows.astype(pool.dtype), mode="drop")


def build_block_copy_fn():
    """The copy-on-write fork program body: duplicate ONE physical
    block's bytes — every layer, both k and v, payload AND scales for a
    :class:`QuantKV` pool — from ``src`` to ``dst``.

    ``fn(pool, src, dst) -> pool`` with ``src``/``dst`` traced i32
    scalars, so one compiled program serves every fork (block ids are
    data, not shapes — the SERVE-SHAPE discipline).  The scheduler
    decides WHEN to fork (a session extending into a shared block); the
    destination is a fresh exclusive block, the source keeps serving
    its other holders untouched — the copy is what makes shared blocks
    immutable in practice."""
    def fn(pool, src, dst):
        if isinstance(pool, QuantKV):
            return QuantKV(
                pool.q.at[:, :, dst].set(pool.q[:, :, src]),
                pool.scale.at[:, :, dst].set(pool.scale[:, :, src]))
        return pool.at[:, :, dst].set(pool[:, :, src])
    return fn


# ---------------------------------------------------------------------------
# Program bodies
# ---------------------------------------------------------------------------


def _embed(ctx, model, toks, positions):
    """Token embedding (int8-aware row gather), plus the learned
    position's where the model has a table of them (a rotary model's
    positions reach its layers instead); ``positions`` clip to the table
    (pad rows only — real positions are range-checked at admission,
    where the bound is a host decision, not here where a clamp would
    silently corrupt)."""
    x = gather_rows(ctx, model.tok_emb.weight, toks)
    pos_emb = getattr(model, "pos_emb", None)
    if pos_emb is None:
        return x
    pos = jnp.clip(positions, 0, pos_emb.weight.shape[0] - 1)
    return x + gather_rows(ctx, pos_emb.weight, pos)


def _head(ctx, model, x):
    """Logits through the model's own head, else the tied embedding."""
    table = getattr(model, "lm_head", model.tok_emb).weight
    return model._mask_pad_logits(jnp.matmul(
        x, jnp.swapaxes(ctx.value(table), 0, 1).astype(x.dtype)))


def _num_blocks(pool) -> int:
    return (pool.q if isinstance(pool, QuantKV) else pool).shape[2]


def _through_blocks(ctx, model, pools, x, q_pos, live, tables, block_size,
                    window, *, decode, states=(), slots=None):
    """``x (B, Q, E)`` at positions ``q_pos (B, Q)``, of which ``live
    (B, Q)`` are real, through every block (``decode``: the tick's one
    row a session, ``Q == 1``), by the one layer protocol every servable
    block follows.  A block that keeps rows of a token:

    * ``blk.cache_rows`` — ``(streams, heads, head_dim)``: what the block
      keeps of a token (its pool's geometry is read off it);
    * ``blk.window`` — how many keys a query of this block reads (the
      query's own among them), or None for all of them; a block without
      the attribute takes ``window``, the engine-wide default;
    * ``blk.chunk_rows(ctx, x, positions) -> (q, rows)`` — its queries
      and the rows to store, positions in hand (rotary or unused);
    * ``blk.read_decode`` / ``blk.read_chunk(q, pool, layer, tables,
      positions, window)`` — how a query reads the stored rows: one
      query row a session through the block table (the decode tick), or
      a chunk of rows against a gathered view of these sessions' blocks,
      one layer at a time, in the pool's dtype;
    * ``blk.finish(ctx, x, o, live) -> (x, counted)`` — the rest of the
      block, and what it counted on the way over the ``live`` rows (a
      routed layer's token-expert pairs a held expert; None).

    A block that keeps none (``blk.cache_rows is None``):

    * ``blk.state`` — ``((shape, dtype), ...)``: what the block keeps of
      a *session* (a state-space layer's state and its convolution's last
      inputs; empty for a block that keeps nothing), in dtypes of its
      own (the cache's dtype is the rows');
    * ``blk.step(ctx, x (B, E), state, live (B,)) -> (o, bufs)`` — one
      position of every session of the batch (the decode tick);
      ``state`` a :class:`StateRef`: the buffers of the block's group,
      its place in them and the sessions' slots (padding rows: the null
      slot); ``bufs`` the buffers with those slots' rows stepped;
    * ``blk.chunk(ctx, x (Q, E), state, n_real, first) -> (o, bufs)`` —
      a chunk of ONE session of which the first ``n_real`` rows are real
      (the others leave the state alone); ``first``: the chunk starts
      the session, so the state it starts from is zeros whatever the
      slot's last session left;
    * ``blk.finish(ctx, x, o, live)`` as above.

    ``pools`` and ``tables`` are one buffer and one ``(B, nb)`` table
    where all layers are of one cache group, else a tuple of each, a
    group (:func:`cache_groups`): a layer is handed its group's buffer
    and table, its place in that buffer and its own window.

    ``states``: a tuple of buffers a state group (:func:`state_groups`),
    ``slots (B,)`` each session's row of them; a chunk of several
    sessions (the speculative verify) is no state layer's.

    Each layer writes the live rows into its pool and then attends —
    the write-then-read of ``GptBlock.decode_chunk``, so a query finds
    its own key where every other key is (through an int8 pool: exactly
    the bytes stored).  Returns ``(x, pools, counted)``, and the states
    after them where the model has a state group: ``counted`` the
    layers' counts stacked ``(layers that count, ...)``, None if none
    does."""
    groups, where = cache_groups(model, window)
    _, s_where = state_groups(model)
    states = list(states)
    one = not isinstance(tables, (tuple, list))
    pools, tables = ([pools], [tables]) if one else (list(pools), tables)
    if len(pools) != len(groups):
        raise ValueError(f"the model's layers form {len(groups)} cache "
                         f"groups, the program was handed {len(pools)} "
                         f"pools")
    targets = [row_targets(tables[g], q_pos, live, block_size,
                           _num_blocks(pools[g]), grp.rows[0],
                           ring=grp.window is not None)
               for g, grp in enumerate(groups)]
    pos = q_pos[:, 0] if decode else q_pos
    counted = []
    for layer, blk in enumerate(model.blocks):
        if where[layer] is None:
            # (a block that keeps nothing is handed no buffer)
            g, at = s_where[layer] or (None, 0)
            ref = StateRef(() if g is None else states[g], at, slots)
            if decode:
                o, kept = blk.step(ctx, x[:, 0], ref, live[:, 0])
            else:
                o, kept = blk.chunk(
                    ctx, x[0], ref, jnp.sum(live[0], dtype=jnp.int32),
                    q_pos[0, 0] == 0)
            if g is not None:
                states[g] = kept
        else:
            g, at = where[layer]
            q, rows = blk.chunk_rows(ctx, x, q_pos)
            pools[g] = write_rows(pools[g], at, targets[g], rows)
            read = blk.read_decode if decode else blk.read_chunk
            o = read(q, pools[g], at, tables[g], pos, groups[g].window)
        x, n = blk.finish(ctx, x, o, live)
        if n is not None:
            counted.append(n)
    out = x, pools[0] if one else tuple(pools), \
        jnp.stack(counted) if counted else None
    return out + (tuple(states),) if states else out


def build_decode_fn(model, params, block_size, num_blocks, window=None):
    """The decode-tick program body: one token per live session.

    ``fn(vals, pool, tokens, positions, tables) ->
    (next_tokens, logits, pool, counted)`` with ``tokens (B,)`` the last emitted
    token per session, ``positions (B,)`` its ingest position (``-1`` =
    dead pad row), ``tables (B, nb)``; for a model of several cache
    groups ``pool`` and ``tables`` are tuples, one of each a group
    (:func:`cache_groups`).  ``num_blocks`` is what the pools were built
    with (each pool's own shape is what is read); ``window`` the window
    of layers that declare none.  Greedy sampling happens
    in-program (argmax over the masked logits — the same reduction the
    session path's ``make_sampler(0, ...)`` runs), so the engine's host
    round-trip per tick is one small int array; the logits ride along
    as an un-fetched device array for clients (PagedSession) that
    continue from them.  ``counted`` is what the layers counted on the
    way (:func:`_through_blocks`: a routed layer's token-expert pairs a
    held expert, ``(routed layers, held experts)`` i32), None for a
    model whose layers count nothing; the engine fetches it with the
    tokens.

    Where the model has a state group (:func:`state_groups`) the body is
    ``fn(vals, pool, states, tokens, positions, tables, slots) -> (...,
    pool, counted, states)``: ``states`` a tuple of buffers a group,
    ``slots (B,)`` each session's row of them (a dead row: the null
    slot)."""
    def through(ctx, pool, tokens, positions, tables, **state):
        x = _embed(ctx, model, tokens[:, None], positions[:, None])
        x, pool, counted, *states = _through_blocks(
            ctx, model, pool, x, positions[:, None], positions[:, None] >= 0,
            tables, block_size, window, decode=True, **state)
        x = model.ln_f.forward(ctx, x)
        logits = _head(ctx, model, x)[:, 0]               # (B, V)
        nxt = jnp.argmax(logits, axis=-1).astype(tokens.dtype)
        return (nxt, logits, pool, counted, *states)

    # (one name for both: a device trace knows the program as jit_fn)
    if state_groups(model)[0]:
        def fn(vals, pool, states, tokens, positions, tables, slots):
            return through(_ctx(params, vals), pool, tokens, positions,
                           tables, states=states, slots=slots)
    else:
        def fn(vals, pool, tokens, positions, tables):
            return through(_ctx(params, vals), pool, tokens, positions,
                           tables)
    return fn


def build_prefill_fn(model, params, block_size, num_blocks,
                     window=None):
    """The prefill-chunk program body: ingest one fixed-width chunk of
    ONE session's prompt per dispatch (long prompts run as several
    chunks, interleaved with decode ticks so they never stall the
    batch).

    ``fn(vals, pool, toks, table, t0, n_real) -> (last_logits, pool,
    counted)``
    with ``toks (1, chunk)`` zero-padded past ``n_real``, ``table
    (1, nb)``, ``t0`` the chunk's first position, ``n_real`` the live
    prefix length (both traced i32 — the bucketed chunk width, not the
    prompt length, keys compilation).  ``last_logits (1, V)`` is row
    ``n_real - 1`` — the next-token distribution once the final chunk
    lands; ``counted`` as the decode body's.

    Where the model has a state group the body is ``fn(vals, pool,
    states, toks, table, t0, n_real, slot) -> (last_logits, pool,
    counted, states)``, ``slot (1,)`` the session's; the chunk at ``t0 ==
    0`` starts from zeros and not from what the slot holds."""
    def through(ctx, pool, toks, table, t0, n_real, **state):
        rows = jnp.arange(toks.shape[1], dtype=jnp.int32)
        pos = (t0 + rows)[None, :]                        # (1, chunk)
        x = _embed(ctx, model, toks, pos)
        # chunk row d lands at position t0 + d; live rows only
        x, pool, counted, *states = _through_blocks(
            ctx, model, pool, x, pos, (rows < n_real)[None, :], table,
            block_size, window, decode=False, **state)
        x = model.ln_f.forward(ctx, x)
        logits = _head(ctx, model, x)                  # (1, chunk, V)
        last = jax.lax.dynamic_index_in_dim(
            logits, jnp.clip(n_real - 1, 0), axis=1, keepdims=False)
        return (last, pool, counted, *states)

    if state_groups(model)[0]:
        def fn(vals, pool, states, toks, table, t0, n_real, slot):
            return through(_ctx(params, vals), pool, toks, table, t0,
                           n_real, states=states, slots=slot)
    else:
        def fn(vals, pool, toks, table, t0, n_real):
            return through(_ctx(params, vals), pool, toks, table, t0,
                           n_real)
    return fn


def build_spec_verify_fn(target, t_params, draft, d_params, block_size,
                         num_blocks, k):
    """The speculative decode-tick program body: draft-propose + target-
    verify, fused into ONE dispatch per tick.

    ``fn(t_vals, d_vals, t_pool, d_pool, tokens, positions, t_tables,
    d_tables) -> (emitted, n_acc, t_pool, d_pool)`` with ``tokens (B,)``
    each session's pending token, ``positions (B,)`` its ingest position
    (``-1`` = dead pad row), and separate block tables into the target
    and draft pools (same :class:`~apex_tpu.serve.pool.BlockPool`
    free-list, two geometry-matched buffers).

    Inside the program:

    1. the DRAFT runs ``k + 1`` sequential paged decode steps from the
       pending token — the extra step writes the draft KV row for the
       all-accepted case (speculative.py's ``m + k`` cache-coverage
       rule) — proposing greedy tokens ``d_1..d_k``;
    2. the TARGET verifies the chunk ``[x_0, d_1..d_k]`` at positions
       ``p..p+k`` in one batched multi-position paged pass (the prefill
       body's insert mask, per batch row), yielding greedy tokens
       ``g_1..g_{k+1}``;
    3. ragged greedy acceptance PER ROW: ``n_acc[b] - 1`` is the length
       of the longest prefix where ``d_i == g_i``, so row ``b`` commits
       ``emitted[b, :n_acc[b]]`` — between 1 and ``k + 1`` tokens, each
       one exactly what the plain decode program would have emitted.

    Both pools are written through position ``p + k`` every tick; rows
    past the committed point hold KV of rejected continuations and are
    overwritten by the next tick's chunk (positions ``p'..p'+k`` with
    ``p' <= p + k + 1``) before any query's validity mask can reach
    them — the cache-staleness invariant speculative.py documents,
    expressed in block tables.  Shape-static like every serve body: the
    batch bucket, the two table buckets and ``k`` key compilation;
    acceptance lengths are DATA (`n_acc`), never shapes."""
    kp1 = k + 1

    def fn(t_vals, d_vals, t_pool, d_pool, tokens, positions,
           t_tables, d_tables):
        t_ctx = _ctx(t_params, t_vals)
        d_ctx = _ctx(d_params, d_vals)
        live = positions >= 0
        # ---- draft proposes: kp1 sequential paged single-token steps,
        # each the decode program's own layer loop (its fresh row goes
        # into the draft pool before it attends)
        chunk_toks = [tokens]
        tok = tokens
        for j in range(kp1):
            pos_j = jnp.where(live, positions + j, -1)
            x = _embed(d_ctx, draft, tok[:, None], pos_j[:, None])
            x, d_pool, _ = _through_blocks(
                d_ctx, draft, d_pool, x, pos_j[:, None], pos_j[:, None] >= 0,
                d_tables, block_size, None, decode=True)
            if j < k:                  # step k only writes its KV row
                x = draft.ln_f.forward(d_ctx, x)
                logits = _head(d_ctx, draft, x)[:, 0]
                tok = jnp.argmax(logits, axis=-1).astype(tokens.dtype)
                chunk_toks.append(tok)
        chunk = jnp.stack(chunk_toks, axis=1)       # (B, kp1)
        # ---- target verifies the whole chunk in one paged pass
        offs_q = jnp.arange(kp1, dtype=jnp.int32)[None, :]
        q_live = jnp.broadcast_to(live[:, None], chunk.shape)
        q_pos = jnp.where(q_live, positions[:, None] + offs_q, -1)
        x = _embed(t_ctx, target, chunk, q_pos)
        x, t_pool, _ = _through_blocks(
            t_ctx, target, t_pool, x, q_pos, q_live, t_tables, block_size,
            None, decode=False)
        x = target.ln_f.forward(t_ctx, x)
        logits = _head(t_ctx, target, x)                # (B, kp1, V)
        emitted = jnp.argmax(logits, axis=-1).astype(tokens.dtype)
        agree = chunk[:, 1:] == emitted[:, :k]
        stop = jnp.concatenate(
            [agree, jnp.zeros((agree.shape[0], 1), bool)], axis=1)
        n_acc = jnp.argmin(stop.astype(jnp.int32), axis=1) + 1
        return emitted, n_acc, t_pool, d_pool
    return fn
