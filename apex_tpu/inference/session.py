"""Stateful multi-turn decode sessions over the LM cache protocol.

``models.gpt.generate`` is one-shot: prompt in, tokens out, caches
gone.  The chat/serving pattern — prefill a history once, generate,
append the next user turn, generate again — would re-prefill the whole
conversation every turn.  :class:`DecodeSession` keeps the KV caches
(and the write cursor) alive across calls instead: ``append`` ingests
tokens at the cursor, ``generate`` continues from it, and every turn
reuses the same compiled programs (the cursor is a traced argument, so
shapes and sampling config — not positions — key the compilation,
through the shared ``compiled_run_cache`` with its parameter-identity
and LRU invariants: a LoRA apply/merge mid-session recompiles against
the new parameter objects rather than silently decoding stale
weights).

The reference has no inference path (SURVEY.md §2 — training-side
library); this is the serving-session layer over the decode stack, and
it composes with everything the underlying paths do: int8 KV caches,
int8 weights, and the rolling sliding-window cache.  Sharded decode
(tp/sp/moe) stays with the one-shot ``generate(mesh=...)`` drivers —
a session would have to hold device-sharded caches across shard_map
regions; refused loudly for now.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


class DecodeSession:
    """Incremental decoding with persistent KV caches.

    ``DecodeSession(model, batch=1, capacity=None, cache_dtype=None)``
    allocates caches for ``capacity`` positions (default
    ``model.max_positions``).  Then, any interleaving of:

    - ``append(tokens)`` — teacher-force ``tokens (B, S)`` into the
      caches (a user turn, a system prompt); returns the logits for
      the ingested positions.
    - ``generate(n, temperature=0.0, top_k=None, top_p=None, key=None)``
      — continue from the cursor, returning the ``(B, n)`` new tokens
      (they are also ingested, like a model turn).
    - ``reset()`` — drop the decode state, keep the session.

    ``session.position`` is the write cursor.  Output equals one-shot
    ``generate`` on the concatenated history (cache-mediated numerics:
    ingest runs through ``decode_chunk``).
    """

    def __init__(self, model, batch=1, capacity=None, cache_dtype=None):
        from ..models.gpt import _sharded_decode_axes

        for a in ("init_caches", "decode_chunk", "decode_step"):
            if not hasattr(model, a):
                raise ValueError(
                    f"DecodeSession needs model.{a} (the GPT/Llama "
                    f"cache protocol)")
        guard = getattr(model, "_decode_guard", None)
        if guard is not None:
            guard("DecodeSession")
        if _sharded_decode_axes(model):
            raise NotImplementedError(
                "DecodeSession holds caches across calls and runs "
                "single-shard; sharded models (tp/sp/moe) decode "
                "through the one-shot generate(mesh=...) drivers")
        self.model = model
        self.batch = batch
        self.capacity = capacity if capacity is not None \
            else model.max_positions
        if not 1 <= self.capacity <= model.max_positions:
            raise ValueError(
                f"capacity must be in [1, max_positions="
                f"{model.max_positions}], got {self.capacity}")
        self._cache_dtype = cache_dtype if cache_dtype is not None \
            else model.tok_emb.weight.data.dtype
        self._vocab = getattr(model, 'vocab_size', None) \
            or model.tok_emb.weight.shape[0]
        self.reset()

    def reset(self):
        self.caches = self.model.init_caches(
            self.batch, self.capacity, dtype=self._cache_dtype)
        self.position = 0
        self._last_logits = None

    # -- internals ---------------------------------------------------------

    def _compiled(self, cfg, build_with_params):
        """A compiled program from the model's shared session cache:
        ``build_with_params(params)`` closes over the CURRENT
        Parameter/Buffer objects, and the cache keys on their ids
        (utils/jit_cache.py invariants — LoRA swaps miss, entries
        LRU-capped), so stale zips cannot read wrong weights."""
        from ..utils.jit_cache import compiled_run_cache

        params = list(self.model.parameters()) + \
            list(self.model.buffers())
        fn = compiled_run_cache(
            self.model, "_session_jit_cache", cfg, params,
            lambda: build_with_params(params))
        return fn, [p.data for p in params]

    def _check_room(self, n, what):
        if self.position + n > self.capacity:
            raise ValueError(
                f"{what}: cursor {self.position} + {n} tokens exceeds "
                f"the session capacity {self.capacity} — reset() or "
                f"allocate a larger session")

    @staticmethod
    def _ctx(params, vals):
        from ..nn.modules import Ctx
        return Ctx(env={id(p): v for p, v in zip(params, vals)},
                   stats_out={}, training=False)

    # -- public ------------------------------------------------------------

    def append(self, tokens):
        """Ingest ``tokens (B, S)`` at the cursor; returns their logits
        ``(B, S, V)`` (the last row is the next-token distribution)."""
        tokens = jnp.asarray(tokens)
        if tokens.ndim != 2 or tokens.shape[0] != self.batch:
            raise ValueError(
                f"append expects (batch={self.batch}, S) token ids, "
                f"got {tokens.shape}")
        s = int(tokens.shape[1])
        self._check_room(s, "append")

        def build(params):
            def run(vals, toks, caches, pos):
                ctx = self._ctx(params, vals)
                return self.model.decode_chunk(ctx, toks, caches, pos)
            return jax.jit(run)

        cache_name = self._cache_dtype if isinstance(
            self._cache_dtype, str) else jnp.dtype(self._cache_dtype).name
        fn, vals = self._compiled(
            ("session-append", self.batch, s, cache_name), build)
        logits, self.caches = fn(vals, tokens, self.caches,
                                 jnp.int32(self.position))
        self.position += s
        self._last_logits = logits[:, -1]
        return logits

    def generate(self, max_new_tokens, temperature=0.0, top_k=None,
                 top_p=None, key=None):
        """Continue the session by ``max_new_tokens`` (greedy, or
        sampled with generate()'s knobs); the emitted tokens are
        ingested like any turn.  Requires at least one prior ``append``
        (there is nothing to continue otherwise)."""
        from ..models.gpt import make_sampler

        if self.position == 0:
            raise ValueError(
                "generate on an empty session — append a prompt first")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        self._check_room(max_new_tokens, "generate")
        sample = make_sampler(temperature, top_k, top_p, self._vocab)
        if temperature > 0.0 and key is None:
            raise ValueError("sampling (temperature > 0) needs a PRNG "
                             "key")
        if key is None:
            key = jax.random.PRNGKey(0)

        def build(params):
            def run(vals, caches, pos, last_logits, key):
                ctx = self._ctx(params, vals)
                key, sub = jax.random.split(key)
                tok = sample(last_logits, sub)    # token AT the cursor

                def step(carry, t):
                    tok, caches, key, _ = carry
                    logits, caches = self.model.decode_step(
                        ctx, tok, caches, t)
                    key, sub = jax.random.split(key)
                    nxt = sample(logits, sub)
                    return (nxt, caches, key, logits), tok

                (_, caches, _, logits), toks = jax.lax.scan(
                    step, (tok, caches, key, last_logits),
                    pos + jnp.arange(max_new_tokens, dtype=jnp.int32))
                # toks = the n EMITTED tokens (each step emits the
                # token it consumed); the final carry logits are the
                # cursor's next-token distribution, kept so a
                # back-to-back generate() continues correctly
                return jnp.swapaxes(toks, 0, 1), logits, caches
            return jax.jit(run)

        cache_name = self._cache_dtype if isinstance(
            self._cache_dtype, str) else jnp.dtype(self._cache_dtype).name
        fn, vals = self._compiled(
            ("session-generate", self.batch, max_new_tokens,
             float(temperature), top_k,
             None if top_p is None else float(top_p), cache_name), build)
        toks, self._last_logits, self.caches = fn(
            vals, self.caches, jnp.int32(self.position),
            self._last_logits, key)
        self.position += max_new_tokens
        return toks


class PagedSession:
    """A decode session whose KV state is a BLOCK TABLE into a shared
    :class:`~apex_tpu.serve.ServeEngine` pool — no private cache
    buffer.

    Where :class:`DecodeSession` allocates ``(B, H, capacity, D)``
    caches per layer up front (capacity paid even for a two-turn
    chat), a PagedSession holds only the integer ids of the pool
    blocks its history actually fills, growing block-by-block as the
    conversation does; hundreds of sessions share the engine's one
    preallocated buffer.  The compiled programs are the ENGINE's
    prefill/decode programs — the same executables its continuous-
    batching loop dispatches — so an interactive session and the
    batch-serving path cannot drift numerically, and opening a session
    compiles nothing new after the engine has warmed its buckets.

    Same surface as DecodeSession (``append`` / ``generate`` /
    ``reset`` / ``position``), batch 1, greedy-only ``generate``
    (the serve programs sample in-program; sampled decode stays on
    DecodeSession, the single-session compatibility path).  ``append``
    returns only the LAST position's logits ``(1, V)`` — the paged
    prefill never materializes per-position logits for the whole
    chunk.  Use as a context manager (or call ``close()``) so the
    blocks return to the pool.
    """

    def __init__(self, engine):
        if len(engine.groups) > 1:
            raise NotImplementedError(
                "a PagedSession holds one block table: this engine's "
                "model keeps several cache groups (window and full "
                "attention layers); serve it through submit/step")
        self.engine = engine
        self._table = []
        self.position = 0
        self._last_logits = None
        # prefix-cache state: every ingested token in order (the chain
        # source), the rolling chain keys of committed full blocks, and
        # the tag the chain was built under — a weight republish
        # mid-session changes the engine's tag and stops this session
        # from publishing further (mixed-epoch) blocks
        self._tokens = []
        self._chain = []
        self._committed = 0
        self._cache_tag = None
        self._cacheable = True

    # -- block-table state -------------------------------------------------

    @property
    def block_table(self):
        """The session's logical→physical block ids (read-only view)."""
        return tuple(self._table)

    def _ensure(self, n_positions, what):
        from ..serve.pool import blocks_for
        eng = self.engine
        if n_positions > eng.model.max_positions:
            raise ValueError(
                f"{what}: {n_positions} positions exceed max_positions "
                f"{eng.model.max_positions}")
        need = blocks_for(n_positions, eng.block_size) - len(self._table)
        if need > 0:
            ids = eng.block_pool.alloc(need)
            if ids is None:
                raise RuntimeError(
                    f"{what}: block pool exhausted "
                    f"({eng.block_pool.in_use}/{eng.block_pool.capacity}"
                    f" in use) — close idle sessions or build the "
                    f"engine with more num_blocks")
            self._table.extend(ids)

    def _commit_full(self):
        """Publish every newly full block into the engine pool's hash
        index (rolling chain over the session's ingested tokens) —
        the PagedSession half of the serve scheduler's note_commit."""
        from ..serve.pool import chain_key
        eng = self.engine
        sched = eng.scheduler
        if not sched.prefix_cache or not self._cacheable:
            return
        if self._cache_tag is None:
            self._cache_tag = sched.cache_tag
        elif sched.cache_tag != self._cache_tag:
            # publish_weights re-tagged the engine mid-session: rows
            # already written used the old weights, so nothing this
            # session writes from here on may enter the index
            self._cacheable = False
            return
        bs = eng.block_size
        full = min(self.position // bs, len(self._table),
                   len(self._tokens) // bs)
        while self._committed < full:
            i = self._committed
            prev = self._chain[i - 1] if i else ""
            key = chain_key(prev, self._tokens[i * bs:(i + 1) * bs],
                            self._cache_tag)
            self._chain.append(key)
            eng.block_pool.commit(self._table[i], key)
            self._committed = i + 1

    def _adopt_prefix(self, toks) -> int:
        """First-append prefix walk: adopt every cached full block of
        ``toks`` shared and return the number of already-ingested
        positions.  A FULL-chain hit forks the last shared block
        copy-on-write (the final token must re-ingest for its logits,
        and that row lands inside the shared block)."""
        import numpy as np
        from ..serve.pool import chain_keys
        from ..runtime import executor as _executor
        from ..observe import registry as _obs
        eng = self.engine
        sched = eng.scheduler
        if not sched.prefix_cache:
            return 0
        tag = sched.cache_tag
        keys = chain_keys(toks, eng.block_size, tag)
        shared = eng.block_pool.acquire_prefix(keys)
        if not shared:
            return 0
        self._cache_tag = tag
        if len(shared) * eng.block_size >= toks.size:
            # full hit — fork the last shared block so the re-ingested
            # final token writes an exclusive copy
            fdst_l = eng.block_pool.alloc(1)
            if fdst_l is None:
                # no room for the fork: fall back to a partial hit by
                # releasing the last shared block (it retires cached)
                eng.block_pool.free([shared[-1]])
                shared = shared[:-1]
            else:
                fsrc, fdst = shared[-1], fdst_l[0]
                prog = eng._copy_program()
                eng.pool = _executor.executor.submit(
                    prog, (eng.pool, np.int32(fsrc), np.int32(fdst)),
                    step=next(eng._dispatch_no))
                eng.block_pool.free([fsrc])   # copy is in the stream
                eng._cow_forks += 1
                _obs.counter("serve.prefix.cow_forks").inc()
                self._table = shared[:-1] + [fdst]
                self._chain = keys[:len(shared) - 1]
                self._committed = len(shared) - 1
                self.position = toks.size - 1
                return self.position
        self._table = list(shared)
        self._chain = keys[:len(shared)]
        self._committed = len(shared)
        self.position = len(shared) * eng.block_size
        return self.position

    # -- public ------------------------------------------------------------

    def append(self, tokens):
        """Ingest ``tokens`` (a 1-D sequence, or ``(1, S)``) at the
        cursor through the engine's chunked prefill program; returns
        the final ingested position's logits ``(1, V)``."""
        from ..serve.scheduler import bucket
        import numpy as np
        eng = self.engine
        toks = np.asarray(tokens, np.int32).reshape(-1)
        if toks.size == 0:
            raise ValueError("append of zero tokens")
        prefill_prog, _ = eng._programs()
        chunk = eng.scheduler.prefill_chunk
        done = 0
        if self.position == 0:
            # empty session: a conversation replay (or a shared system
            # prompt another session committed) is a natural prefix hit
            done = self._adopt_prefix(toks)
        self._tokens.extend(int(t) for t in toks)
        while done < toks.size:
            n = int(min(chunk, toks.size - done))
            self._ensure(self.position + n, "append")
            nb = bucket(len(self._table))
            padded = np.zeros((1, chunk), np.int32)
            padded[0, :n] = toks[done:done + n]
            table = np.asarray(
                [self._table + [0] * (nb - len(self._table))], np.int32)
            from ..runtime import executor as _executor
            last, eng.pool, _ = _executor.executor.submit(
                prefill_prog,
                (eng._vals(), eng.pool, padded, table,
                 np.int32(self.position), np.int32(n)),
                step=next(eng._dispatch_no))
            self.position += n
            done += n
            self._commit_full()
        self._last_logits = last
        return last

    def generate(self, max_new_tokens):
        """Greedily continue by ``max_new_tokens`` (emitted tokens are
        ingested, like a model turn); returns ``(1, n)`` token ids."""
        from ..serve.scheduler import bucket
        import numpy as np
        eng = self.engine
        if self.position == 0:
            raise ValueError(
                "generate on an empty session — append a prompt first")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        _, decode_prog = eng._programs()
        from ..runtime import executor as _executor
        tok = int(jnp.argmax(self._last_logits[0]))
        out = [tok]
        for i in range(max_new_tokens):
            # ingest the token at the cursor; the final iteration only
            # refreshes _last_logits (its sampled successor is the
            # NEXT generate's first token)
            self._ensure(self.position + 1, "generate")
            nb = bucket(len(self._table))
            table = np.asarray(
                [self._table + [0] * (nb - len(self._table))], np.int32)
            nxt, logits, eng.pool, _ = _executor.executor.submit(
                decode_prog,
                (eng._vals(), eng.pool,
                 np.asarray([out[-1]], np.int32),
                 np.asarray([self.position], np.int32), table),
                step=next(eng._dispatch_no))
            self.position += 1
            self._tokens.append(out[-1])
            self._commit_full()
            self._last_logits = logits
            if i < max_new_tokens - 1:
                out.append(int(np.asarray(nxt)[0]))
        return jnp.asarray([out], jnp.int32)

    def reset(self):
        """Drop the decode state and return the blocks to the pool;
        the session object stays usable."""
        if self._table:
            self.engine.block_pool.free(self._table)
        self._table = []
        self.position = 0
        self._last_logits = None
        self._tokens = []
        self._chain = []
        self._committed = 0
        self._cache_tag = None
        self._cacheable = True

    close = reset

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.reset()
