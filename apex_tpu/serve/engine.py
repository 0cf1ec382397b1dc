"""ServeEngine: the continuous-batching serving loop.

One engine owns one model, its paged KV cache (one pool a *cache group*:
the layers that keep the same rows of a token for the same length; most
models have one group), one scheduler, and a
small fixed family of compiled programs — two *kinds* (``prefill_step``,
``decode_step``) dispatched through the one-runtime executor
(runtime/executor.py), so serving inherits the whole training-side
runtime for free: step-cache keying (``stats()['by_kind']`` pins
compiles per kind; the bench's ``decode_compiles <= buckets`` bound is
exactly the training side's 1-compile-per-window discipline), dispatch
spans, watchdog heartbeats, and the donation policy (the pool is the
donated carry — on tpu/gpu each tick rewrites KV in place).

The tick loop (:meth:`ServeEngine.step`):

1. **admit** — the scheduler moves queue-head requests into the live
   set while batch slots / blocks / prefill backlog allow;
2. **one prefill chunk** — the oldest prefilling session ingests up to
   ``prefill_chunk`` prompt tokens (ONE chunk per tick, so a long
   prompt interleaves with everyone else's decode instead of stalling
   it); completing prefill emits the first token from the chunk's last
   logits — no decode dispatch spent on it;
3. **one decode tick** — every decoding session advances one token in
   a single bucketed dispatch; sessions that hit ``max_new_tokens`` or
   their ``eos`` free their blocks this same tick.

Per-request lifecycle telemetry (``serve.request`` events with phases
queued→prefill→first_token→done, TTFT/e2e/tick-latency histograms,
queue-depth and pool-occupancy gauges) flows through the observe
registry; ``run()`` can wrap the loop in a stall watchdog — the
executor's per-dispatch heartbeats make a wedged backend fire a typed
``watchdog.stall`` diagnostic instead of hanging silently.

Greedy decoding only, by design: serving parity is pinned bitwise
against ``inference.DecodeSession``, and a sampled path would need
per-session PRNG threading through the bucketed programs — a later
PR's satellite, not this one's.
"""
from __future__ import annotations

import itertools
import time
import weakref
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models.gpt import _sharded_decode_axes
from ..observe import registry as _obs
from ..observe import spans as _spans
from ..observe import watchdog as _watchdog
from ..runtime import executor as _executor
from . import kernels as _kernels
from .pool import (BlockPool, blocks_for, init_pool_buffer,
                   init_state_buffers)
from .scheduler import DECODE, Request, Scheduler, Session

#: per-engine token in the serve program static keys — two engines over
#: identically-shaped models must never share a cache entry (their
#: program closures hold different parameter objects)
_SERVE_TOKENS = itertools.count()


def _forget_programs(token: int) -> None:
    """Drop a dead engine's programs from the step cache.  Their static
    keys start with the engine's token, so nothing can hit them again,
    and their closures hold the model: until they go, so do its
    weights (8.6 GB for the latent-MoE cell of the benchmark, whose
    reference needs that room once the engine is gone)."""
    from ..runtime import step_cache as _sc
    _sc.step_cache.discard(
        lambda kind, key: isinstance(key, tuple) and key[:1] == (token,))


#: engine roles in a disaggregated deployment (serve/disagg.py): the
#: phase joins every serve program's static key, so a prefill engine
#: and a decode engine over the same weights never collide in the step
#: cache even when their geometry matches
PHASES = ("unified", "prefill", "decode")


class ServeEngine:
    """Continuous-batching paged-KV serving over a model whose blocks
    follow the layer protocol of ``serve/kernels.py`` (a GPT block: a K
    and a V row a token, learned positions; a latent block: one latent
    row, rotary positions, routed experts).  What differs between them
    is read off the model's layers, never set here.

    The cache is one pool a *cache group* (``serve/kernels.py``
    ``cache_groups``): the layers that keep the same ``cache_rows`` of a
    token for the same ``window`` share a buffer ``(layers of the group,
    streams, blocks, block_size, width)``, a :class:`BlockPool` and, a
    session, a block table.  A group whose layers read a window of keys
    retires the blocks wholly before the band as the session advances; a
    group whose layers read every key keeps them.  Admission, growth,
    preemption and ``finish`` ask every group: a session holds its
    blocks in all of them or in none.  ``pools`` / ``block_pools`` list
    them, groups without a window first; ``pool`` / ``block_pool`` are
    the first group's (the only one of a model whose layers are alike).

    ``num_blocks`` sizes the first group's pool, and that of every other
    group without a window (one block = ``block_size`` tokens' rows in
    each of the group's layers; block 0 is the reserved null block); a
    further group with a window is sized from the model, ``max_batch ×
    (ceil(window / block_size) + 2)`` blocks and a prefill chunk's, which
    every session's band always fits.  ``cache_dtype`` follows the
    session convention — default the token-embedding dtype, ``"int8"``
    for the quantized pool.  ``window`` is the window of layers that
    declare none (a GPT block; ``None``: they read every key).
    Speculation needs one group without a window, the prefix cache one
    group (``docs/serving.md``).

    Layers that keep a state of a *session* and nothing of a token (a
    state-space layer: ``block.cache_rows is None``, ``block.state``)
    form *state groups* (``serve/kernels.py`` ``state_groups``):
    ``states`` lists each group's buffers ``(layers of the group,
    max_batch + 1, *shape)``, sized from the model and ``max_batch``
    alone and in the layers' own dtypes; a session holds one slot of
    them from admission to ``finish`` or preemption, and starts from
    zeros whatever the slot held.  Such a model is served without the
    prefix cache, speculation and the KV handoffs (``docs/serving.md``
    says why); a model with no such layer is served as it always was.
    """

    def __init__(self, model, *, num_blocks, block_size=16, max_batch=8,
                 prefill_chunk=32, cache_dtype=None,
                 max_prefill_backlog=None, window=None, phase="unified",
                 draft=None, spec_k=4, draft_cache_dtype="int8",
                 prefix_cache=True):
        self._validate_model(model)
        if phase not in PHASES:
            raise ValueError(f"phase must be one of {PHASES}, got "
                             f"{phase!r}")
        self.model = model
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.window = window
        self._phase = phase
        self._params = list(model.parameters()) + list(model.buffers())
        dtype = cache_dtype if cache_dtype is not None \
            else model.tok_emb.weight.data.dtype
        self._dtype_name = dtype if isinstance(dtype, str) \
            else jnp.dtype(dtype).name
        self.groups, _ = _kernels.cache_groups(model, window)
        self.state_groups, _ = _kernels.state_groups(model)
        # a row a slot: as many slots as batch rows, and the null slot
        self.states = [init_state_buffers(g.state, len(g.layers), max_batch)
                       for g in self.state_groups]
        # the layers that keep a state and the bytes of it a session a
        # layer (the mean layer's, were the groups' to differ): the tick
        # record's ssm_* fields
        kept = [(len(g.layers), sum(int(np.prod(shape)) * jnp.dtype(dt).itemsize
                                    for shape, dt in g.state))
                for g in self.state_groups]
        self._state_layers = sum(n for n, _ in kept)
        self._state_bytes = sum(n * size for n, size in kept) \
            // max(self._state_layers, 1)
        # a residual of several streams: the sublayers that mix them --
        # the tick record's hc_* fields (_count_streams); None for one
        counts = getattr(model, "stream_sublayers", None)
        self._streams = counts() if counts is not None else None
        self._windowed = any(g.window is not None for g in self.groups)
        # (window, layers without one, layers with one): the tick
        # record's kv_* fields (_count_rows)
        self._kv_kinds = (
            min((g.window for g in self.groups if g.window is not None),
                default=None),
            sum(len(g.layers) for g in self.groups if g.window is None),
            sum(len(g.layers) for g in self.groups if g.window is not None))
        sizes = [self.num_blocks if i == 0 or g.window is None
                 else max_batch * (blocks_for(g.window, self.block_size) + 2)
                 + blocks_for(prefill_chunk, self.block_size) + 1
                 for i, g in enumerate(self.groups)]
        self.pools = [self._pool_for(g, n, dtype)
                      for g, n in zip(self.groups, sizes)]
        self.block_pools = [
            BlockPool(n, self.block_size,
                      metrics_prefix="serve." if i == 0
                      else f"serve.{g.name}.")
            for i, (g, n) in enumerate(zip(self.groups, sizes))]
        self.block_pool = self.block_pools[0]
        self._in_use_gauges = [
            (f"serve.pool.{g.name}.blocks_in_use", bp)
            for g, bp in zip(self.groups, self.block_pools)]
        # one group: a prefix is one table's blocks; a state a session:
        # a hit would need a snapshot of it at the block's boundary
        prefix_cache = prefix_cache and len(self.groups) == 1 \
            and not self.state_groups
        # -- speculative mode: a draft model served from its OWN pool
        # buffer (int8 by default — weight-only drafts are bandwidth
        # bound) whose block ids come from the SAME BlockPool free-list
        self.spec = draft is not None
        self.draft = draft
        self.spec_k = int(spec_k)
        self._d_params: List = []
        self._d_dtype_name = None
        self.dpool = None
        if self.spec:
            self._validate_spec(model, draft, window, self.spec_k)
            self._d_params = list(draft.parameters()) \
                + list(draft.buffers())
            d_dtype = draft_cache_dtype if draft_cache_dtype is not None \
                else draft.tok_emb.weight.data.dtype
            self._d_dtype_name = d_dtype if isinstance(d_dtype, str) \
                else jnp.dtype(d_dtype).name
            self.dpool = self._pool_for(
                _kernels.cache_groups(draft)[0][0], self.num_blocks, d_dtype)
        if max_prefill_backlog is None:
            max_prefill_backlog = 4 * prefill_chunk
        self.scheduler = Scheduler(
            self.block_pools, windows=[g.window for g in self.groups],
            max_batch=max_batch,
            prefill_chunk=prefill_chunk,
            max_prefill_backlog=max_prefill_backlog,
            max_positions=model.max_positions,
            spec_tables=self.spec,
            pos_slack=self.spec_k if self.spec else 0,
            prefix_cache=prefix_cache,
            cache_tag=self._cache_tag(epoch=0),
            state_slots=bool(self.state_groups))
        self._token = next(_SERVE_TOKENS)
        weakref.finalize(self, _forget_programs, self._token)
        self._donate = _executor.donation.enabled
        self._decode_prog = None
        self._prefill_prog = None
        self._copy_prog = None
        self._draft_prefill_prog = None
        self._spec_prog = None
        self._dispatch_no = itertools.count(1)
        self._tick = 0
        # what this tick's programs counted (routed layers' pairs a held
        # expert), device arrays until the tick's fetch
        self._counted: List = []
        self._fetched: List = []
        # prefix-cache telemetry (admission-weighted; the pool keeps
        # its own eviction counter)
        self._prefill_tokens_saved = 0
        self._prefix_prompt_tokens = 0
        self._cow_forks = 0
        self._spec_ticks = 0
        self._spec_committed = 0
        self._spec_offered = 0
        self._spec_accepted = 0
        self.results: Dict[str, List[int]] = {}
        # weight hot-swap bookkeeping (apex_tpu.rollout): monotonically
        # growing epoch per weight set; every finished request is
        # attributed to the target epoch it was ADMITTED under (epochs
        # only grow, so that is the oldest weights any token saw)
        self.weight_epochs: Dict[str, int] = {"target": 0, "draft": 0}
        self.result_meta: Dict[str, dict] = {}

    def _pool_for(self, group, num_blocks, dtype):
        """The pool buffer whose geometry is the group's layers'."""
        streams, heads, head_dim = group.rows
        return init_pool_buffer(len(group.layers), heads, head_dim,
                                num_blocks, self.block_size, dtype,
                                streams=streams)

    @property
    def pool(self):
        """The first cache group's buffer (the only one of a model whose
        layers keep the same rows for the same length)."""
        return self.pools[0]

    @pool.setter
    def pool(self, buf) -> None:
        self.pools[0] = buf

    def _cache(self):
        """The pools as the programs take them: the buffer, or a tuple of
        them where there are several groups."""
        return self.pools[0] if len(self.pools) == 1 else tuple(self.pools)

    def _set_cache(self, pools, states=()) -> None:
        """What a program gave back: the pools, and after them the state
        groups' buffers where the model has any."""
        self.pools = list(pools) if len(self.pools) > 1 else [pools]
        if states:
            self.states = list(states[0])

    def _states(self):
        """The state groups' buffers as the programs take them (donated,
        beside the pools): nothing for a model that has none."""
        return (tuple(self.states),) if self.state_groups else ()

    @staticmethod
    def _validate_model(model):
        for a in ("blocks", "tok_emb", "ln_f", "_mask_pad_logits",
                  "max_positions"):
            if not hasattr(model, a):
                raise ValueError(
                    f"ServeEngine needs model.{a} (the decode protocol)")
        for blk in model.blocks:
            rows = ("cache_rows", "chunk_rows", "read_decode", "read_chunk",
                    "finish")
            state = ("cache_rows", "state", "step", "chunk", "finish")
            keeps_rows = getattr(blk, "cache_rows", ()) is not None
            for a in rows if keeps_rows else state:
                if not hasattr(blk, a):
                    raise ValueError(
                        f"ServeEngine needs block.{a} — the layer "
                        f"protocol of serve/kernels.py: a block that "
                        f"keeps rows of a token has {', '.join(rows)}, "
                        f"and window where the block reads a band of keys; one "
                        f"that keeps a state of a session (cache_rows = "
                        f"None) has {', '.join(state[1:])} "
                        f"({type(blk).__name__} does not follow it)")
        if not any(getattr(blk, "cache_rows", None) is not None
                   for blk in model.blocks):
            raise NotImplementedError(
                "ServeEngine counts a session's positions by the blocks "
                "it holds: a model needs a layer that keeps rows of a "
                "token")
        axes = _sharded_decode_axes(model)
        if axes:
            names = ", ".join(f"{a}='{v}'" for a, v in axes)
            raise NotImplementedError(
                f"ServeEngine runs single-shard; the model was built "
                f"with {names}")

    def _validate_spec(self, model, draft, window, spec_k):
        self._validate_model(draft)
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        if self.state_groups or _kernels.state_groups(draft)[0]:
            raise NotImplementedError(
                "speculative mode + a state a session: a rejected draft "
                "would need the state rolled back to the last accepted "
                "token — serve one or the other")
        if self._windowed:
            raise NotImplementedError(
                "speculative mode + sliding window: the verify chunk "
                "would need a per-row band mask over retired blocks — "
                "serve one mode or the other")
        if len(self.groups) > 1 or len(_kernels.cache_groups(draft)[0]) > 1:
            raise NotImplementedError(
                "speculative mode serves models of one cache group: the "
                "draft and verify programs take one pool each")
        if draft.tok_emb.weight.shape[0] < model.tok_emb.weight.shape[0]:
            raise ValueError(
                "draft vocabulary is smaller than the target's — "
                "verified tokens could not be re-fed to the draft")
        if draft.max_positions < model.max_positions:
            raise ValueError(
                f"draft.max_positions {draft.max_positions} < target's "
                f"{model.max_positions}: the draft cache must cover "
                f"every position the target can reach")

    # -- programs ----------------------------------------------------------
    # One Program instance per kind: operand shapes (bucketed batch /
    # blocks / chunk) complete the step-cache key through the argument
    # signature, so each bucket compiles once and session churn re-hits.

    def _programs(self):
        if self._decode_prog is None:
            key = (self._token, self._phase, self.block_size,
                   self._dtype_name, self.window, self._donate)
            # the pools, and the state groups' buffers beside them
            donate = (1, 2) if self.state_groups else (1,)
            self._decode_prog = _executor.Program(
                "decode_step", key,
                _kernels.build_decode_fn(
                    self.model, self._params, self.block_size,
                    self.num_blocks, self.window),
                donate_argnums=donate if self._donate else ())
            self._prefill_prog = _executor.Program(
                "prefill_step", key,
                _kernels.build_prefill_fn(
                    self.model, self._params, self.block_size,
                    self.num_blocks, self.window),
                donate_argnums=donate if self._donate else ())
        return self._prefill_prog, self._decode_prog

    def _spec_programs(self):
        if self._spec_prog is None:
            key = (self._token, self._phase, self.block_size,
                   self._dtype_name, self._d_dtype_name, self.spec_k,
                   self._donate)
            self._draft_prefill_prog = _executor.Program(
                "draft_prefill_step", key,
                _kernels.build_prefill_fn(
                    self.draft, self._d_params, self.block_size,
                    self.num_blocks, None),
                donate_argnums=(1,) if self._donate else ())
            self._spec_prog = _executor.Program(
                "spec_verify_step", key,
                _kernels.build_spec_verify_fn(
                    self.model, self._params, self.draft,
                    self._d_params, self.block_size, self.num_blocks,
                    self.spec_k),
                donate_argnums=(2, 3) if self._donate else ())
        return self._draft_prefill_prog, self._spec_prog

    def _copy_program(self):
        if self._copy_prog is None:
            key = (self._token, self._phase, self.block_size,
                   self._dtype_name, self._donate)
            self._copy_prog = _executor.Program(
                "block_copy", key, _kernels.build_block_copy_fn(),
                donate_argnums=(0,) if self._donate else ())
        return self._copy_prog

    def _vals(self):
        return [p.data for p in self._params]

    def _d_vals(self):
        return [p.data for p in self._d_params]

    # -- prefix cache ------------------------------------------------------

    def _cache_tag(self, epoch=None) -> str:
        """The chain-key compatibility stamp: everything a committed
        block's bytes depend on besides its token chain.  dtype and
        block size fix the stored layout, the window changes every KV
        row's upstream hidden states, and the target weight epoch makes
        ``publish_weights`` an automatic whole-cache invalidation — a
        new epoch means new tags, so stale entries can never match."""
        if epoch is None:
            epoch = self.weight_epochs["target"]
        return (f"{self._dtype_name}:b{self.block_size}:"
                f"w{self.groups[0].window}:e{int(epoch)}")

    def _dispatch_cow(self, s: Session) -> None:
        """Materialize admission's copy-on-write forks: one paged
        block-copy dispatch per fork, then release the shared source's
        reference (scheduler.complete_cow) — the source was kept
        referenced so the dispatch stream copies its bytes before any
        eviction could recycle them."""
        if not s.cow_pending:
            return
        prog = self._copy_program()
        for _idx, fsrc, fdst in s.cow_pending:
            self.pool = _executor.executor.submit(
                prog, (self.pool, np.int32(fsrc), np.int32(fdst)),
                step=next(self._dispatch_no))
        n = self.scheduler.complete_cow(s)
        self._cow_forks += n
        _obs.counter("serve.prefix.cow_forks").inc(n)

    def _note_commit(self, s: Session) -> None:
        """Chain-commit the session's newly full blocks — unless its
        KV was written under an older target epoch (a mid-swap session
        decodes under mixed weights; hashing its blocks would poison
        the index with bytes no current-epoch chain can reproduce)."""
        if s.weight_epoch == self.weight_epochs["target"]:
            n = self.scheduler.note_commit(s)
            if n:
                _obs.counter("serve.prefix.blocks_chained").inc(n)

    # -- intake ------------------------------------------------------------

    def submit(self, request: Request) -> None:
        self.scheduler.submit(request)
        sess = self.scheduler.queue[-1]
        sess.t_queued = time.monotonic()
        _obs.event("serve.request", rid=request.rid, phase="queued",
                   tick=self._tick, prompt_len=len(request.prompt),
                   max_new=request.max_new_tokens)

    def submit_recompute(self, request: Request, out) -> None:
        """Queue a request that already generated ``out`` tokens on
        another engine (a session shed or lost during a fleet shrink):
        admission re-prefills ``prompt + out[:-1]`` in recompute mode,
        so the continuation is bitwise the uninterrupted one."""
        self.scheduler.submit_recompute(request, out)
        sess = self.scheduler.queue[-1]
        sess.t_queued = time.monotonic()
        _obs.event("serve.request", rid=request.rid, phase="requeued",
                   tick=self._tick, generated=len(sess.out))

    def evict_session(self, s: Session) -> Session:
        """Shed a live session: free its blocks (both tables) and hand
        it back in recompute mode for the caller — the elastic fleet —
        to re-home on another engine.  Local preemption stays
        ``preempt_for`` (re-queues here); this is the cross-engine
        half."""
        self.scheduler.evict(s)
        _obs.event("serve.request", rid=s.rid, phase="shed",
                   tick=self._tick, generated=len(s.out))
        return s

    # -- weight hot-swap (apex_tpu.rollout) --------------------------------

    def publish_weights(self, leaves, *, which: str = "target",
                        epoch: Optional[int] = None) -> int:
        """Swap the ``which`` model's parameter values between ticks —
        the serve half of the rollout weight-publish path.

        No program is invalidated: the bucketed serve programs pass
        parameter VALUES as traced operands (``_vals()`` reads
        ``p.data`` at every dispatch) and their static keys are
        config-only, so rebinding ``.data`` on the SAME Parameter
        objects changes what the next dispatch computes without a
        recompile.  Shapes and dtypes must match the current values
        exactly — a different shape/dtype is a different engine, not a
        new epoch (and the KV pool dtype was derived from the old
        weights).  Buffers are not swapped.

        Live sessions keep their KV cache: rows written under the old
        weights stay as-is, so a mid-generation swap continues the
        sequence under mixed weights.  That is the documented semantics
        (docs/rollout.md) — each request is attributed to the epoch it
        was ADMITTED under, the oldest weights any of its tokens saw.

        ``epoch`` pins the recorded epoch (checkpoint restore republishes
        at the saved epoch); default bumps the counter by one.  Returns
        the epoch now being served.
        """
        if which not in ("target", "draft"):
            raise ValueError(f"which must be 'target' or 'draft', "
                             f"got {which!r}")
        if which == "draft":
            if not self.spec:
                raise RuntimeError(
                    "publish_weights(which='draft') on a non-speculative "
                    "engine — no draft to publish into")
            params = list(self.draft.parameters())
        else:
            params = list(self.model.parameters())
        leaves = list(leaves)
        if len(leaves) != len(params):
            raise ValueError(
                f"publish_weights({which!r}): {len(leaves)} leaves for "
                f"{len(params)} parameters — different model config")
        for p, v in zip(params, leaves):
            if tuple(getattr(v, "shape", ())) != tuple(p.data.shape):
                raise ValueError(
                    f"publish_weights({which!r}): leaf {p.name or '?'} "
                    f"shape {tuple(getattr(v, 'shape', ()))} != serving "
                    f"shape {tuple(p.data.shape)}")
            if jnp.dtype(getattr(v, "dtype", None)) != \
                    jnp.dtype(p.data.dtype):
                raise ValueError(
                    f"publish_weights({which!r}): leaf {p.name or '?'} "
                    f"dtype {jnp.dtype(v.dtype)} != serving dtype "
                    f"{jnp.dtype(p.data.dtype)} — cast on the publish "
                    f"side (rollout.WeightPublisher casts once)")
        for p, v in zip(params, leaves):
            p.data = v
        ep = self.weight_epochs[which] + 1 if epoch is None else int(epoch)
        self.weight_epochs[which] = ep
        if which == "target":
            # invalidate the prefix cache: the new epoch lands in the
            # chain tag (so future admissions can't match pre-swap
            # chains) and cached-tier blocks holding stale KV go back
            # to the free list rather than waiting out the LRU
            self.scheduler.cache_tag = self._cache_tag()
            self.block_pool.flush_cache()
        _obs.event("serve.weight_swap", which=which, epoch=ep,
                   tick=self._tick, leaves=len(leaves))
        return ep

    # -- the tick ----------------------------------------------------------

    def step(self) -> bool:
        """One engine tick: admit, one prefill (or draft catch-up)
        chunk, one decode/speculative tick.  Returns True while any
        request is live or queued.

        A ``phase="prefill"`` engine stops after the prefill stage —
        sessions that complete prefill wait in DECODE state for the
        disaggregation coordinator (:mod:`apex_tpu.serve.disagg`) to
        stream their KV blocks out.  A ``phase="decode"`` engine runs
        the full tick (its prefill stage serves recompute-mode
        re-admissions after local preemption)."""
        self._tick += 1
        # the root of the tick's span tree (docs/observability.md lists
        # the children); spans opened below take its ``tick``
        with _spans.span("serve.step", tick=self._tick, decode_batch=0,
                         prefill_rid=None) as tick:
            more = self._run_tick(tick)
            if self._counted:       # what no decode fetch took with it
                with _spans.span("serve.fetch", what="counts"):
                    self._fetched += jax.device_get(self._counted)
                self._counted = []
            if self._fetched:
                self._publish_moe(tick)
        if tick["decode_batch"]:
            _obs.histogram("serve.decode_tick_ms").observe(tick["dur_ms"])
        return more

    def _run_tick(self, tick: dict) -> bool:
        if self.scheduler.queue:
            with _spans.span("serve.admit") as rec:
                rec["n"] = self._admit()
        ps = self.scheduler.next_prefill()
        if ps is not None:
            tick["prefill_rid"] = ps.rid
            self._prefill_chunk(ps)
        elif self.spec:
            cs = self._next_draft_catchup()
            if cs is not None:
                self._draft_catchup_chunk(cs)
        if self._phase != "prefill":
            with _spans.span("serve.ensure_blocks"):
                self._ensure_decode_blocks()
            ds = self._decode_ready()
            if ds:
                tick["decode_batch"] = len(ds)
                if self.spec:
                    self._spec_tick(ds)
                else:
                    if self._windowed:
                        self._count_rows(tick, ds)
                    if self._state_layers:
                        self._count_state(tick, ds)
                    if self._streams:
                        self._count_streams(tick, ds)
                    self._decode_tick(ds)
        _obs.gauge("serve.queue_depth").set(len(self.scheduler.queue))
        _obs.gauge("serve.active_sessions").set(
            len(self.scheduler.sessions))
        for name, bp in self._in_use_gauges:
            _obs.gauge(name).set(bp.in_use)
        return self.scheduler.has_work()

    def _admit(self) -> int:
        """Admission with its bookkeeping; returns the number admitted."""
        admitted = self.scheduler.admit()
        for s in admitted:
            s.weight_epoch = self.weight_epochs["target"]
            self._dispatch_cow(s)
            self._prefill_tokens_saved += s.prefix_hit_tokens
            self._prefix_prompt_tokens += len(s.prefill_src)
            if s.prefix_hit_tokens:
                _obs.counter("serve.prefix.tokens_saved").inc(
                    s.prefix_hit_tokens)
            if self._prefix_prompt_tokens:
                _obs.gauge("serve.prefix.hit_rate").set(
                    self._prefill_tokens_saved
                    / self._prefix_prompt_tokens)
            _obs.event("serve.request", rid=s.rid, phase="prefill",
                       tick=self._tick, blocks=len(s.table),
                       prefix_hit=s.prefix_hit_tokens,
                       weight_epoch=s.weight_epoch)
        return len(admitted)

    def run(self, requests: Sequence[Request], arrivals=None,
            watchdog_deadline_s=None, max_ticks=None):
        """Serve ``requests`` to completion; returns ``{rid: tokens}``.

        ``arrivals``: optional per-request tick indices (an open-loop
        trace — request i becomes visible at tick ``arrivals[i]``);
        None submits everything up front.  ``watchdog_deadline_s`` arms
        a stall watchdog over the loop: every dispatch heartbeats, so
        a wedged backend fires ``watchdog.stall`` instead of hanging."""
        pending = sorted(
            zip(arrivals if arrivals is not None else [0] * len(requests),
                range(len(requests))),
            key=lambda p: (p[0], p[1]))
        wd = _watchdog.StallWatchdog(watchdog_deadline_s) \
            if watchdog_deadline_s else None
        if wd is not None:
            wd.start()
        try:
            i = 0
            while True:
                while i < len(pending) and pending[i][0] <= self._tick:
                    self.submit(requests[pending[i][1]])
                    i += 1
                more = self.step()
                if not more and i >= len(pending):
                    break
                if max_ticks is not None and self._tick >= max_ticks:
                    break
        finally:
            if wd is not None:
                wd.stop()
        return dict(self.results)

    # -- internals ---------------------------------------------------------

    def _prefill_chunk(self, s: Session) -> None:
        chunk = self.scheduler.prefill_chunk
        n = min(chunk, s.prefill_remaining)
        with _spans.span("serve.prefill_chunk", rid=s.rid, n_real=n):
            self._prefill(s, chunk, n)

    def _tables(self, packed):
        """Packed tables (one group's, or a tuple a group) as the
        programs' operands: the scheduler's int32 arrays as they are,
        fresh for this dispatch (``docs/serving.md``, packing)."""
        return packed

    def _prefill(self, s: Session, chunk: int, n: int) -> None:
        prefill_prog, _ = self._programs()
        t0 = s.position
        if self._windowed:
            # a window group's blocks are granted a chunk at a time (the
            # ones before the band go back as the prompt goes in)
            last_chunk = t0 + n >= len(s.prefill_src)
            if not self._grow_or_preempt(s, t0 + n + last_chunk):
                return
        toks = list(s.prefill_src[t0:t0 + n])
        toks += [0] * (chunk - n)
        slot = (np.asarray([s.slot], np.int32),) if self.state_groups else ()
        last, pools, counted, *states = _executor.executor.submit(
            prefill_prog,
            (self._vals(), self._cache(), *self._states(),
             np.asarray([toks], np.int32),
             self._tables(self.scheduler.pack_groups([s], 1)[1]),
             np.int32(t0), np.int32(n), *slot),
            step=next(self._dispatch_no))
        self._set_cache(pools, states)
        if counted is not None:
            self._counted.append(counted)
        if self.spec and s.draft_position == t0:
            # lockstep draft ingest: the draft's cache tracks the
            # target's row for row through prefill (and recompute
            # re-prefill), so a fresh session is spec-ready the tick
            # its prefill completes.  A prefix-hit session starts its
            # target cursor PAST rows the draft never saw — it skips
            # lockstep and repairs through the catch-up path instead.
            draft_prog, _ = self._spec_programs()
            _dl, self.dpool, _ = _executor.executor.submit(
                draft_prog,
                (self._d_vals(), self.dpool,
                 np.asarray([toks], np.int32),
                 self.scheduler.pack_rows([s.draft_table], 1)[1],
                 np.int32(t0), np.int32(n)),
                step=next(self._dispatch_no))
            s.draft_position = t0 + n
        s.position = t0 + n
        self._retire(s)
        self._note_commit(s)
        if s.prefill_remaining > 0:
            return
        s.state = DECODE
        if s.emit_on_prefill:
            with _spans.span("serve.fetch", what="first_token"):
                tok = int(jnp.argmax(last[0]))
            s.out.append(tok)
            s.pending_tok = tok
            s.t_first = time.monotonic()
            _obs.histogram("serve.ttft_ms").observe(
                (s.t_first - s.t_queued) * 1e3)
            _obs.event("serve.request", rid=s.rid, phase="first_token",
                       tick=self._tick)
            if s.finished():
                self._finish(s)

    def _next_draft_catchup(self) -> Optional[Session]:
        """Oldest decoding session whose draft cache lags its target
        cache — only handed-off sessions (or plain-decode fallback
        ticks) create the lag; one catch-up chunk per tick repairs it
        in the prefill slot."""
        for s in self.scheduler.sessions:
            if s.state == DECODE and s.draft_position < s.position:
                return s
        return None

    def _draft_catchup_chunk(self, s: Session) -> None:
        draft_prog, _ = self._spec_programs()
        chunk = self.scheduler.prefill_chunk
        fed = s.fed_tokens
        d0 = s.draft_position
        n = min(chunk, s.position - d0)
        toks = list(fed[d0:d0 + n]) + [0] * (chunk - n)
        _dl, self.dpool, _ = _executor.executor.submit(
            draft_prog,
            (self._d_vals(), self.dpool, np.asarray([toks], np.int32),
             self.scheduler.pack_rows([s.draft_table], 1)[1],
             np.int32(d0), np.int32(n)),
            step=next(self._dispatch_no))
        s.draft_position = d0 + n

    def _decode_ready(self) -> List[Session]:
        """Sessions eligible for this tick's decode dispatch: every
        DECODE session, minus (spec mode) those whose draft cache is
        still catching up — including them would verify against stale
        draft rows."""
        ds = self.scheduler.decode_sessions()
        if not self.spec:
            return ds
        return [s for s in ds if s.draft_position == s.position]

    def _ensure_decode_blocks(self) -> None:
        """Every decoding session needs its table to cover the rows
        this tick writes — one row for plain decode, ``spec_k + 1``
        rows across BOTH tables for a speculative tick; a dry pool
        preempts the newest session (recompute mode) until the
        survivors fit."""
        slack = self.spec_k if self.spec else 0
        for s in list(self.scheduler.decode_sessions()):
            if s.state != DECODE:
                continue                     # preempted below us
            if self.spec and s.draft_position < s.position:
                continue                     # catch-up session: no tick
            self._grow_or_preempt(s, s.position + 1 + slack)

    def _grow_or_preempt(self, s: Session, need: int) -> bool:
        """Every table of ``s`` (every cache group's, and the draft's)
        grown to cover ``need`` rows, the newest sessions preempted
        while a pool is dry; False if ``s`` itself had to go."""
        while not (self.scheduler.grow(s, need)
                   and (not self.spec
                        or self.scheduler.grow(s, need, draft=True))):
            victim = self.scheduler.preempt_for(s)
            _obs.counter("serve.preemptions").inc()
            _obs.event("serve.request", rid=victim.rid,
                       phase="preempted", tick=self._tick,
                       generated=len(victim.out))
            if victim is s:
                return False
        return True

    def _retire(self, s: Session) -> None:
        """The blocks wholly before the band of every window group go
        back to their pools."""
        if self._windowed:
            n = self.scheduler.retire_window_blocks(s)
            if n:
                _obs.counter("serve.window.blocks_retired").inc(n)

    def _decode_tick(self, sessions: List[Session]) -> None:
        _, decode_prog = self._programs()
        with _spans.span("serve.pack") as rec:
            b, nb, tokens, positions, tables = \
                self.scheduler.pack_decode(sessions)
            rec["entries"] = b * (sum(nb) if isinstance(nb, tuple) else nb)
            operands = (np.asarray(tokens, np.int32),
                        np.asarray(positions, np.int32), self._tables(tables))
            if self.state_groups:
                operands += (np.asarray(self.scheduler.pack_slots(
                    sessions, b), np.int32),)
        nxt, _logits, pools, counted, *states = _executor.executor.submit(
            decode_prog,
            (self._vals(), self._cache(), *self._states(), *operands),
            step=next(self._dispatch_no))
        self._set_cache(pools, states)
        if counted is not None:
            self._counted.append(counted)
        with _spans.span("serve.fetch", what="tokens"):
            if self._counted:
                # the layers' counts of this tick's programs (a prefill
                # chunk's too) come over in the same fetch as the tokens
                nxt, fetched = jax.device_get((nxt, self._counted))
                self._fetched += fetched
                self._counted = []
            else:
                nxt = np.asarray(nxt)
        with _spans.span("serve.commit", n_finished=0) as rec:
            for i, s in enumerate(sessions):
                s.position += 1
                tok = int(nxt[i])
                s.out.append(tok)
                s.pending_tok = tok
                self._retire(s)
                self._note_commit(s)
                if s.finished():
                    self._finish(s)
                    rec["n_finished"] += 1

    def _count_rows(self, tick: dict, sessions: List[Session]) -> None:
        """What one layer of each kind reads in this tick's decode
        dispatch, on the tick's ``serve.step`` record
        (docs/observability.md): a query at position p reads keys 0 .. p
        in a layer without a window and the last ``window`` of them in
        one with."""
        window, layers_full, layers_window = self._kv_kinds
        depths = [s.position + 1 for s in sessions]
        tick["kv_rows_full"] = sum(depths)
        tick["kv_rows_window"] = sum(min(d, window) for d in depths)
        tick["kv_layers_full"] = layers_full
        tick["kv_layers_window"] = layers_window
        tick["kv_window"] = window

    def _count_state(self, tick: dict, sessions: List[Session]) -> None:
        """What the state-space layers move in this tick's decode
        dispatch, on the tick's ``serve.step`` record
        (docs/observability.md): each live session's state is read once
        and written once by every layer that keeps one."""
        tick["ssm_sessions"] = len(sessions)
        tick["ssm_layers"] = self._state_layers
        tick["ssm_state_bytes"] = 2 * len(sessions) * self._state_bytes

    def _count_streams(self, tick: dict, sessions: List[Session]) -> None:
        """What the residual's mixes move in this tick's decode dispatch,
        on the tick's ``serve.step`` record (docs/observability.md): each
        live session's row goes through a mapping and a mix at every
        sublayer."""
        tick["hc_rows"] = len(sessions)
        tick["hc_sublayers"] = self._streams

    def _publish_moe(self, tick: dict) -> None:
        """What the tick's programs counted in their routed layers —
        each ``(routed layers, held experts)`` token-expert pairs —
        as ``serve.moe.*`` counters and on the tick's ``serve.step``
        record (docs/observability.md)."""
        per_program = np.stack(self._fetched)       # (programs, layers, held)
        self._fetched = []
        tick["moe_pairs"] = int(per_program.sum())
        # (program, layer, expert) cells with a pair: how often an
        # expert's matrices had to be streamed in this tick
        tick["moe_experts_hit"] = int((per_program > 0).sum())
        tick["moe_pairs_max"] = int(per_program.max())
        tick["moe_layers"], tick["moe_held"] = per_program.shape[1:]
        _obs.counter("serve.moe.pairs").inc(tick["moe_pairs"])
        _obs.counter("serve.moe.experts_hit").inc(tick["moe_experts_hit"])
        _obs.gauge("serve.moe.pairs_max").set(tick["moe_pairs_max"])

    def _spec_tick(self, sessions: List[Session]) -> None:
        """One batched speculative tick: a single ``spec_verify_step``
        dispatch drafts ``spec_k`` proposals and verifies them with one
        (k+1)-wide target pass; the host commits the ragged accepted
        prefix per row.  Commitment rule: row i's emitted tokens are
        the TARGET's argmax at positions p..p+k conditioned on its own
        committed prefix, and ``n_acc`` only ever truncates that stream
        where the draft diverged — so the committed token sequence is
        bitwise the plain-decode sequence, whatever the acceptance
        pattern, eos/max_new truncation, or preemption does to tick
        boundaries."""
        _, spec_prog = self._spec_programs()
        with _spans.span("serve.pack") as rec:
            b, nbt, nbd, tokens, positions, t_tables, d_tables = \
                self.scheduler.pack_spec(sessions)
            rec["entries"] = b * (nbt + nbd)
            operands = (np.asarray(tokens, np.int32),
                        np.asarray(positions, np.int32),
                        *self._tables((t_tables, d_tables)))
        emitted, n_acc, self.pool, self.dpool = _executor.executor.submit(
            spec_prog,
            (self._vals(), self._d_vals(), self.pool, self.dpool, *operands),
            step=next(self._dispatch_no))
        with _spans.span("serve.fetch", what="spec_tokens"):
            emitted = np.asarray(emitted)
            n_acc = np.asarray(n_acc)
        with _spans.span("serve.commit", n_finished=0) as rec:
            committed_total = 0
            for i, s in enumerate(sessions):
                m = 0
                for j in range(int(n_acc[i])):
                    tok = int(emitted[i, j])
                    s.out.append(tok)
                    s.pending_tok = tok
                    s.position += 1
                    m += 1
                    if s.finished():
                        break
                # rows p..p+m-1 of the draft cache hold exactly the
                # committed tokens (the rejected tail past them is rewritten
                # by the next tick's chunk before any mask can read it)
                s.draft_position = s.position
                # chain-commit only blocks the committed position has fully
                # crossed — every row of such a block holds committed-token
                # KV (any rejected-tail rows were overwritten by later
                # ticks before position could pass them)
                self._note_commit(s)
                committed_total += m
                self._spec_offered += self.spec_k
                self._spec_accepted += max(0, m - 1)
                if s.finished():
                    self._finish(s)
                    rec["n_finished"] += 1
        self._spec_ticks += 1
        self._spec_committed += committed_total
        _obs.histogram("serve.spec.accepted_tokens").observe(
            committed_total)
        if self._spec_offered:
            _obs.gauge("serve.spec.accept_rate").set(
                self._spec_accepted / self._spec_offered)

    # -- disaggregation handoff --------------------------------------------

    def harvest_ready(self) -> List[Session]:
        """Prefill-phase engines: sessions whose prefill completed
        (DECODE state, first token emitted) and now wait for the
        coordinator to stream their KV blocks to a decode engine."""
        return [s for s in self.scheduler.decode_sessions()
                if not s.finished()]

    def release_handoff(self, s: Session) -> None:
        """Drop a session whose KV blocks were streamed out: frees its
        blocks and batch slot without recording a result — the decode
        engine owns the request from here."""
        self.scheduler.finish(s)

    def ingest_handoff(self, request: Request, *, out, pending_tok,
                       position, handoff_dir, t_queued=0.0,
                       t_first=None, n_blocks=None, hash_chain=None,
                       weight_epoch=None) -> Optional[Session]:
        """Decode-phase engines: adopt a prefilled session whose KV
        blocks were streamed into ``handoff_dir`` (schema-3 shard
        files, runtime/resilience.py).  Allocates a fresh target table
        and scatters the streamed blocks into this engine's pool
        verbatim — bitwise, no recompute; in spec mode a draft table of
        the same size is allocated but the draft cache starts EMPTY and
        catches up through the prefill slot.  ``n_blocks`` is the
        streamed block count when the source table had grown past the
        admission grant (the elastic fleet passes the snapshot
        manifest's count — a mid-decode session owns
        ``blocks_for(position)`` blocks); None means the
        disaggregation default below.  Returns the new session, or
        None when a batch slot / blocks are not available right now
        (the coordinator retries next tick)."""
        from ..runtime.resilience import load_kv_handoff
        if len(self.groups) > 1 or self.state_groups:
            raise NotImplementedError(
                "a KV handoff streams one cache group's blocks; this "
                "model's layers form several, or keep a state a session")
        need_pos = len(request.prompt) + request.max_new_tokens \
            + self.scheduler.pos_slack
        if need_pos > self.scheduler.max_positions:
            raise ValueError(
                f"request {request.rid}: {need_pos} positions exceed "
                f"decode engine max_positions "
                f"{self.scheduler.max_positions}")
        if len(self.scheduler.sessions) >= self.scheduler.max_batch:
            return None
        if n_blocks is None:
            # the prefill engine's table is exactly its admission grant
            # — blocks_for(prompt + 1) — because prefill-phase engines
            # never decode, so the streamed block count is deterministic
            n_blocks = blocks_for(len(request.prompt) + 1,
                                  self.block_size)
        have = int(n_blocks)
        ids = self.block_pool.alloc(have)
        if ids is None:
            return None
        draft_ids: List[int] = []
        if self.spec:
            draft_ids = self.block_pool.alloc(have)
            if draft_ids is None:
                self.block_pool.free(ids)
                return None
        try:
            self.pool, _peak = load_kv_handoff(
                handoff_dir, self.pool, ids)
        except Exception:
            self.block_pool.free(ids)
            if draft_ids:
                self.block_pool.free(draft_ids)
            raise
        s = self.scheduler.import_session(request, ids, draft_ids, position)
        s.pending_tok = int(pending_tok)
        s.out = list(out)
        s.t_queued = t_queued
        s.t_first = t_first
        s.weight_epoch = self.weight_epochs["target"]
        if hash_chain and self.scheduler.prefix_cache \
                and weight_epoch == self.weight_epochs["target"]:
            # re-link the migrated chain into THIS pool's index: the
            # streamed blocks are bitwise copies of committed-prefix
            # blocks, so they are valid cache entries here too
            s.hash_chain = list(hash_chain)
            s.committed_blocks = len(s.hash_chain)
            for bid, key in zip(ids, s.hash_chain):
                self.block_pool.commit(bid, key)
        elif hash_chain:
            # the chain was built under a different weight epoch than
            # this engine serves — the KV itself stays valid for THIS
            # session (mixed-epoch semantics, docs/rollout.md) but must
            # never be published for cross-request reuse
            s.cacheable = False
        self._retire(s)
        _obs.event("serve.request", rid=s.rid, phase="ingested",
                   tick=self._tick, blocks=have,
                   generated=len(s.out))
        return s

    def _finish(self, s: Session) -> None:
        self.results[s.rid] = list(s.out)
        self.result_meta[s.rid] = {"weight_epoch": s.weight_epoch,
                                   "prompt_len": len(s.request.prompt)}
        s.t_done = time.monotonic()
        _obs.histogram("serve.e2e_ms").observe(
            (s.t_done - s.t_queued) * 1e3)
        _obs.event("serve.request", rid=s.rid, phase="done",
                   tick=self._tick, generated=len(s.out),
                   weight_epoch=s.weight_epoch)
        self.scheduler.finish(s)

    # -- teardown ----------------------------------------------------------

    def close(self) -> None:
        """Tear the engine down: return every live session's blocks —
        every cache group's AND draft tables — to the pools, drop the
        queue (queued sessions hold no blocks), and assert the pool is
        leak-free.  An engine dropped mid-run without this strands its
        resident sessions' blocks; the elastic fleet also calls it when
        a replica's simulated process dies (the pool's memory dies with
        the process).  Idempotent; no result is recorded for the
        sessions it drops."""
        for s in list(self.scheduler.sessions):
            self.scheduler.finish(s)
        self.scheduler.queue.clear()
        for bp in self.block_pools:
            bp.check_no_leaks()
        if self.scheduler.slots is not None:
            self.scheduler.slots.check_no_leaks()

    def __enter__(self) -> "ServeEngine":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.close()
        return False

    # -- introspection -----------------------------------------------------

    @property
    def tick(self) -> int:
        """Ticks executed so far — the loop's logical clock (open-loop
        arrival traces index into it)."""
        return self._tick

    def metrics(self) -> dict:
        """SLO snapshot: compile/dispatch counters per serve kind plus
        the engine's own gauges/histograms."""
        from ..runtime import step_cache as _sc
        snap = _obs.get_registry().snapshot()
        out = {
            "decode": _sc.kind_stats("decode_step"),
            "prefill": _sc.kind_stats("prefill_step"),
            "pool_occupancy": self.block_pool.occupancy,
            "queue_depth": len(self.scheduler.queue),
            "prefix_cache": {
                "hit_rate": (self._prefill_tokens_saved
                             / self._prefix_prompt_tokens
                             if self._prefix_prompt_tokens else 0.0),
                "prefill_tokens_saved": self._prefill_tokens_saved,
                "cached_blocks": self.block_pool.cached_count,
                "cow_forks": self._cow_forks,
                "cache_evictions": self.block_pool.cache_evictions,
            },
            "histograms": {k: v for k, v in snap["histograms"].items()
                           if k.startswith("serve.")},
        }
        if self.spec:
            out["spec_verify"] = _sc.kind_stats("spec_verify_step")
            out["draft_prefill"] = _sc.kind_stats("draft_prefill_step")
            out["spec"] = {
                "ticks": self._spec_ticks,
                "committed_tokens": self._spec_committed,
                "offered": self._spec_offered,
                "accepted": self._spec_accepted,
                "accept_rate": (self._spec_accepted / self._spec_offered
                                if self._spec_offered else 0.0),
                "tokens_per_tick": (self._spec_committed
                                    / self._spec_ticks
                                    if self._spec_ticks else 0.0),
            }
        return out
