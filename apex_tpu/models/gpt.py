"""GPT-style causal decoder family — the autoregressive counterpart to
models/bert.py, built from the same fused components.

The reference repo carries no language models of its own (SURVEY.md §2 —
its fused pieces were consumed by external scripts); this standalone
decoder completes the transformer story: pre-LN blocks, causal Pallas
flash attention (``SelfMultiheadAttn`` with a time mask), FusedLayerNorm,
GELU FFN, weight-tied LM head.

Layout: public API is batch-first ``(B, S)`` token ids; internally the
decoder runs ``(S, B, E)`` for the attention module's reference layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..normalization import FusedLayerNorm
from ..contrib.multihead_attn import SelfMultiheadAttn
from ..nn.modules import fold_shard_into_key as _fold_shard_into_key


class GptBlock(nn.Module):
    """Pre-LN decoder block: LN → causal MHA → residual, LN → GELU FFN →
    residual."""

    def __init__(self, hidden, heads, intermediate, dropout=0.1,
                 attn_dropout=0.1, sp_axis=None, tp_axis=None,
                 attn_bias=False, _dense_ffn=True):
        super().__init__()
        self.ln1 = FusedLayerNorm(hidden)
        # causal=True: the flash path masks the triangle in-kernel with
        # no O(S^2) mask operand.  Attention dropout ALSO rides the
        # kernel (counter-based hash mask regenerated in the backward,
        # ops/pallas/attention.py) — no (S, S) dropout mask tensor in
        # HBM; composes with tp_axis (per-shard seed streams) and
        # sp_axis (ring: bit-consistent global hash mask).
        # attn_bias=True (GPT-2 checkpoints carry QKV/out-proj biases)
        # selects the reference's 'default' impl, which is the one that
        # supports biases (reference contrib/multihead_attn/
        # self_multihead_attn.py fast-impl assert) — the materializing
        # attention path, priced in docs/models.md
        self.attn = SelfMultiheadAttn(hidden, heads, dropout=attn_dropout,
                                      bias=attn_bias,
                                      impl="default" if attn_bias
                                      else "fast", causal=True,
                                      seq_parallel_axis=sp_axis,
                                      tensor_parallel_axis=tp_axis)
        self.ln2 = FusedLayerNorm(hidden)
        if _dense_ffn:
            self.fc1 = nn.Linear(hidden, intermediate)
            self.fc2 = nn.Linear(intermediate, hidden)
        else:
            # MoeGptBlock supplies its own routed FFN (the LlamaBlock
            # convention): skip drawing dense matrices it would discard
            self.fc1 = self.fc2 = None
        self.dropout = nn.Dropout(dropout)
        self.tp_axis = tp_axis
        self.sp_axis = sp_axis

    def _ffn(self, ctx, h):
        """The feed-forward on the LN2 output — one hook for the dense,
        Megatron-TP, and (in MoeGptBlock) expert-routed variants, shared
        by the training forward and every cached decode path."""
        if self.tp_axis is not None:
            # Megatron MLP: fc1 column-parallel, gelu on the sharded
            # hidden, fc2 row-parallel — one psum for the pair; weights
            # stay full, the shard slice happens at trace time
            from ..parallel.tensor_parallel import tp_ffn
            return tp_ffn(h,
                          ctx.value(self.fc1.weight),
                          ctx.value(self.fc1.bias),
                          ctx.value(self.fc2.weight),
                          ctx.value(self.fc2.bias),
                          self.tp_axis, activation=F.gelu)
        return self.fc2.forward(ctx, F.gelu(self.fc1.forward(ctx, h)))

    def forward(self, ctx, x):
        h, _ = self.attn.forward(ctx, self.ln1.forward(ctx, x))
        x = x + self.dropout.forward(ctx, h)
        h = self._ffn(ctx, self.ln2.forward(ctx, x))
        return x + self.dropout.forward(ctx, h)

    def tp_sharded_params(self):
        """Parameters whose per-device gradients are block-sparse under
        ``tp_axis`` (each device's slice sees only its block): their grads
        must be psum'd over the TP axis to keep the replicated full
        parameters consistent (training/step.py handles this when built
        with ``tp_axis``).  The attention subset lives on the attention
        module itself; this block adds its column/row MLP entries."""
        return self.attn.tp_sharded_params() + [
            self.fc1.weight, self.fc1.bias, self.fc2.weight]

    def _chunk_qkv(self, ctx, x):
        """(B, S_c, E) -> q/k/v (B, H, S_c, D) via the training
        projection (the interleaved QKV layout of
        attn_funcs._split_interleaved_qkv), so caches filled here
        reproduce the training forward's attention.  Under ``tp_axis``
        the interleaved layout is head-major (3·D contiguous rows per
        head), so a contiguous row slice of the in-projection IS a head
        block — decode shards heads exactly like the training path —
        and the returned H is the LOCAL head count."""
        attn = self.attn
        heads, d = attn.num_heads, attn.head_dim
        b, s_c, _ = x.shape
        h = self.ln1.forward(ctx, x)
        wi = ctx.value(attn.in_proj_weight)
        bi = ctx.value(attn.in_proj_bias) if attn.bias else None
        if self.tp_axis is not None:
            from ..parallel.tensor_parallel import (copy_to_tp_region,
                                                    _shard_rows)
            n = jax.lax.psum(1, self.tp_axis)
            if heads % n:
                raise ValueError(
                    f"tensor parallelism: heads ({heads}) not divisible "
                    f"by the '{self.tp_axis}' axis size ({n})")
            h = copy_to_tp_region(h, self.tp_axis)
            wi = _shard_rows(wi, self.tp_axis)
            if bi is not None:
                bi = _shard_rows(bi, self.tp_axis)
            heads //= n
        qkv = jnp.matmul(h, wi.T.astype(h.dtype))
        if bi is not None:
            qkv = qkv + bi.astype(qkv.dtype)
        qkv = qkv.reshape(b, s_c, heads, 3, d)
        to_bh = lambda y: jnp.swapaxes(y, 1, 2)       # (B, H, S_c, D)
        return (to_bh(qkv[:, :, :, 0]), to_bh(qkv[:, :, :, 1]),
                to_bh(qkv[:, :, :, 2]))

    def _attn_mlp_tail(self, ctx, x, o):
        """Shared residual tail after attention combine: out projection
        + GELU MLP (one body for prefill/decode_chunk/decode).  Under
        ``tp_axis`` ``o`` carries LOCAL head features: the out
        projection is row-parallel (its psum exits the attention
        region; the bias is added once, post-reduction) and the MLP is
        the column→row pair."""
        attn = self.attn
        wo = ctx.value(attn.out_proj_weight)
        bo = ctx.value(attn.out_proj_bias) if attn.bias else None
        if self.tp_axis is not None:
            from ..parallel.tensor_parallel import (row_parallel_linear,
                                                    _shard_cols)
            x = x + row_parallel_linear(
                o, _shard_cols(wo, self.tp_axis), bo, self.tp_axis)
        else:
            o = jnp.matmul(o, wo.T.astype(o.dtype))
            if attn.bias:
                o = o + bo.astype(o.dtype)
            x = x + o
        return x + self._ffn(ctx, self.ln2.forward(ctx, x))

    # -- the serve engine's layer protocol (serve/kernels.py) --------------

    @property
    def cache_rows(self):
        """What the block keeps of a token in a paged pool:
        ``(streams, heads, head_dim)`` — a K and a V row of all heads."""
        return 2, self.attn.num_heads, self.attn.head_dim

    def chunk_rows(self, ctx, x, positions):
        """``x (B, Q, E)`` at ``positions (B, Q)`` -> the queries ``(B,
        H, Q, D)`` and the rows to store, ``(B, Q, H*D)`` each.  The
        positions are unused: a learned position is added at the
        embedding."""
        q, k_new, v_new = self._chunk_qkv(ctx, x)
        b, h, s_q, d = k_new.shape
        rows = tuple(jnp.swapaxes(r, 1, 2).reshape(b, s_q, h * d)
                     for r in (k_new, v_new))
        return q, rows

    def read_decode(self, q, pool, layer, tables, positions, window):
        """One query row a session through the block table."""
        from ..kernels.paged_attention import paged_decode_attention
        return paged_decode_attention(q[:, :, 0], pool, layer, tables,
                                      positions, self.attn.scaling,
                                      window)[:, None]

    def read_chunk(self, q, pool, layer, tables, positions, window):
        """A chunk of query rows against a gathered view of the tables'
        blocks of one layer, in the pool's dtype."""
        from ..kernels.paged_attention import attend, gather_kv
        k, v = gather_kv(pool, layer, tables)
        return attend(q, k, v, positions, self.attn.scaling, window)

    def finish(self, ctx, x, o, live):
        """The block's output from its attention's, and what the block
        counted on the way over the ``live`` rows (nothing here)."""
        return self._attn_mlp_tail(ctx, x, o.astype(x.dtype)), None

    def prefill(self, ctx, x, kcache, vcache):
        """Cache-filling forward from position 0: flash causal attention
        over the chunk (the caches are empty) + KV writes — one pass for
        a whole prompt instead of S_p decode steps."""
        b, s_c, _ = x.shape
        d = self.attn.head_dim
        from ..inference.quant import kv_write
        q, k_new, v_new = self._chunk_qkv(ctx, x)     # H is LOCAL under tp
        kcache = kv_write(kcache, k_new, (0, 0, 0, 0))
        vcache = kv_write(vcache, v_new, (0, 0, 0, 0))
        from ..contrib.multihead_attn.attn_funcs import flash_attention
        o = flash_attention(q, k_new, v_new, causal=True,
                            scale=self.attn.scaling)
        o = jnp.swapaxes(o, 1, 2).reshape(b, s_c, q.shape[1] * d)
        return self._attn_mlp_tail(ctx, x, o), kcache, vcache

    def decode_chunk(self, ctx, x, kcache, vcache, t0):
        """Cached forward over a chunk ``x (B, S_c, E)`` at positions
        ``t0 ..`` — each query attends the cache with the shifted-causal
        mask.  Meant for SHORT verification windows (scores are
        (S_c, S_max) per head); prompts go through :meth:`prefill`."""
        attn = self.attn
        d = attn.head_dim
        b, s_c, _ = x.shape
        pos = t0 + jnp.arange(s_c, dtype=jnp.int32)
        from ..inference.quant import kv_value, kv_write
        q, k_new, v_new = self._chunk_qkv(ctx, x)     # H is LOCAL under tp
        if self.sp_axis is not None:
            # sequence-parallel decode: this device's cache block holds
            # positions sp_slot_positions(...); the chunk's KV rows land
            # on their owners, scores run against the LOCAL block only,
            # and the partials lse-merge over the axis
            # (parallel/context_parallel.py)
            from ..parallel.context_parallel import (
                sp_kv_write, sp_slot_positions, sp_softmax_combine)
            kcache = sp_kv_write(kcache, k_new, t0, self.sp_axis)
            vcache = sp_kv_write(vcache, v_new, t0, self.sp_axis)
            slots = sp_slot_positions(kcache.shape[2], self.sp_axis)
        else:
            kcache = kv_write(kcache, k_new, (0, 0, t0, 0))
            vcache = kv_write(vcache, v_new, (0, 0, t0, 0))
            slots = jnp.arange(kcache.shape[2], dtype=jnp.int32)
        scores = jnp.einsum("bhqd,bhsd->bhqs", q.astype(jnp.float32),
                            kv_value(kcache)) * attn.scaling
        # cache slots beyond each position are unwritten (or stale)
        valid = slots[None, :] <= pos[:, None]
        scores = jnp.where(valid[None, None, :, :], scores, -1e30)
        if self.sp_axis is not None:
            o = sp_softmax_combine(
                scores, self.sp_axis,
                lambda p: jnp.einsum("bhqs,bhsd->bhqd", p,
                                     kv_value(vcache))).astype(x.dtype)
        else:
            probs = jax.nn.softmax(scores, axis=-1)
            o = jnp.einsum("bhqs,bhsd->bhqd", probs,
                           kv_value(vcache)).astype(x.dtype)
        o = jnp.swapaxes(o, 1, 2).reshape(b, s_c, q.shape[1] * d)
        return self._attn_mlp_tail(ctx, x, o), kcache, vcache

    def decode(self, ctx, x, kcache, vcache, t):
        """One-token decode with a KV cache: ``x (B, E)`` at global
        position ``t`` (traced i32), caches ``(B, H, S_max, D)``.  The
        ``S_c = 1`` case of :meth:`decode_chunk` — one body, so the
        single-token and chunked programs cannot drift apart."""
        y, kcache, vcache = self.decode_chunk(
            ctx, x[:, None, :], kcache, vcache, t)
        return y[:, 0], kcache, vcache


class MoeGptBlock(GptBlock):
    """Pre-LN decoder block with a Switch-MoE feed-forward: LN → causal
    MHA → residual, LN → top-k routed expert FFN → residual.

    One expert per device along ``moe_axis`` (which the model typically
    shares with the data axis — experts then ride the same mesh dimension
    the batch shards over, the canonical Switch/GShard layout).  Expert
    weights are held STACKED and full-size ``(E, ...)`` on every device —
    same philosophy as the TP families: checkpoints are mesh-independent,
    each device slices its expert at trace time.  Their gradients are
    exact under the train step's psum-MEAN over the axis: device ``i``'s
    grad is nonzero only in its expert's slice and the global loss is the
    mean of per-device means, so mean-of-blocks IS the true gradient —
    no extra collectives needed (contrast parallel/tensor_parallel.py's
    f/g pair).

    The Switch load-balancing aux loss (weighted by ``aux_weight``) is
    recorded via ``Ctx.add_aux_loss``; ``make_train_step`` folds it into
    the optimized loss.  Tokens over capacity are dropped by the MoE —
    the residual connection carries them through unchanged.
    """

    def __init__(self, hidden, heads, intermediate, num_experts,
                 dropout=0.1, attn_dropout=0.1, sp_axis=None,
                 moe_axis="data", capacity_factor=1.25, top_k=1,
                 aux_weight=0.01):
        from ..nn.parameter import Parameter
        super().__init__(hidden, heads, intermediate, dropout,
                         attn_dropout, sp_axis=sp_axis, _dense_ffn=False)
        self.moe_axis = moe_axis
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.top_k = top_k
        self.aux_weight = aux_weight
        # router: (H, E), Switch init — small scale keeps early routing
        # near-uniform so the aux loss can act before collapse
        self.router = nn.Linear(hidden, num_experts, bias=False)
        self.router.weight.data = self.router.weight.data * 0.1
        # stacked per-expert FFN weights, nn.Linear layout (out, in) per
        # expert; drawn through throwaway Linears so each expert gets the
        # standard init distribution
        w1, b1, w2, b2 = [], [], [], []
        for _ in range(num_experts):
            l1 = nn.Linear(hidden, intermediate)
            l2 = nn.Linear(intermediate, hidden)
            w1.append(l1.weight.data)
            b1.append(l1.bias.data)
            w2.append(l2.weight.data)
            b2.append(l2.bias.data)
        self.w1 = Parameter(jnp.stack(w1))    # (E, I, H)
        self.b1 = Parameter(jnp.stack(b1))    # (E, I)
        self.w2 = Parameter(jnp.stack(w2))    # (E, H, I)
        self.b2 = Parameter(jnp.stack(b2))    # (E, H)

    def _ffn(self, ctx, h):
        """Routed expert mixture on the LN2 output (overrides the dense
        hook, so the training forward AND the cached decode paths route
        identically — tokens flatten over whatever leading layout the
        caller uses: (S, B, E) in forward, (B, S_c, E) in decode)."""
        from ..parallel.expert_parallel import switch_moe

        shape = h.shape
        toks = h.reshape(-1, shape[-1])
        i = jax.lax.axis_index(self.moe_axis)
        params = tuple(
            jax.lax.dynamic_index_in_dim(ctx.value(p), i, 0,
                                         keepdims=False)
            for p in (self.w1, self.b1, self.w2, self.b2))

        def expert_fn(params, xe):
            w1l, b1l, w2l, b2l = params
            hh = F.gelu(jnp.matmul(xe, w1l.T.astype(xe.dtype))
                        + b1l.astype(xe.dtype))
            return jnp.matmul(hh, w2l.T.astype(xe.dtype)) \
                + b2l.astype(xe.dtype)

        y, aux = switch_moe(toks, ctx.value(self.router.weight).T,
                            params, expert_fn, self.moe_axis,
                            capacity_factor=self.capacity_factor,
                            top_k=self.top_k)
        ctx.add_aux_loss(self.aux_weight * aux)
        return y.reshape(shape)

    def tp_sharded_params(self):
        return []    # MoE blocks carry no TP-sharded params


class GptModel(nn.Module):
    """Token+position embeddings → N pre-LN causal blocks → final LN →
    weight-tied LM head.  ``forward(input_ids[B,S]) -> logits (B,S,V)``."""

    def __init__(self, vocab_size=50257, hidden=768, layers=12, heads=12,
                 intermediate=None, max_positions=1024, dropout=0.1,
                 attn_dropout=0.1, remat=False, sp_axis=None, tp_axis=None,
                 tp_vocab=False, moe_axis=None, moe_num_experts=None,
                 moe_every=2, moe_capacity_factor=1.25, moe_top_k=1,
                 moe_aux_weight=0.01, attn_bias=False,
                 pad_vocab_multiple=None, output_hidden=False):
        super().__init__()
        intermediate = intermediate or 4 * hidden
        # pad_vocab_multiple: the Megatron --make-vocab-size-divisible-by
        # convention — the embedding table and tied head round the vocab
        # up to a lane-aligned multiple (GPT-2's 50257 is not).  logits
        # come back with padded width; pad columns are masked to -1e30,
        # so softmax / cross-entropy / argmax over them are EXACT w.r.t.
        # the logical vocab (labels never change).  That includes
        # label-smoothed losses THROUGH THIS PACKAGE — F.cross_entropy
        # and contrib.xentropy exclude <=-1e29-masked columns from the
        # smoothing term (mask-aware smoothing) — but a third-party
        # smoothed loss that spreads s/C over all columns would average
        # the -1e30 pads into the loss; slice logits[..., :vocab_size]
        # before such a loss.  Pad table rows are
        # never looked up and receive zero gradient through the masked
        # columns.  Measured on v5e (unledgered run, round 4): a WASH on
        # the GPT headlines (912 vs 921 seq/s at seq-128) — XLA pads
        # unaligned contraction dims internally — so this is a
        # divisibility/parity convenience (e.g. for tp sharding), not a
        # perf lever on this backend.
        self.vocab_size = vocab_size
        self.padded_vocab = vocab_size
        if pad_vocab_multiple:
            self.padded_vocab = -(-vocab_size // pad_vocab_multiple) \
                * pad_vocab_multiple
        if tp_vocab and self.padded_vocab != vocab_size:
            raise ValueError(
                "pad_vocab_multiple with tp_vocab is not supported: the "
                "vocab-parallel loss would see unmasked pad columns in "
                "the last shard")
        # attn_bias: QKV/out-proj biases on every block's attention (what
        # GPT-2 checkpoints carry — models/hf.py loads into this config);
        # selects the bias-capable 'default' attention impl per block
        if attn_bias and moe_axis is not None:
            raise ValueError(
                "attn_bias is not supported with moe_axis (MoE blocks "
                "are this framework's own architecture; imported "
                "checkpoints are dense)")
        self.hidden = hidden
        self.max_positions = max_positions
        # moe_axis: Switch-MoE — every ``moe_every``-th block (Switch's
        # every-other-layer default) swaps its dense FFN for a top-k
        # routed expert FFN with one expert per device along this mesh
        # axis (usually the data axis).  ``moe_num_experts`` must equal
        # that axis's size at run time (validated by switch_moe).
        self.moe_axis = moe_axis
        if moe_axis is not None:
            if moe_num_experts is None:
                raise ValueError(
                    "moe_axis requires moe_num_experts (= the mesh axis "
                    "size: one expert per device)")
            if tp_axis is not None:
                raise ValueError(
                    "moe_axis and tp_axis are mutually exclusive for now "
                    "(the MoE FFN replaces the dense FFN that TP shards)")
            if not 1 <= moe_every <= layers:
                raise ValueError(
                    f"moe_every={moe_every} with layers={layers}: must "
                    f"be in [1, layers] or no block would be MoE (block "
                    f"moe_every-1 is the first routed one)")
        # tp_axis: Megatron tensor parallelism — forward must run inside
        # shard_map over a mesh with this axis; attention heads and the
        # MLP hidden shard over it, embeddings/LNs/head stay replicated.
        # Composes with sp_axis (TP shards heads, SP shards time) and
        # with a data axis for 2-D/3-D meshes.
        self.tp_axis = tp_axis
        # attention dropout composes with tp_axis on the flash path:
        # each head-shard folds its axis index into the in-kernel mask
        # seed (attn_funcs._dropout_seed).  The 'default' impl
        # (attn_bias=True) cannot decorrelate — fail where the config
        # is written, not deep inside shard_map tracing
        if tp_axis is not None and attn_dropout > 0.0 and attn_bias:
            raise ValueError(
                "tp_axis with attn_dropout > 0 requires the flash impl; "
                "attn_bias=True selects the materializing 'default' "
                "impl, which draws from one shared key — set "
                "attn_dropout=0.0 or attn_bias=False")
        # tp_vocab: Megatron vocab parallelism — the tied embedding table
        # row-shards over tp_axis, the input lookup combines partial rows,
        # and forward returns VOCAB-SHARDED logits (B, S, V/n_tp): the
        # full logits tensor (the largest activation of an LM step) never
        # materializes.  Train with
        # parallel.vocab_parallel_cross_entropy(logits, targets, tp_axis)
        # as the loss.
        self.tp_vocab = tp_vocab
        if tp_vocab and tp_axis is None:
            raise ValueError("tp_vocab requires tp_axis")
        # output_hidden: training-time option — forward returns
        # (hidden, table) instead of logits so a chunked/fused loss can
        # own the vocab chain (see forward).  Decode paths apply the
        # head themselves and are unaffected.
        self.output_hidden = output_hidden
        if output_hidden and tp_vocab:
            raise ValueError(
                "output_hidden with tp_vocab is redundant: vocab-parallel "
                "logits already never materialize whole — use "
                "vocab_parallel_cross_entropy as the loss instead")
        # remat: rematerialize each block's activations in backward
        # (jax.checkpoint) — HBM drops from O(layers * S * E) residuals to
        # O(layers) block boundaries, the long-sequence enabler
        self.remat = remat
        # sp_axis: sequence parallelism — forward must run inside
        # shard_map with input_ids sharded on dim 1 over this mesh axis;
        # attention rides the ring (parallel/ring_attention.py), position
        # embeddings use global offsets, everything else is local.
        # max_positions caps the GLOBAL sequence length.  Composes with
        # remat for the long-context recipe.
        self.sp_axis = sp_axis
        # attention dropout composes with sp_axis: the ring hashes
        # GLOBAL coordinates under the replicated pre-shard key, so the
        # dropped positions are bit-identical to the unsharded run
        # (attn_funcs.self_attn_func; ulysses decorrelates per shard)
        self.tok_emb = nn.Embedding(self.padded_vocab, hidden)
        self.pos_emb = nn.Embedding(max_positions, hidden)
        # GPT initializer_range=0.02 (nn.Embedding draws std-1 normals; the
        # tied head would otherwise see logits of std ~sqrt(hidden))
        for emb in (self.tok_emb, self.pos_emb):
            emb.weight.data = emb.weight.data * 0.02
        self.drop = nn.Dropout(dropout)
        def _block(idx):
            if moe_axis is not None and idx % moe_every == moe_every - 1:
                return MoeGptBlock(
                    hidden, heads, intermediate, moe_num_experts,
                    dropout, attn_dropout, sp_axis=sp_axis,
                    moe_axis=moe_axis,
                    capacity_factor=moe_capacity_factor,
                    top_k=moe_top_k, aux_weight=moe_aux_weight)
            return GptBlock(hidden, heads, intermediate, dropout,
                            attn_dropout, sp_axis=sp_axis, tp_axis=tp_axis,
                            attn_bias=attn_bias)

        self.blocks = nn.ModuleList([_block(i) for i in range(layers)])
        self.ln_f = FusedLayerNorm(hidden)

    def tp_sharded_params(self):
        """All blocks' TP-block-sparse parameters (see GptBlock), plus
        the vocab-sharded embedding table under ``tp_vocab`` (its
        gradient is a scatter into the device's own vocab rows)."""
        ps = [p for blk in self.blocks for p in blk.tp_sharded_params()]
        if self.tp_vocab:
            ps.append(self.tok_emb.weight)
        return ps

    def forward(self, ctx, input_ids):
        b, s = input_ids.shape
        if self.sp_axis is not None:
            ctx = _fold_shard_into_key(ctx, self.sp_axis)
            # s is the LOCAL shard; global position = shard offset + local
            from ..compat import axis_size as _axis_size
            n = _axis_size(self.sp_axis)
            if s * n > self.max_positions:
                raise ValueError(
                    f"global sequence length {s * n} exceeds "
                    f"max_positions {self.max_positions}")
            off = jax.lax.axis_index(self.sp_axis) * s
            pos = (off + jnp.arange(s, dtype=jnp.int32))[None, :]
        elif s > self.max_positions:
            # jax gather clamps out-of-range indices, so oversized inputs
            # would silently reuse the last position embedding (torch
            # errors here)
            raise ValueError(
                f"sequence length {s} exceeds max_positions "
                f"{self.max_positions}")
        else:
            pos = jnp.arange(s, dtype=jnp.int32)[None, :]
        if self.tp_vocab:
            from ..parallel.tensor_parallel import vocab_parallel_embedding
            x = vocab_parallel_embedding(
                input_ids, ctx.value(self.tok_emb.weight), self.tp_axis) \
                + self.pos_emb.forward(ctx, pos)
        else:
            x = self.tok_emb.forward(ctx, input_ids) \
                + self.pos_emb.forward(ctx, pos)
        x = self.drop.forward(ctx, x)
        x = jnp.swapaxes(x, 0, 1)          # (S, B, E)
        for blk in self.blocks:
            if self.remat:
                x = nn.checkpoint_forward(blk, ctx, x)
            else:
                x = blk.forward(ctx, x)
        x = self.ln_f.forward(ctx, x)
        x = jnp.swapaxes(x, 0, 1)          # (B, S, E)
        emb = ctx.value(self.tok_emb.weight)
        if self.output_hidden:
            # head deferred to the loss: (hidden (B,S,E), table (V,E)) —
            # the chunked/fused vocab-chain losses (contrib.xentropy.
            # chunked_lm_head_loss, ops.pallas.fused_lm_head_xent) apply
            # the tied head themselves so (B,S,V) logits never have to
            # materialize whole
            return x, emb
        if self.tp_vocab:
            from ..parallel.tensor_parallel import vocab_parallel_logits
            return vocab_parallel_logits(x, emb, self.tp_axis)
        return self._mask_pad_logits(
            jnp.matmul(x, jnp.swapaxes(emb, 0, 1).astype(x.dtype)))


    def _mask_pad_logits(self, logits):
        """-1e30 on vocab-pad columns: softmax/argmax/cross-entropy over
        the padded width equal the logical-vocab results exactly."""
        if self.padded_vocab == self.vocab_size:
            return logits
        cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                        logits.ndim - 1)
        return jnp.where(cols < self.vocab_size, logits,
                         jnp.asarray(-1e30, logits.dtype))

    def init_caches(self, batch, s_max, dtype=jnp.float32):
        """Per-layer (k, v) caches of shape (B, H, S_max, D).  Under
        ``tp_axis`` H is the LOCAL head count (call inside shard_map —
        generate does): each device caches only its own head shard.
        Under ``sp_axis`` S is the LOCAL sequence block (ceil(S_max/n),
        rounded up so every position has an owner): per-device cache HBM
        shrinks with the mesh — the context-length scaling lever."""
        blk0 = self.blocks[0]
        h, d = blk0.attn.num_heads, blk0.attn.head_dim
        if self.tp_axis is not None:
            try:
                n = jax.lax.psum(1, self.tp_axis)   # static axis size
            except NameError:
                raise ValueError(
                    f"init_caches on a tp_axis='{self.tp_axis}' model "
                    f"must run inside shard_map over a mesh with that "
                    f"axis — generate(..., mesh=...) wraps the whole "
                    f"decode; direct callers must shard_map themselves"
                ) from None
            if h % n:
                raise ValueError(
                    f"init_caches: heads ({h}) must divide by the "
                    f"'{self.tp_axis}' axis size ({n})")
            h //= n
        if self.sp_axis is not None:
            from ..parallel.context_parallel import sp_axis_size
            s_max = -(-s_max // sp_axis_size(self.sp_axis))
        from ..inference.quant import make_kv_cache
        return [(make_kv_cache((batch, h, s_max, d), dtype),
                 make_kv_cache((batch, h, s_max, d), dtype))
                for _ in self.blocks]

    def _cache_capacity(self, caches):
        """Global position capacity of the caches (under ``sp_axis`` the
        per-device block times the axis size)."""
        cap = caches[0][0].shape[2]
        if self.sp_axis is not None:
            from ..parallel.context_parallel import sp_axis_size
            cap *= sp_axis_size(self.sp_axis)
        return cap

    def _decode_guard(self, what):
        """Cached decode supports single-shard, tensor-parallel
        (``tp_axis``), expert-parallel (``moe_axis``), and
        sequence-parallel (``sp_axis``) execution — the sharded flavors
        run inside shard_map (generate(mesh=...) wraps it): TP shards
        heads with psum-replicated logits; MoE keeps caches replicated
        and routes each decoded chunk through the training forward's
        all_to_all; SP shards the KV cache's TIME axis with lse-merged
        partial attention (parallel/context_parallel.py).  SP×TP
        composes (heads and time shard independently); SP×MoE does not
        (untested collective interleaving) — refuse loudly."""
        if self.sp_axis is not None and self.moe_axis is not None:
            raise NotImplementedError(
                f"{what}: sp_axis does not compose with moe_axis for "
                f"cached decode; build the model with one or the other "
                f"for inference")

    def _run_blocks(self, ctx, toks, caches, pos_of, blk_fn):
        """Embed ``toks`` + positions (``pos_of(pos_table)``), thread the
        caches through ``blk_fn`` per block, final-LN + tied head — the
        shared body of every cached decode entry point.  The token
        gather is int8-aware (only selected rows dequantize); the tied
        HEAD matmul still reads the full table, which ctx.value
        dequantizes fused into the matmul."""
        from ..inference.quant import gather_rows
        emb = ctx.value(self.tok_emb.weight)
        x = gather_rows(ctx, self.tok_emb.weight, toks) \
            + pos_of(ctx.value(self.pos_emb.weight))
        new_caches = []
        for blk, (kc, vc) in zip(self.blocks, caches):
            x, kc, vc = blk_fn(blk, x, kc, vc)
            new_caches.append((kc, vc))
        x = self.ln_f.forward(ctx, x)
        return self._mask_pad_logits(
            jnp.matmul(x, jnp.swapaxes(emb, 0, 1).astype(x.dtype))), \
            new_caches

    def prefill(self, ctx, toks, caches):
        """Consume a PROMPT ``toks (B, S_p)`` from position 0 in one
        flash-attention pass, filling the KV caches: returns
        ``(logits (B, S_p, V), new_caches)`` — O(1) calls instead of
        S_p decode steps.  Under ``sp_axis`` the prompt runs in cache-
        block-bounded chunks instead (cross-chunk attention rides the
        sharded cache; parallel/context_parallel.py)."""
        self._decode_guard("prefill")
        if self.sp_axis is not None:
            from ..parallel.context_parallel import sp_chunked_prefill
            return sp_chunked_prefill(self, ctx, toks, caches)
        s_p = toks.shape[1]
        return self._run_blocks(
            ctx, toks, caches, lambda pos: pos[:s_p][None, :, :],
            lambda blk, x, kc, vc: blk.prefill(ctx, x, kc, vc))

    def decode_chunk(self, ctx, toks, caches, t0):
        """Logits for a token CHUNK ``toks (B, S_c)`` at positions
        ``t0 ..`` against the caches (the speculative-verification
        primitive; same contract as LlamaModel.decode_chunk).

        ``t0 + S_c`` must be ``<= max_positions``: the position table is
        read with ``lax.dynamic_slice``, which CLAMPS an out-of-range
        start instead of failing — silently wrong position embeddings.
        A concrete (Python int) ``t0`` is checked here; traced callers
        (generate / speculative_generate) enforce the bound on the whole
        generation up front, so the clamp is unreachable through them."""
        self._decode_guard("decode_chunk")
        s_c = toks.shape[1]
        if not isinstance(t0, jax.core.Tracer):
            bound = min(self.max_positions, self._cache_capacity(caches))
            if int(t0) < 0 or int(t0) + s_c > bound:
                raise ValueError(
                    f"decode_chunk: positions {int(t0)}..{int(t0) + s_c} "
                    f"out of range for max_positions {self.max_positions} "
                    f"/ cache capacity {self._cache_capacity(caches)} — "
                    f"dynamic_slice would clamp and return wrong position "
                    f"embeddings / corrupt the cache")
        return self._run_blocks(
            ctx, toks, caches,
            lambda pos: jax.lax.dynamic_slice(
                pos, (t0, 0), (s_c, pos.shape[1]))[None, :, :],
            lambda blk, x, kc, vc: blk.decode_chunk(ctx, x, kc, vc, t0))

    def decode_step(self, ctx, tok, caches, t):
        """Logits for one token: ``tok (B,)`` ids at global position
        ``t`` (traced i32).  Returns ``(logits (B, V), new_caches)``."""
        self._decode_guard("decode_step")
        return self._run_blocks(
            ctx, tok, caches,
            lambda pos: jax.lax.dynamic_index_in_dim(pos, t,
                                                     keepdims=False),
            lambda blk, x, kc, vc: blk.decode(ctx, x, kc, vc, t))


def _sharded_decode_axes(model):
    """The mesh axes a model's decode needs: tp (head-sharded), moe
    (expert dispatch), and/or sp (time-sharded KV cache).  Callers run
    the model's own ``_decode_guard`` FIRST, so a composition a family
    refuses (sp×moe) never reaches the mesh demands here."""
    axes = []
    for attr in ("tp_axis", "moe_axis", "sp_axis"):
        ax = getattr(model, attr, None)
        if ax is not None:
            axes.append((attr, ax))
    return axes


def _check_decode_mesh(model, mesh, what="generate", who="model"):
    """Shared mesh validation for the decode drivers: a model with any
    sharded decode axis needs a mesh carrying ALL of them; a mesh with
    nothing to shard is a caller error.  ``who`` names the model in the
    errors (speculative decoding passes "target"/"draft" so a mismatch
    says which of its two models to fix).  Call the model's
    ``_decode_guard`` before this — an unsupported-composition refusal
    must win over a 'pass mesh=' demand."""
    axes = _sharded_decode_axes(model)
    if axes and mesh is None:
        names = ", ".join(f"{a}='{v}'" for a, v in axes)
        raise ValueError(
            f"{who} was built with {names}: decode runs inside "
            f"shard_map — pass {what}(..., mesh=<Mesh with the axes>)")
    if mesh is not None:
        for attr, ax in axes:
            if ax not in mesh.axis_names:
                raise ValueError(
                    f"mesh axes {mesh.axis_names} do not include "
                    f"{who}'s {attr} '{ax}'")


def nucleus_filter(logits, top_p):
    """Top-p (nucleus) logit filter, static shapes: keep the smallest
    prefix of the probability-sorted vocab whose cumulative probability
    reaches ``top_p`` (the first token always survives), set the rest
    to -1e30.  ``logits (..., V)``."""
    if top_p >= 1.0:
        # exact no-op: f32 cumsum rounding can push the tail's prefix
        # mass a few ulps past 1.0 and mask valid tokens otherwise
        return logits
    srt = jnp.sort(logits, axis=-1)[..., ::-1]          # descending
    probs = jax.nn.softmax(srt.astype(jnp.float32), axis=-1)
    # token i is OUTSIDE the nucleus iff the mass strictly before it
    # already reached top_p
    before = jnp.cumsum(probs, axis=-1) - probs
    kept = before < top_p                               # (..., V) sorted
    # per-row threshold = smallest kept logit
    thresh = jnp.min(jnp.where(kept, srt, jnp.inf), axis=-1,
                     keepdims=True).astype(logits.dtype)
    return jnp.where(logits < thresh, -1e30, logits)


def make_sampler(temperature, top_k, top_p, vocab):
    """Validate the sampling knobs and return ``sample(logits, key)``
    — ONE implementation of the greedy/temperature/top-k/top-p
    composition, shared by ``generate`` and ``inference.DecodeSession``
    so the two paths cannot drift."""
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if top_k is not None and not 1 <= top_k <= vocab:
        raise ValueError(
            f"top_k must be in [1, vocab={vocab}], got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")

    def sample(logits, k):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1)
        logits = logits / temperature
        if top_k is not None:
            kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
            logits = jnp.where(logits < kth, -1e30, logits)
        if top_p is not None:
            logits = nucleus_filter(logits, top_p)
        return jax.random.categorical(k, logits, axis=-1)

    return sample


def generate(model: GptModel, prompt_ids, max_new_tokens, temperature=0.0,
             top_k=None, key=None, cache_dtype=None, mesh=None,
             top_p=None):
    """Autoregressive sampling with a KV cache: models with the chunk
    protocol (GPT, Llama) consume the prompt in ONE ``model.prefill``
    flash pass, then generation runs a ``lax.scan`` of per-token decode
    steps; models without it run the whole sequence through the scan,
    teacher-forced inside the prompt.  Either way everything compiles
    into one jitted program, cached per model instance and config, so
    repeated calls pay compile once.

    ``prompt_ids (B, P)``; returns ``(B, P + max_new_tokens)``.
    ``temperature=0`` is greedy; ``top_k`` keeps the k highest logits
    and ``top_p`` the probability nucleus (applied after top_k, the
    usual composition); ``cache_dtype`` defaults to the token-embedding
    dtype (use
    ``jnp.bfloat16`` to halve cache HBM for fp32 checkpoints, or the
    string ``"int8"`` for a quantized KV cache — per-position absmax,
    half of bf16's traffic again; long-context decode re-reads the
    whole cache every token, so cache bytes are the lever there).  The
    reference has no inference path (it is a training-side library); this
    is the decode half of the GPT family.

    Tensor-parallel decode: a model built with ``tp_axis`` needs
    ``mesh`` (a ``jax.sharding.Mesh`` carrying that axis) — the whole
    decode program runs inside ``shard_map`` with weights, tokens, and
    the PRNG key replicated: each device projects only its own head
    blocks (KV caches are head-sharded, HBM/device shrinks with the
    mesh), the row-parallel psums make the logits replicated, and
    sampling therefore emits bit-identical tokens on every device —
    the output equals the single-shard decode of the same weights.

    Note on sampled reproducibility: the prefill fast path consumes ONE
    key split for the prompt where the legacy per-token path consumed
    ``P - 1``, so sampled (temperature > 0) streams differ from runs of
    this function before prefill existed (and from models without the
    chunk protocol).  Greedy output is unaffected.
    """
    from ..nn.modules import Ctx

    b, p = prompt_ids.shape
    s_total = p + max_new_tokens
    if s_total > model.max_positions:
        raise ValueError(
            f"prompt ({p}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_positions {model.max_positions}")
    if temperature > 0.0 and key is None:
        raise ValueError("sampling (temperature > 0) needs a PRNG key")
    if key is None:
        key = jax.random.PRNGKey(0)
    vocab = getattr(model, 'vocab_size', None) \
        or model.tok_emb.weight.shape[0]
    sample = make_sampler(temperature, top_k, top_p, vocab)
    # unsupported-composition refusal (sp) wins over mesh demands;
    # then validate the mesh against the sharded axes
    model._decode_guard("generate")
    _check_decode_mesh(model, mesh)
    if mesh is not None and not _sharded_decode_axes(model):
        raise ValueError(
            "mesh was passed but the model has no tp_axis/moe_axis/"
            "sp_axis — single-shard decode needs no mesh")

    params = [q for q in model.parameters()]
    buffers = list(model.buffers())
    vals = [q.data for q in params] + [bu.data for bu in buffers]
    if cache_dtype is None:
        cache_dtype = model.tok_emb.weight.data.dtype

    prompt_padded = jnp.concatenate(
        [prompt_ids, jnp.zeros((b, max_new_tokens), prompt_ids.dtype)],
        axis=1)

    # models exposing prefill (the GPT and Llama families; the dispatch
    # condition is the method itself) consume the whole prompt in ONE
    # flash-attention cached forward instead of p sequential decode
    # steps; max_new_tokens == 0 keeps the legacy path (the prefill
    # path's first sampled token would be unrequested)
    chunk_prefill = hasattr(model, "prefill") and p > 1 \
        and max_new_tokens >= 1

    def run(vals, prompt_padded, key):
        env = {id(o): v for o, v in zip(params + buffers, vals)}
        ctx = Ctx(env=env, stats_out={}, training=False)
        caches = model.init_caches(b, s_total, dtype=cache_dtype)

        def step(carry, t):
            tok, caches, key = carry
            logits, caches = model.decode_step(ctx, tok, caches, t)
            key, sub = jax.random.split(key)
            sampled = sample(logits, sub)
            # teacher-force inside the prompt, sample past it (the scan
            # covers t < s_total - 1, so t + 1 is always in bounds)
            nxt = jnp.where(t + 1 < p, prompt_padded[:, t + 1], sampled)
            return (nxt, caches, key), nxt

        if chunk_prefill:
            logits, caches = model.prefill(
                ctx, prompt_padded[:, :p], caches)
            key, sub = jax.random.split(key)
            first_new = sample(logits[:, -1], sub)
            (_, _, _), toks = jax.lax.scan(
                step, (first_new, caches, key),
                jnp.arange(p, s_total - 1))
            return jnp.concatenate(
                [prompt_padded[:, :p], first_new[:, None],
                 jnp.swapaxes(toks, 0, 1)], axis=1)

        (_, _, _), toks = jax.lax.scan(
            step, (prompt_padded[:, 0], caches, key),
            jnp.arange(s_total - 1))
        return jnp.concatenate(
            [prompt_padded[:, :1], jnp.swapaxes(toks, 0, 1)], axis=1)

    # per-model compiled-run cache (see utils/jit_cache.py for the
    # parameter-identity/LRU invariants — LoRA apply/merge must miss)
    from ..utils.jit_cache import compiled_run_cache

    def build():
        if mesh is not None:
            # everything replicated in and out; the TP sharding lives in
            # the trace-time head-block slices inside the blocks
            from jax.sharding import PartitionSpec as _P
            from ..compat import shard_map as _shard_map
            return jax.jit(_shard_map(
                run, mesh=mesh, in_specs=(_P(), _P(), _P()),
                out_specs=_P(), check_vma=False))
        return jax.jit(run)

    fn = compiled_run_cache(
        model, "_generate_jit_cache",
        (b, p, max_new_tokens, float(temperature), top_k,
         None if top_p is None else float(top_p),
         cache_dtype if isinstance(cache_dtype, str)
         else jnp.dtype(cache_dtype).name, mesh),
        params + buffers, build)
    return fn(vals, prompt_padded, key)


def gpt2_small(**kw):
    """GPT-2 small geometry: 12 layers, hidden 768, 12 heads (124M)."""
    return GptModel(**{**dict(hidden=768, layers=12, heads=12), **kw})


def gpt2_medium(**kw):
    """GPT-2 medium geometry: 24 layers, hidden 1024, 16 heads (350M)."""
    return GptModel(**{**dict(hidden=1024, layers=24, heads=16), **kw})


def gpt2_large(**kw):
    """GPT-2 large geometry: 36 layers, hidden 1280, 20 heads (774M)."""
    return GptModel(**{**dict(hidden=1280, layers=36, heads=20), **kw})


def gpt2_xl(**kw):
    """GPT-2 XL geometry: 48 layers, hidden 1600, 25 heads (1.5B)."""
    return GptModel(**{**dict(hidden=1600, layers=48, heads=25), **kw})
