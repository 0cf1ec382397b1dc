"""The latent-attention mixture-of-experts family on the serve path, at a
small size on the CPU, float32, seeded random weights.

The yardstick is the benchmark's plain reference of the architecture
(``perfbench/pb/reference_latent_moe.py``: expanded attention, every
held expert computed for every token, no cache), which imports nothing
of the program; the program's parameters carry the reference's leaf
names, so one set of arrays feeds both.  Logits are compared, never
sampled tokens.
"""
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

from pb import cells  # noqa: E402

from apex_tpu import nn  # noqa: E402
from apex_tpu.kernels import grouped_matmul as gm  # noqa: E402
from apex_tpu.kernels import latent_attention as la  # noqa: E402
from apex_tpu.kernels.dispatch import force_mode  # noqa: E402
from apex_tpu.models.latent_moe import (LatentMoeModel, yarn_inv_freq,  # noqa: E402
                                        yarn_softmax_scale)
from apex_tpu.nn.modules import Ctx  # noqa: E402
from apex_tpu.parallel.routed_experts import (RoutedExperts,  # noqa: E402
                                              group_limited_route)
from apex_tpu.serve import kernels as sk  # noqa: E402
from apex_tpu.serve.pool import NULL_BLOCK, init_pool_buffer  # noqa: E402

FAMILY = cells.family_module("latent_moe")
CONFIG = os.path.join(REPO, "perfbench", "configs",
                      "gigachat3.1-702b-a36b-ep16.json")
BS, NUM_BLOCKS, NB, CHUNK = 4, 48, 16, 8

# float32 against float32: what differs is the order of summation
# (absorbed against expanded attention, sorted pairs against a masked
# sum over experts): 2e-5 on logits of size ~1, as the GPT path's test
TOL = 2e-5


def _tiny_cfg(**over):
    import json
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(FAMILY.tiny(cfg))
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def served():
    """``(cfg, model, leaves)``: the family's tiny model with seeded
    leaves in it, and the leaves by name for the reference."""
    cfg = _tiny_cfg()
    leaves = FAMILY.draw(cfg, jax.random.PRNGKey(3), jnp.float32)
    model = FAMILY.model(cfg)
    for (name, p), leaf in zip(model.named_parameters(),
                               FAMILY.to_program(cfg)(leaves)):
        assert tuple(p.shape) == tuple(leaf.shape), name
        p.data = leaf
    model.eval()
    return cfg, model, leaves


def _reference_logits(cfg, leaves, toks):
    ref = cells._module_from(
        os.path.join(REPO, cfg["reference"]), "reference")
    lg, _ = ref.logits(cfg, leaves, jnp.asarray([toks], jnp.int32))
    return np.asarray(lg[0], np.float32)


class _Paged:
    """The serve programs driven by hand: block tables chosen by the
    test, logits kept."""

    def __init__(self, model):
        self.params = list(model.parameters()) + list(model.buffers())
        self.vals = [p.data for p in self.params]
        streams, heads, head_dim = model.blocks[0].cache_rows
        self.pool = init_pool_buffer(len(model.blocks), heads, head_dim,
                                     NUM_BLOCKS, BS, jnp.float32,
                                     streams=streams)
        self.prefill = jax.jit(sk.build_prefill_fn(
            model, self.params, BS, NUM_BLOCKS))
        self.decode = jax.jit(sk.build_decode_fn(
            model, self.params, BS, NUM_BLOCKS))
        self.copy = jax.jit(sk.build_block_copy_fn())
        self.counted = []

    @staticmethod
    def table(ids):
        return ids + [NULL_BLOCK] * (NB - len(ids))

    def ingest(self, toks, t0, ids):
        last = None
        for a in range(0, len(toks), CHUNK):
            part = toks[a:a + CHUNK]
            last, self.pool, n = self.prefill(
                self.vals, self.pool,
                jnp.asarray([part + [0] * (CHUNK - len(part))], jnp.int32),
                jnp.asarray([self.table(ids)], jnp.int32),
                jnp.int32(t0 + a), jnp.int32(len(part)))
            self.counted.append((len(part), np.asarray(n)))
        return np.asarray(last[0], np.float32)

    def step(self, rows):
        toks = [r[0] if r else 0 for r in rows]
        pos = [r[1] if r else -1 for r in rows]
        tabs = [self.table(r[2]) if r else [NULL_BLOCK] * NB for r in rows]
        _, logits, self.pool, n = self.decode(
            self.vals, self.pool, jnp.asarray(toks, jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(tabs, jnp.int32))
        self.counted.append((sum(r is not None for r in rows),
                             np.asarray(n)))
        return np.asarray(logits, np.float32)


def _toks(seed, n, vocab):
    return [int(t) for t in np.random.default_rng(seed).integers(1, vocab, n)]


@pytest.mark.parametrize("case", ["chunks_then_decode", "join_and_leave",
                                  "prefix_hit", "copy_on_write"])
def test_paged_latent_logits_match_the_reference_forward(served, case):
    cfg, model, leaves = served
    vocab = cfg["vocab_size"]
    pg = _Paged(model)
    a = _toks(1, 30, vocab)
    ref_a = _reference_logits(cfg, leaves, a)
    ids_a = list(range(1, 9))
    if case == "chunks_then_decode":
        # 19 tokens in three chunks (the last one padded), then decode
        last = pg.ingest(a[:19], 0, ids_a)
        np.testing.assert_allclose(last, ref_a[18], atol=TOL)
        for t in range(19, 30):
            lg = pg.step([(a[t], t, ids_a), None])
            np.testing.assert_allclose(lg[0], ref_a[t], atol=TOL)
    elif case == "join_and_leave":
        b = _toks(2, 22, vocab)
        ref_b = _reference_logits(cfg, leaves, b)
        ids_b = list(range(20, 26))
        pg.ingest(a[:10], 0, ids_a)
        for t in range(10, 14):          # a alone, in slot 1
            lg = pg.step([None, (a[t], t, ids_a)])
            np.testing.assert_allclose(lg[1], ref_a[t], atol=TOL)
        pg.ingest(b[:7], 0, ids_b)       # b joins, in slot 0
        for k in range(8):
            lg = pg.step([(b[7 + k], 7 + k, ids_b),
                          (a[14 + k], 14 + k, ids_a)])
            np.testing.assert_allclose(lg[0], ref_b[7 + k], atol=TOL)
            np.testing.assert_allclose(lg[1], ref_a[14 + k], atol=TOL)
        for t in range(15, 22):          # a has left; b decodes on
            lg = pg.step([(b[t], t, ids_b), None])
            np.testing.assert_allclose(lg[0], ref_b[t], atol=TOL)
    elif case == "prefix_hit":
        # b shares a's first 12 tokens (three full blocks): it adopts
        # a's blocks and prefills its own suffix only, from position 12
        pg.ingest(a[:16], 0, ids_a)
        b = a[:12] + _toks(4, 12, vocab)
        ref_b = _reference_logits(cfg, leaves, b)
        ids_b = ids_a[:3] + [30, 31, 32]
        last = pg.ingest(b[12:20], 12, ids_b)
        np.testing.assert_allclose(last, ref_b[19], atol=TOL)
        for t in range(20, 24):
            lg = pg.step([(b[t], t, ids_b), (a[t - 4], t - 4, ids_a)])
            np.testing.assert_allclose(lg[0], ref_b[t], atol=TOL)
            np.testing.assert_allclose(lg[1], ref_a[t - 4], atol=TOL)
    else:
        # b shares 14 tokens with a: the fourth block (rows 12..15) is
        # partly shared, so b forks it (a copy of the block) and writes
        # its own rows 14.. into the copy; a's block is untouched
        pg.ingest(a[:16], 0, ids_a)
        b = a[:14] + _toks(5, 10, vocab)
        ref_b = _reference_logits(cfg, leaves, b)
        pg.pool = pg.copy(pg.pool, jnp.int32(ids_a[3]), jnp.int32(40))
        ids_b = ids_a[:3] + [40, 41, 42]
        last = pg.ingest(b[14:20], 14, ids_b)
        np.testing.assert_allclose(last, ref_b[19], atol=TOL)
        for t in range(20, 24):
            lg = pg.step([(a[t - 4], t - 4, ids_a), (b[t], t, ids_b)])
            np.testing.assert_allclose(lg[0], ref_a[t - 4], atol=TOL)
            np.testing.assert_allclose(lg[1], ref_b[t], atol=TOL)
    # every program reported the pairs its live rows sent to held
    # experts: (routed layers, held), never more than the rows could send
    for rows, n in pg.counted:
        assert n.shape == (2, 2) and n.dtype == np.int32
        assert 0 <= n.sum() <= rows * 2 * 2


def test_the_engine_serves_it_through_submit_and_step(served):
    """The normal path (scheduler, block pool, prefix cache, step cache),
    greedy tokens against the reference's own: a float32 model's argmax
    is the reference's wherever the margin is not a rounding."""
    from apex_tpu import observe
    from apex_tpu.serve import Request, ServeEngine
    cfg, model, leaves = served
    vocab = cfg["vocab_size"]
    eng = ServeEngine(model, num_blocks=64, block_size=4, max_batch=4,
                      prefill_chunk=8)
    shared = _toks(7, 12, vocab)
    prompts = {"a": shared + _toks(8, 5, vocab),
               "b": shared + _toks(9, 9, vocab), "c": _toks(10, 6, vocab)}
    began = time.perf_counter_ns()      # other engines' ticks lie before
    out = eng.run([Request(r, p, 6) for r, p in prompts.items()])
    for rid, p in prompts.items():
        seq = list(p)
        for tok in out[rid]:
            lg = _reference_logits(cfg, leaves, seq)[-1]
            top = np.sort(lg)[-2:]
            assert tok == int(np.argmax(lg)) or top[1] - top[0] < 1e-4
            seq.append(tok)
    assert eng.metrics()["prefix_cache"]["prefill_tokens_saved"] > 0
    steps = [e for e in observe.events("span")
             if e.get("span") == "serve.step" and "moe_pairs" in e
             and e["t0_ns"] >= began]
    assert steps and all(
        e["moe_layers"] == 2 and e["moe_held"] == 2
        and e["moe_pairs_max"] <= e["moe_pairs"]
        and e["moe_experts_hit"] <= e["moe_pairs"] for e in steps)
    eng.close()


# -- attention ---------------------------------------------------------------


def _small_model(**kw):
    nn.manual_seed(11)
    args = dict(q_rank=24, kv_rank=128, nope_dim=16, rope_dim=32, v_dim=24,
                dense_intermediate=64, expert_intermediate=16, n_experts=8,
                top_k=2, n_group=4, topk_group=2, first_dense=1,
                max_positions=256,
                rope=dict(rope_theta=1e4, factor=8.0, beta_fast=32,
                          beta_slow=1, mscale=1, mscale_all_dim=1,
                          original_max_position_embeddings=32))
    args.update(kw)
    m = LatentMoeModel(61, 64, 2, 8, **args)
    m.eval()
    return m


@pytest.mark.parametrize("tier", ["xla", "pallas_interpret"])
def test_absorbed_decode_is_expanded_attention(tier):
    """One new token a session, read through the block table in absorbed
    form, against the expanded attention of the whole sequence: the same
    mathematics in another order of summation (1e-5 in float32)."""
    m = _small_model()
    blk, attn = m.blocks[0], m.blocks[0].attn
    assert blk.cache_rows == (1, 1, 256) and attn.kv_rank == 128
    ctx = Ctx(training=False)
    rng = np.random.default_rng(0)
    depths = [37, 5]
    s_max = max(depths) + 1
    h = jnp.asarray(rng.standard_normal((2, s_max, 64)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s_max)[None], (2, s_max))
    want = attn.forward(ctx, h, pos)                       # expanded
    # the cache as prefill leaves it, 16 rows a block
    _, rows = attn.absorbed(ctx, h, pos)
    nb = 4
    pool = jnp.zeros((1, 1, 1 + 2 * nb, 16, 256), jnp.float32)
    tables = np.zeros((2, nb), np.int32)
    for b, d in enumerate(depths):
        for j in range(d // 16 + 1):
            tables[b, j] = 1 + b * nb + j
            pool = pool.at[0, 0, tables[b, j]].set(
                jnp.pad(rows[b, 16 * j:16 * j + 16],
                        ((0, max(0, 16 * j + 16 - s_max)), (0, 0))))
    last = jnp.asarray(depths)
    q, _ = attn.absorbed(ctx, h[jnp.arange(2), last][:, None],
                         last[:, None])
    with force_mode("interpret" if tier == "pallas_interpret" else "off"):
        o = la.latent_decode_attention(
            q[:, :, 0], pool, 0, jnp.asarray(tables), last, attn.scaling,
            attn.kv_rank)
    got = attn.output(ctx, o[:, None])[:, 0]
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want[jnp.arange(2), last]),
                               atol=1e-5)


def test_yarn_tables_against_a_hand_computation():
    """The published block: theta 1e5, factor 64, 4096 original
    positions, beta 32 and 1, 64 rotary dimensions.  A pair i turns
    4096 * theta_i / 2 pi times over the original context: more than 32
    times below i = 8.38, fewer than once above i = 18.01, so pairs
    0..8 keep theta_i, pairs 19.. take theta_i / 64, and pair i between
    blends with ramp (i - 8) / 11."""
    inv = yarn_inv_freq(64, 1e5, 64.0, 4096, 32, 1)
    theta = lambda i: 1e5 ** (-2.0 * i / 64)              # noqa: E731
    assert inv.shape == (32,)
    np.testing.assert_allclose(inv[:9], [theta(i) for i in range(9)],
                               rtol=1e-12)
    np.testing.assert_allclose(inv[19:], [theta(i) / 64 for i in
                                          range(19, 32)], rtol=1e-12)
    r = 5.0 / 11.0
    np.testing.assert_allclose(
        inv[13], theta(13) * (1 - r) + theta(13) / 64 * r, rtol=1e-12)
    rope = dict(rope_theta=1e5, factor=64, mscale=1, mscale_all_dim=1,
                original_max_position_embeddings=4096, beta_fast=32,
                beta_slow=1)
    m = 0.1 * math.log(64) + 1
    assert m == pytest.approx(1.4159, abs=1e-4)
    assert yarn_softmax_scale(192, rope) == pytest.approx(
        192 ** -0.5 * m * m, rel=1e-12)


# -- the router and the experts ------------------------------------------------


def test_the_router_against_a_hand_worked_example():
    """8 experts in 4 groups of 2, 2 groups kept, 2 experts chosen.
    Logits (2.0, -1.0 | 0.5, 0.4 | 1.0, 0.9 | -2, -2), bias 0.3 on
    experts 2 and 3.  Group scores (sums of both biased sigmoids):
    1.150, 1.821, 1.442, 0.238: groups 1 and 2 are kept, and group 0 is
    out although expert 0 has the best score of all.  Of experts 2..5
    the best two by biased score are 2 (0.922) and 3 (0.899); their
    weights are the UNBIASED sigmoids 0.6225 and 0.5987 over their sum,
    times 2.5."""
    logits = jnp.asarray([[2.0, -1.0, 0.5, 0.4, 1.0, 0.9, -2.0, -2.0]])
    bias = jnp.asarray([0, 0, 0.3, 0.3, 0, 0, 0, 0], jnp.float32)
    experts, w = group_limited_route(
        logits, jnp.eye(8), bias, n_group=4, topk_group=2, top_k=2,
        norm_topk=True, scale=2.5)
    assert experts.tolist() == [[2, 3]]
    s2, s3 = 1 / (1 + math.exp(-0.5)), 1 / (1 + math.exp(-0.4))
    np.testing.assert_allclose(
        np.asarray(w), [[2.5 * s2 / (s2 + s3), 2.5 * s3 / (s2 + s3)]],
        rtol=1e-6)


def _layer_cfg(held):
    return _tiny_cfg(experts_held=list(held), n_routed_experts=len(held))


def _reference_layer(cfg, leaves, x):
    """The reference's feed-forward of one routed layer: its held
    experts' part and the shared expert."""
    ref = cells._module_from(
        os.path.join(REPO, cfg["reference"]), "reference")
    p = "blocks.1."
    routed, _ = ref._routed(cfg, leaves, p + "experts.", x, None)
    shared = ref._gated(x, leaves[p + "w_in"], leaves[p + "w_out"], None)
    return np.asarray(routed), np.asarray(shared)


def _program_layer(cfg, leaves, x):
    """The program's routed experts of the same layer with ``cfg``'s
    share -> ``(y, pairs)``."""
    m = cfg["moe_intermediate_size"]
    mod = RoutedExperts(
        cfg["hidden_size"], m, cfg["router_experts"],
        cfg["num_experts_per_tok"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"], scale=cfg["routed_scaling_factor"],
        norm_topk=cfg["norm_topk_prob"], experts_held=cfg["experts_held"])
    for name in ("router", "router_bias", "w_in", "w_out"):
        getattr(mod, name).data = leaves["blocks.1.experts." + name]
    y, pairs = mod.forward(Ctx(training=False), x)
    return np.asarray(y), np.asarray(pairs)


def _softmax_shares_add_up():
    """The softmax branch of the same layer (``score="softmax"``: no
    bias, no groups, no shared expert): 8 experts, 2 a token, against
    the uncut module and the grouped-query family's reference."""
    rng = np.random.default_rng(8)
    leaves = {"router": rng.standard_normal((8, 64)) / 8,
              "w_in": rng.standard_normal((8, 64, 32)) / 8,
              "w_out": rng.standard_normal((8, 16, 64)) / 4}
    leaves = {k: jnp.asarray(v, jnp.float32) for k, v in leaves.items()}
    x = jnp.asarray(rng.standard_normal((40, 64)), jnp.float32)

    def part(held):
        mod = RoutedExperts(64, 16, 8, 2, experts_held=held, score="softmax")
        assert mod.router_bias is None
        mod.router.data = leaves["router"]
        mod.w_in.data = leaves["w_in"][jnp.asarray(held)]
        mod.w_out.data = leaves["w_out"][jnp.asarray(held)]
        y, pairs = mod.forward(Ctx(training=False), x)
        return np.asarray(y), int(np.asarray(pairs).sum())
    whole, n = part(list(range(8)))
    assert n == 40 * 2
    shares = [part([e]) for e in range(8)]
    assert sum(n for _, n in shares) == 40 * 2          # every pair, once
    np.testing.assert_allclose(sum(y for y, _ in shares), whole, atol=2e-5)
    ref = cells._module_from(os.path.join(
        REPO, "perfbench", "pb", "reference_gqa_moe.py"), "reference")
    cfg = {"num_experts": 8, "num_experts_per_tok": 2,
           "norm_topk_prob": True, "moe_intermediate_size": 16}
    want, _ = ref._experts(cfg, {"e." + k: v for k, v in leaves.items()},
                           "e.", x, None)
    np.testing.assert_allclose(whole, np.asarray(want), atol=2e-5)


def _ungated_shares_add_up():
    """The layer whose experts are not gated (``gated=False,
    act="relu2"``: one input matrix an expert, kept ``(out, in)``;
    sigmoid scores with a bias and a scale, no groups): the parts that
    the four shares of ``experts_held`` give (4 of 16 experts each), the
    shared expert counted once, against the state-space hybrid family's
    reference for the uncut layer."""
    family = cells.family_module("hybrid_ssm_moe")
    with open(os.path.join(REPO, "perfbench", "configs",
                           "nemotron3-nano-30b-a3b-ep4-l13.json")) as f:
        base = json.load(f)
    base.update(family.tiny(base))

    def cfg_of(held):
        return dict(base, router_experts=16, experts_held=list(held),
                    n_routed_experts=len(held), num_experts_per_tok=3)
    whole = cfg_of(range(16))
    leaves = family.draw(whole, jax.random.PRNGKey(5), jnp.float32)
    x = jnp.asarray(np.random.default_rng(6).standard_normal((40, 64)),
                    jnp.float32)
    ref = cells._module_from(os.path.join(REPO, whole["reference"]),
                             "reference")
    p = "blocks.1."                                     # an E layer
    want, _ = ref._experts(whole, leaves, p + "experts.", x, None)
    shared = ref._relu2(x, leaves[p + "w_in"], leaves[p + "w_out"], None)
    total, pairs = np.zeros_like(want), 0
    for first in range(0, 16, 4):
        share = cfg_of(range(first, first + 4))
        # a share's draw is a slice of the whole model's draw
        drawn = family.draw(share, jax.random.PRNGKey(5), jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(drawn[p + "experts.w_in"]),
            np.asarray(leaves[p + "experts.w_in"][first:first + 4]))
        mod = RoutedExperts(
            64, share["moe_intermediate_size"], 16, 3,
            scale=share["routed_scaling_factor"],
            experts_held=share["experts_held"], gated=False, act="relu2")
        assert mod.w_in.shape == mod.w_out.shape == (4, 24, 64)
        for name in ("router", "router_bias", "w_in", "w_out"):
            getattr(mod, name).data = drawn[p + "experts." + name]
        y, n = mod.forward(Ctx(training=False), x)
        # ... and is what the reference gives for the same share
        np.testing.assert_allclose(
            np.asarray(y) + np.asarray(shared),
            np.asarray(ref._experts(share, drawn, p + "experts.", x,
                                    None)[0]), atol=1e-5)
        total += np.asarray(y)
        pairs += int(np.asarray(n).sum())
    assert pairs == 40 * 3                              # every pair, once
    np.testing.assert_allclose(total + np.asarray(shared), np.asarray(want),
                               atol=2e-5)


@pytest.mark.parametrize("score", ["sigmoid", "softmax", "relu2"])
def test_the_shares_add_up_to_the_uncut_layer(score):
    """Guide ``model-configs``, section 4: the routed parts that all 16
    shares give (one expert each here), with the shared expert counted
    once, add up to what the uncut reference gives for the whole
    layer; the softmax router's layer, and the layer whose experts are
    not gated, likewise."""
    if score == "softmax":
        return _softmax_shares_add_up()
    if score == "relu2":
        return _ungated_shares_add_up()
    whole = _layer_cfg(range(16))
    leaves = FAMILY.draw(whole, jax.random.PRNGKey(5), jnp.float32)
    x = jnp.asarray(np.random.default_rng(6).standard_normal((40, 64)),
                    jnp.float32)
    routed, shared = _reference_layer(whole, leaves, x)
    total = np.zeros_like(routed)
    pairs = 0
    for e in range(16):
        share = _layer_cfg([e])
        mine = dict(leaves)
        for name in ("w_in", "w_out"):
            mine["blocks.1.experts." + name] = \
                leaves["blocks.1.experts." + name][e:e + 1]
        # a share's draw is a slice of the whole model's draw
        drawn = FAMILY.draw(share, jax.random.PRNGKey(5), jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(drawn["blocks.1.experts.w_in"]),
            np.asarray(mine["blocks.1.experts.w_in"]))
        y, n = _program_layer(share, mine, x)
        # ... and is what the reference gives for the same share
        np.testing.assert_allclose(
            y, _reference_layer(share, mine, x)[0], atol=1e-5)
        total += y
        pairs += int(n.sum())
    assert pairs == 40 * whole["num_experts_per_tok"]   # every pair, once
    np.testing.assert_allclose(total + shared, routed + shared, atol=2e-5)


@pytest.mark.parametrize("tier", ["xla", "pallas_interpret"])
def test_no_token_is_dropped_under_a_skewed_router(tier):
    """A bias that sends every token to the same two held experts: each
    gets all 200 tokens (no capacity), and the result is the plain
    weighted sum."""
    nn.manual_seed(2)
    e, wi, t = 128, 128, 200
    mod = RoutedExperts(e, wi, 8, 2, n_group=2, topk_group=1, scale=1.5,
                        experts_held=(1, 2, 5))
    mod.router_bias.data = jnp.asarray([0, 9, 9, 0, 0, 0, 0, 0],
                                       jnp.float32)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((t, e)),
                    jnp.float32)
    ctx = Ctx(training=False)
    with force_mode("interpret" if tier == "pallas_interpret" else "off"):
        y, pairs = mod.forward(ctx, x)
    assert pairs.tolist() == [t, t, 0]
    experts, w = mod.route(ctx, x)
    assert sorted(experts[0].tolist()) == [1, 2]
    want = np.zeros((t, e), np.float32)
    for j, eid in enumerate((1, 2)):
        col = np.asarray(jnp.sum(jnp.where(experts == eid, w, 0.0), axis=1))
        gu = np.asarray(x) @ np.asarray(mod.w_in.data[j])
        h = gu[:, :wi] / (1 + np.exp(-gu[:, :wi])) * gu[:, wi:]
        want += col[:, None] * (h @ np.asarray(mod.w_out.data[j]))
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("groups", [[0, 0, 3, 1, 4, 4, 0, 1, 4, 4],
                                    [4] * 10, [2] * 10],
                         ids=["ragged", "none_held", "one_group"])
def test_grouped_matmul_tiers_agree(groups):
    """The tile-aligned layout: each held group starts at a tile, tiles
    past the last one in use are skipped, and the kernel (interpret
    mode) gives what ``ragged_dot`` gives on the rows that hold a
    pair."""
    g, k, n = 4, 256, 128
    group = jnp.asarray(groups, jnp.int32)
    rng = np.random.default_rng(0)
    rhs = jnp.asarray(rng.standard_normal((g, k, n)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((len(groups), k)), jnp.float32)
    with force_mode("interpret"):
        lay = gm.tile_layout(group, g, len(groups), gm.TILE_ROWS)
        lhs = x[jnp.maximum(lay.pair_of_row, 0)]
        got = gm.grouped_matmul(lhs, rhs, lay)
    held = [i for i, gi in enumerate(groups) if gi < g]
    assert int(lay.sizes.sum()) == len(held)
    assert int(lay.n_active) == len({groups[i] for i in held})
    rows = np.asarray(lay.row_of_pair)
    assert (rows[[i for i in range(len(groups)) if i not in held]]
            == -1).all()
    for i in held:
        assert int(lay.pair_of_row[rows[i]]) == i
        np.testing.assert_allclose(
            np.asarray(got[rows[i]]),
            np.asarray(x[i]) @ np.asarray(rhs[groups[i]]), atol=2e-4,
            rtol=2e-4)
