"""The latent-attention mixture of experts with a residual of four streams
(``models/latent_moe.py`` at ``hc_mult`` 4) on the serve path, at a small
size on the CPU, float32, seeded random weights.

The yardstick is the benchmark's plain reference of the architecture
(``perfbench/pb/reference_latent_moe_hc.py``: the streams, the mapping
and its Sinkhorn iterations written out, expanded attention, every held
expert for every token, no cache), which imports nothing of the program;
the program's parameters carry the reference's leaf names, so one set of
arrays feeds both.  Logits are compared, never sampled tokens.
"""
import json
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

from apex_tpu.kernels import hyper_connection as hc  # noqa: E402
from apex_tpu.kernels.dispatch import force_mode  # noqa: E402
from apex_tpu.serve import kernels as sk  # noqa: E402
from apex_tpu.serve.pool import NULL_BLOCK, init_pool_buffer  # noqa: E402

FAMILY = cells.family_module("latent_moe_hc")
CONFIG = os.path.join(REPO, "perfbench", "configs", "xing4.0-29b-a4b-l5.json")
BS, NUM_BLOCKS, NB, CHUNK = 4, 48, 16, 8

# float32 against float32: what differs is the order of summation
# (absorbed against expanded attention, sorted pairs against a masked
# sum over experts, the mapping's product in two bfloat16 pieces of a
# float32 row against a float32 Phi at HIGHEST, sums down a tile against
# sums along an axis): 2.4e-7 here.  Streams kept in bfloat16 read
# 1.6e-3, 10 Sinkhorn iterations 1.8e-3 and 5 iterations 1.2e-2
# (test_the_tolerance_sees_a_broken_mix)
TOL = 2e-5


def _tiny_cfg():
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(FAMILY.tiny(cfg))
    return cfg


def _leaves(cfg, seed=3):
    """The family's leaves, each ``phi`` widened so that a logit of the
    mapping spreads as at the published widths (0.02 sqrt(14336) = 2.4;
    the tiny streams of 512 alone give 0.45): there 20 Sinkhorn
    iterations are not yet 10."""
    leaves = FAMILY.draw(cfg, jax.random.PRNGKey(seed), jnp.float32)
    wide = np.sqrt(4 * 3584 / (cfg["hc_mult"] * cfg["hidden_size"]))
    return {k: v * wide if k.endswith(".phi") else v
            for k, v in leaves.items()}


@pytest.fixture(scope="module")
def served():
    """``(cfg, model, leaves)``: the family's tiny model with seeded
    leaves in it, and the leaves by name for the reference."""
    cfg = _tiny_cfg()
    leaves = _leaves(cfg)
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
        self.params = list(model.parameters())
        self.vals = [p.data for p in self.params]
        streams, heads, head_dim = model.blocks[0].cache_rows
        self.pool = init_pool_buffer(len(model.blocks), heads, head_dim,
                                     NUM_BLOCKS, BS, jnp.float32,
                                     streams=streams)
        self.prefill = jax.jit(sk.build_prefill_fn(
            model, self.params, BS, NUM_BLOCKS))
        self.decode = jax.jit(sk.build_decode_fn(
            model, self.params, BS, NUM_BLOCKS))

    @staticmethod
    def table(ids):
        return ids + [NULL_BLOCK] * (NB - len(ids))

    def ingest(self, toks, ids):
        for a in range(0, len(toks), CHUNK):
            part = toks[a:a + CHUNK]
            last, self.pool, _ = self.prefill(
                self.vals, self.pool,
                jnp.asarray([part + [0] * (CHUNK - len(part))], jnp.int32),
                jnp.asarray([self.table(ids)], jnp.int32),
                jnp.int32(a), jnp.int32(len(part)))
        return np.asarray(last[0], np.float32)

    def step(self, rows):
        toks = [r[0] if r else 0 for r in rows]
        pos = [r[1] if r else -1 for r in rows]
        tabs = [self.table(r[2]) if r else [NULL_BLOCK] * NB for r in rows]
        _, logits, self.pool, _ = self.decode(
            self.vals, self.pool, jnp.asarray(toks, jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(tabs, jnp.int32))
        return np.asarray(logits, np.float32)


def _toks(seed, n, vocab):
    return [int(t) for t in np.random.default_rng(seed).integers(1, vocab, n)]


def _prefill_then_decode(model, cfg):
    """Two sessions: ``a`` prefills 19 tokens in three chunks (the last
    padded) and decodes on beside ``b``, which joins after 7 of its own.
    Returns ``[(served logits, reference position, session)]``."""
    vocab = cfg["vocab_size"]
    pg = _Paged(model)
    a, b = _toks(1, 26, vocab), _toks(2, 12, vocab)
    ids_a, ids_b = list(range(1, 9)), list(range(20, 26))
    out = [(pg.ingest(a[:19], ids_a), 18, "a")]
    for t in range(19, 21):
        out.append((pg.step([None, (a[t], t, ids_a)])[1], t, "a"))
    out.append((pg.ingest(b[:7], ids_b), 6, "b"))
    for k in range(5):
        lg = pg.step([(b[7 + k], 7 + k, ids_b), (a[21 + k], 21 + k, ids_a)])
        out += [(lg[0], 7 + k, "b"), (lg[1], 21 + k, "a")]
    return out, {"a": a, "b": b}


def _widest_gap(served, tier="xla"):
    cfg, model, leaves = served
    mode = {"xla": "off", "pallas_interpret": "interpret"}[tier]
    with force_mode(mode):
        got, toks = _prefill_then_decode(model, cfg)
    ref = {s: _reference_logits(cfg, leaves, t) for s, t in toks.items()}
    return max(float(np.max(np.abs(lg - ref[s][t]))) for lg, t, s in got)


@pytest.mark.parametrize("tier", ["xla", "pallas_interpret"])
def test_prefill_and_decode_logits_match_the_reference_forward(served,
                                                               tier):
    assert _widest_gap(served, tier) < TOL


def _rounded_post(plain):
    def post(x, y, mix, *, n):
        return plain(x, y, mix, n=n).astype(jnp.bfloat16).astype(
            jnp.float32)
    return post


@pytest.mark.parametrize("fault", ["bf16_streams", "5_iterations",
                                   "10_iterations"])
def test_the_tolerance_sees_a_broken_mix(served, fault, monkeypatch):
    """The same comparison with the streams rounded to bfloat16 after
    every mix, or with 5 or 10 Sinkhorn iterations in place of 20: each
    reads wider than ``TOL`` (the chip's ``correct`` passes the first
    two: the cell's ``not_held_by_correct``)."""
    _, model, _ = served
    if fault == "bf16_streams":
        monkeypatch.setattr(hc, "hyper_conn_post",
                            _rounded_post(hc.hyper_conn_post))
    else:
        for blk in model.blocks:
            for sub in (blk.hc_attn, blk.hc_ffn):
                monkeypatch.setattr(sub, "iters", int(fault.split("_")[0]))
    assert _widest_gap(served) > 10 * TOL


def test_the_mapping_matters(served):
    """At the draw the configuration states, ``H_res`` is far from the
    identity and ``H_pre`` / ``H_post`` are far from uniform: a broken
    mix cannot hide behind a mapping that does nothing."""
    cfg, model, leaves = served
    plain = FAMILY.draw(cfg, jax.random.PRNGKey(3), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0),
                          (64, cfg["hc_mult"] * cfg["hidden_size"]))
    for ls in (plain, leaves):
        p = "blocks.1.hc_attn."
        _, mix = hc.hyper_conn_pre(x, ls[p + "phi"], ls[p + "alpha"],
                                   ls[p + "bias"], n=4, iters=20, eps=1e-6,
                                   clamp=30.0)
        h_pre, h_post, h_res = hc.unpack(mix, 4)
        assert float(jnp.mean(jnp.abs(h_res - jnp.eye(4)))) > 0.1
        assert float(jnp.std(h_pre)) > 0.05 and float(jnp.std(h_post)) > 0.1


def test_the_engine_serves_it_through_submit_and_step(served):
    """The normal path (scheduler, block pool, prefix cache, step cache),
    greedy tokens against the reference's own, and the streams' counters
    on every decode tick's record."""
    from apex_tpu import observe
    from apex_tpu.serve import Request, ServeEngine
    cfg, model, leaves = served
    vocab = cfg["vocab_size"]
    eng = ServeEngine(model, num_blocks=64, block_size=4, max_batch=2,
                      prefill_chunk=8)
    shared = _toks(7, 12, vocab)
    prompts = {"a": shared + _toks(8, 3, vocab),
               "b": shared + _toks(9, 2, vocab)}
    began = time.perf_counter_ns()
    # b comes once a is served: its shared blocks are in the prefix cache
    out = {r: eng.run([Request(r, p, 4)])[r] for r, p in prompts.items()}
    for rid, p in prompts.items():
        ref = _reference_logits(cfg, leaves, list(p) + out[rid][:-1])
        for i, tok in enumerate(out[rid]):
            lg = ref[len(p) - 1 + i]
            top = np.sort(lg)[-2:]
            assert tok == int(np.argmax(lg)) or top[1] - top[0] < 1e-4
    assert eng.metrics()["prefix_cache"]["prefill_tokens_saved"] > 0
    steps = [e for e in observe.events("span")
             if e.get("span") == "serve.step" and e["t0_ns"] >= began
             and e.get("decode_batch")]
    assert model.stream_sublayers() == 6
    assert steps and all(
        e["hc_rows"] == e["decode_batch"] and e["hc_sublayers"] == 6
        for e in steps)
    eng.close()


def test_one_stream_adds_no_parameter_and_no_operation():
    """``hc_mult`` 1 (the GigaChat-shaped model): the leaves are the
    family's without streams, and neither serve program holds a mapping,
    a mix or a hyper_connection kernel."""
    from apex_tpu.observe import registry as obs
    plain = cells.family_module("latent_moe")
    with open(os.path.join(REPO, "perfbench", "configs",
                           "gigachat3.1-702b-a36b-ep16.json")) as f:
        cfg = json.load(f)
    cfg.update(plain.tiny(cfg))
    model = plain.model(cfg)
    assert model.hc_mult == 1 and model.stream_sublayers() is None
    assert [n for n, _ in model.named_parameters()] \
        == plain.program_leaf_names(cfg)
    params = list(model.parameters())
    vals = [p.data for p in params]            # shapes, no values
    streams, heads, head_dim = model.blocks[0].cache_rows
    pool = init_pool_buffer(len(model.blocks), heads, head_dim, 16, BS,
                            jnp.float32, streams=streams)

    def tiers():
        return [obs.counter(f"kernels.dispatch.hyper_connection.{t}").value
                for t in ("pallas", "xla")]
    before = tiers()
    i32 = jnp.int32
    decode = jax.make_jaxpr(sk.build_decode_fn(model, params, BS, 16))(
        vals, pool, jnp.zeros(2, i32), jnp.zeros(2, i32),
        jnp.zeros((2, 4), i32))
    prefill = jax.make_jaxpr(sk.build_prefill_fn(model, params, BS, 16))(
        vals, pool, jnp.zeros((1, 8), i32), jnp.zeros((1, 4), i32),
        i32(0), i32(8))
    for jaxpr in (decode, prefill):
        text = str(jaxpr)
        assert "hyper_conn" not in text and "sinkhorn" not in text
    assert tiers() == before
