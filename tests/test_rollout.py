"""apex_tpu.rollout — the generate-then-train loop (tier-1, CPU).

Pins the ISSUE-18 acceptance criteria: bitwise weight sync at every
publish epoch, draft accept-rate strictly improving over >= 3
distillation publishes, and chaos-kill resume matching the
uninterrupted loss trajectory — plus the buffer's staleness/
backpressure/replay contracts, the reshard per-leaf stats satellite,
and zero leaked pool blocks.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import apex_tpu.nn as nn
import apex_tpu.nn.functional as F
from apex_tpu.inference.draft import make_self_draft
from apex_tpu.models.gpt import GptModel
from apex_tpu.observe import registry as obs
from apex_tpu.observe.catalog import CATALOG
from apex_tpu.optimizers.fused_adam import FusedAdam
from apex_tpu.rollout import (OnlineDistiller, RolloutBuffer,
                              RolloutRuntime, RolloutSample,
                              WeightPublisher, master_leaves)
from apex_tpu.runtime import chaos
from apex_tpu.runtime import step_cache as sc
from apex_tpu.runtime.resilience import CheckpointManager, reshard_state
from apex_tpu.serve.engine import ServeEngine
from apex_tpu.serve.scheduler import Request
from apex_tpu.training.step import make_train_step

pytestmark = pytest.mark.rollout

V = 73


def _gpt(seed):
    nn.manual_seed(seed)
    return GptModel(vocab_size=V, hidden=32, layers=2, heads=4,
                    max_positions=96, dropout=0.0, attn_dropout=0.0)


def _lm_loss(logits, ids):
    flat = logits[:, :-1].reshape((-1, V))
    tgt = ids[:, 1:].reshape((-1,))
    return F.cross_entropy(flat, tgt)


def _train_step(model, lr=1e-3):
    opt = FusedAdam(list(model.parameters()), lr=lr)
    return make_train_step(model, opt, _lm_loss, loss_scale=1.0)


def _loop(*, distill=False, capacity=16, max_staleness=2,
          rollouts_per_round=4, train_batch=4, train_steps_per_round=2,
          publish_every=1, seed=0, num_blocks=64, **kw):
    """Fresh, fully seeded loop: train model, serve copy, engine,
    fused step, optional online distiller, runtime."""
    train_m = _gpt(6)
    serve_m = make_self_draft(train_m)
    draft = None
    if distill:
        draft_master = _gpt(99)
        draft = make_self_draft(draft_master)
    eng = ServeEngine(serve_m, num_blocks=num_blocks, block_size=8,
                      max_batch=4, prefill_chunk=4, draft=draft,
                      spec_k=4)
    step = _train_step(train_m)
    dist = OnlineDistiller(eng, draft_master, lr=1e-3) if distill \
        else None
    rt = RolloutRuntime(eng, step, capacity=capacity,
                        max_staleness=max_staleness,
                        rollouts_per_round=rollouts_per_round,
                        train_batch=train_batch,
                        train_steps_per_round=train_steps_per_round,
                        publish_every=publish_every,
                        prompt_len=6, max_new_tokens=6, seq_len=16,
                        distiller=dist, seed=seed, **kw)
    return eng, step, rt


# ---------------------------------------------------------------------------
# buffer: staleness, backpressure, seeded replay
# ---------------------------------------------------------------------------


def _sample(rid, epoch, n=12):
    toks = np.arange(n, dtype=np.int32) % V
    return RolloutSample(rid=rid, tokens=toks, prompt_len=4,
                         weight_epoch=epoch)


def test_buffer_staleness_eviction():
    buf = RolloutBuffer(8, max_staleness=2, seed=0)
    for i, ep in enumerate([0, 0, 1, 3, 4]):
        assert buf.push(_sample(f"s{i}", ep))
    # at epoch 4: ages are 4,4,3,1,0 -> the three older than bound leave
    assert buf.evict_stale(4) == 3
    assert len(buf) == 2
    assert buf.evicted == 3
    assert max(buf.ages(4)) <= 2
    # downweight policy never evicts; it weights instead
    dbuf = RolloutBuffer(8, max_staleness=1, staleness_policy="downweight",
                         downweight=0.5, seed=0)
    for i, ep in enumerate([0, 3]):
        dbuf.push(_sample(f"d{i}", ep))
    assert dbuf.evict_stale(3) == 0
    xs, w, ages = dbuf.sample_batch(8, 8, current_epoch=3)
    for wi, ai in zip(w, ages):
        assert wi == pytest.approx(0.5 ** max(ai - 1, 0))


def test_buffer_full_refuses_and_counts():
    buf = RolloutBuffer(2, seed=0)
    assert buf.push(_sample("a", 0)) and buf.push(_sample("b", 0))
    assert buf.free_slots == 0
    assert not buf.push(_sample("c", 0))
    assert buf.rejects == 1
    assert len(buf) == 2


def test_buffer_seeded_replay_and_checkpoint_roundtrip():
    def fill(buf):
        for i in range(6):
            buf.push(_sample(f"s{i}", i % 3, n=10 + i))
        return buf
    a = fill(RolloutBuffer(8, seed=7))
    b = fill(RolloutBuffer(8, seed=7))
    for _ in range(3):
        xa, _, _ = a.sample_batch(4, 8, current_epoch=3)
        xb, _, _ = b.sample_batch(4, 8, current_epoch=3)
        np.testing.assert_array_equal(xa, xb)
    # checkpoint mid-sequence: the restored buffer replays the exact
    # continuation the original produces
    sd = a.state_dict()
    cont_a = [a.sample_batch(4, 8, current_epoch=3)[0] for _ in range(3)]
    c = RolloutBuffer(8, seed=0).load_state_dict(sd)
    cont_c = [c.sample_batch(4, 8, current_epoch=3)[0] for _ in range(3)]
    for xa, xc in zip(cont_a, cont_c):
        np.testing.assert_array_equal(xa, xc)
    with pytest.raises(ValueError):
        RolloutBuffer(4, seed=0).load_state_dict(sd)  # capacity mismatch


# ---------------------------------------------------------------------------
# satellite: reshard_state per-leaf hit stats
# ---------------------------------------------------------------------------


def test_reshard_state_reports_per_leaf_stats():
    live = [jnp.arange(8, dtype=jnp.float32),
            jnp.ones((4, 4), jnp.float32)]
    tgt = [jnp.zeros(8, jnp.float32), jnp.zeros((4, 4), jnp.float32)]
    stats = {}
    out = reshard_state(live, tgt, stats_out=stats)
    # layout-identical live arrays ride the zero-copy fast path
    assert stats["leaves"] == 2 and stats["zero_copy"] == 2
    assert stats["copied"] == 0 and stats["bytes_moved"] == 0
    assert all(mode == "zero_copy" for _, mode in stats["per_leaf"])
    assert out[0] is live[0]
    # host sources pay the copy, and the bytes are priced
    host = [np.arange(8, dtype=np.float32), np.ones((4, 4), np.float32)]
    stats2 = {}
    reshard_state(host, tgt, stats_out=stats2)
    assert stats2["zero_copy"] == 0 and stats2["copied"] == 2
    assert stats2["bytes_moved"] == 8 * 4 + 16 * 4


def test_gathered_restore_surfaces_reshard_stats(tmp_path):
    m = _gpt(3)
    step = _train_step(m)
    step(jnp.zeros((2, 8), jnp.int32), jnp.zeros((2, 8), jnp.int32))
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(0, state=step.state)
    m2 = _gpt(3)
    step2 = _train_step(m2)
    with pytest.warns(UserWarning):
        mgr.restore_resharded(step2, step=0)
    stats = mgr.last_restore_stats
    assert stats["mode"] == "gathered"
    assert stats["copied_leaves"] > 0 and stats["zero_copy_leaves"] == 0
    assert stats["reshard_bytes_moved"] > 0


# ---------------------------------------------------------------------------
# weight publish: bitwise, versioned, recompile-free
# ---------------------------------------------------------------------------


def test_publish_bitwise_no_recompile_and_epoch_attribution():
    eng, step, rt = _loop()
    reqs = [Request(rid=f"w{i}", prompt=[1 + i, 2, 3, 4],
                    max_new_tokens=4) for i in range(3)]
    eng.run(reqs)                      # warm the bucketed programs
    compiles0 = sc.kind_stats("decode_step")["compiles"]
    for k in range(3):                 # three publish epochs, each pinned
        step(jnp.ones((2, 8), jnp.int32), jnp.ones((2, 8), jnp.int32))
        stats = rt.publisher.publish(master_leaves(step))
        assert stats["epoch"] == k + 1
        assert stats["zero_copy"] == stats["leaves"] > 0
        assert stats["bytes_moved"] == 0 and not stats["cast_dispatch"]
        for p, mv in zip(eng.model.parameters(), master_leaves(step)):
            np.testing.assert_array_equal(np.asarray(p.data),
                                          np.asarray(mv))
        # generation proceeds under the new weights without recompiling
        eng.run([Request(rid=f"w{k}b", prompt=[5, 6, 7],
                         max_new_tokens=4)])
        assert eng.result_meta[f"w{k}b"]["weight_epoch"] == k + 1
    assert sc.kind_stats("decode_step")["compiles"] == compiles0
    ev = obs.events("rollout.weight_sync")
    assert len(ev) >= 3 and ev[-1]["zero_copy_frac"] == 1.0
    eng.close()


def test_publish_casts_once_through_executor():
    train_m = _gpt(6)
    serve_m = make_self_draft(train_m)
    for p in serve_m.parameters():
        p.data = p.data.astype(jnp.bfloat16)
    eng = ServeEngine(serve_m, num_blocks=16, block_size=8)
    step = _train_step(train_m)
    step(jnp.ones((2, 8), jnp.int32), jnp.ones((2, 8), jnp.int32))
    d0 = sc.kind_stats("weight_publish")["dispatches"]
    pub = WeightPublisher(eng, which="target")
    stats = pub.publish(master_leaves(step))
    # one fused cast dispatch; published leaves == masters cast ONCE
    assert stats["cast_dispatch"]
    assert sc.kind_stats("weight_publish")["dispatches"] == d0 + 1
    for p, mv in zip(serve_m.parameters(), master_leaves(step)):
        np.testing.assert_array_equal(
            np.asarray(p.data), np.asarray(jnp.asarray(mv, jnp.bfloat16)))
    # dtype mismatch is rejected at the engine seam (cast is the
    # publisher's job, exactly once)
    with pytest.raises(ValueError):
        eng.publish_weights(master_leaves(step))
    eng.close()


# ---------------------------------------------------------------------------
# the loop: determinism, staleness, backpressure, leaks
# ---------------------------------------------------------------------------


def test_roundtrip_loss_trajectory_pinned():
    eng1, _, rt1 = _loop(seed=11)
    recs1 = rt1.run(4)
    eng1.close()
    eng2, _, rt2 = _loop(seed=11)
    rt2.run(4)
    eng2.close()
    # seeded end-to-end: two fresh loops replay the exact trajectory
    assert rt1.losses == rt2.losses
    assert len(rt1.losses) == 8 and all(np.isfinite(rt1.losses))
    assert rt1.losses[-1] < rt1.losses[0]          # it actually learns
    assert [r["weight_epoch"] for r in recs1] == [1, 2, 3, 4]


def test_staleness_bound_evicts_in_loop():
    eng, _, rt = _loop(max_staleness=0, capacity=32)
    recs = rt.run(4)
    eng.close()
    # publish bumps the epoch every round; epoch-0 samples must leave
    assert sum(r["evicted"] for r in recs) > 0
    assert rt.buffer.evicted > 0
    # the bound is enforced at round start: one more evict pass leaves
    # nothing over the bound (the final publish aged the tail samples
    # after the last round's evict already ran)
    ep = eng.weight_epochs["target"]
    rt.buffer.evict_stale(ep)
    assert all(a <= rt.buffer.max_staleness for a in rt.buffer.ages(ep))


def test_backpressure_throttles_generation_not_samples():
    # publishes never happen (no epoch growth -> no eviction), so the
    # buffer fills and the serve side must throttle
    eng, _, rt = _loop(capacity=6, publish_every=100,
                       rollouts_per_round=4)
    recs = rt.run(4)
    eng.close()
    assert rt.backpressure_rounds > 0
    assert any(r["submitted"] < rt.rollouts_per_round for r in recs)
    assert rt.buffer.rejects == 0      # reservation: never drop a rollout
    assert len(rt.buffer) <= rt.buffer.capacity


def test_zero_leaked_pool_blocks_after_loop():
    eng, _, rt = _loop(distill=True)
    rt.run(3)
    assert eng.block_pool.occupancy == 0
    eng.close()                         # asserts check_no_leaks


def test_rollout_metrics_are_cataloged():
    eng, _, rt = _loop(distill=True)
    rt.run(3)
    eng.close()
    snap = obs.get_registry().snapshot()
    seen = set()
    for kind in ("counters", "gauges", "histograms"):
        seen |= {n for n in snap[kind] if n.startswith("rollout.")}
    seen |= {e["event"] for e in obs.events()
             if e["event"].startswith("rollout.")}
    missing = {n for n in seen if n not in CATALOG}
    assert not missing, f"uncataloged rollout metrics: {missing}"


# ---------------------------------------------------------------------------
# acceptance pin 2: accept rate strictly improves across publishes
# ---------------------------------------------------------------------------


def test_accept_rate_strictly_improves_over_distill_publishes():
    train_m = _gpt(6)
    serve_m = make_self_draft(train_m)
    draft_master = _gpt(99)            # random-init draft: near-0 accept
    eng = ServeEngine(serve_m, num_blocks=64, block_size=8, max_batch=4,
                      prefill_chunk=4, draft=make_self_draft(draft_master),
                      spec_k=4)
    dist = OnlineDistiller(eng, draft_master, lr=1e-3)
    rng = np.random.default_rng(0)
    trace = [[int(t) for t in rng.integers(0, V, size=6)]
             for _ in range(6)]

    def accept_on_trace(tag):
        m0 = eng.metrics()["spec"]
        res = eng.run([Request(rid=f"{tag}.{i}", prompt=p,
                               max_new_tokens=10)
                       for i, p in enumerate(trace)])
        m1 = eng.metrics()["spec"]
        d_off = m1["offered"] - m0["offered"]
        assert d_off > 0
        rate = (m1["accepted"] - m0["accepted"]) / d_off
        # full sequences (prompt + generated continuation) are the
        # on-policy distillation data: the draft must learn the
        # target's behaviour where acceptance is actually measured —
        # off-policy random tokens converge to the target's (weakly
        # input-dependent) modal prediction in a handful of steps and
        # then plateau, so gains would not spread across publishes
        seqs = [np.asarray(p + list(res[f"{tag}.{i}"]), np.int32)
                for i, p in enumerate(trace)]
        return rate, np.stack([np.resize(s, 16) for s in seqs])

    rate0, xs = accept_on_trace("base")
    rates = [rate0]
    for k in range(3):                 # >= 3 distillation publishes
        for _ in range(10):
            dist.train_on(xs)
        dist.publish(accept_rate=rates[-1])
        rate, xs = accept_on_trace(f"pub{k}")
        rates.append(rate)
    assert all(b > a for a, b in zip(rates, rates[1:])), rates
    assert len(dist.publish_log) == 3
    assert [r["epoch"] for r in dist.publish_log] == [1, 2, 3]
    eng.close()


# ---------------------------------------------------------------------------
# acceptance pin 3: chaos resume == uninterrupted trajectory
# ---------------------------------------------------------------------------


def test_resume_equals_uninterrupted_under_train_kill(tmp_path):
    rounds = 6
    eng_u, _, rt_u = _loop(distill=True, seed=5)
    rt_u.run(rounds)
    eng_u.close()
    ref = rt_u.losses
    assert len(ref) == rounds * 2

    mgr = CheckpointManager(str(tmp_path / "ck"))
    eng_i, _, rt_i = _loop(distill=True, seed=5)
    with chaos.session(seed=0) as c:
        # the train.step hook fires for target AND distill steps (3 per
        # round); index 9 is round 3's first target step — mid-round,
        # after three checkpointed round boundaries
        c.on("train.step", action="kill", at=(9,))
        with pytest.raises(chaos.ChaosKilled):
            rt_i.run(rounds, manager=mgr, save_every=1)
    eng_i.close()
    assert mgr.latest_step() == 3

    eng_r, _, rt_r = _loop(distill=True, seed=5)
    resumed_at = rt_r.restore(mgr)
    assert resumed_at == 3 and rt_r.round == 3
    assert rt_r.losses == ref[:6]      # the checkpointed prefix matches
    rt_r.run(rounds - rt_r.round)
    eng_r.close()
    # the FULL trajectory is bitwise the uninterrupted one
    assert rt_r.losses == ref
    assert rt_r.engine.weight_epochs == rt_u.engine.weight_epochs
