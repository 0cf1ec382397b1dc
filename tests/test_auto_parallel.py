"""``make_train_step(parallel=...)`` end to end: plan-parity with the
hand-specified knobs (the planner only drives tested primitives), the
step-cache 1-compile/1-dispatch-per-window invariant under a plan,
memory-model validation against XLA's memory_analysis, measured
refinement (auto_tune), and the zero-stage-0 pure-DP path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import apex_tpu.nn as nn
from apex_tpu.nn import functional as F
from apex_tpu.optimizers import FusedAdam
from apex_tpu.parallel import auto
from apex_tpu.runtime import step_cache
from apex_tpu.training import make_train_step

V, S = 128, 16


def _gpt2_small_shaped(**kw):
    """GPT-2-small architecture at test scale (same topology: learned
    positions, pre-LN blocks, tied LM head; hidden/layers shrunk so the
    8-CPU-device suite stays fast)."""
    from apex_tpu.models import GptModel
    nn.manual_seed(11)
    return GptModel(**{**dict(vocab_size=V, hidden=32, layers=2, heads=4,
                              max_positions=S, dropout=0.0,
                              attn_dropout=0.0), **kw})


def _lm_batch(b=16):
    rng = np.random.default_rng(3)
    ids = jnp.asarray(rng.integers(0, V, (b, S)))
    return ids, jnp.asarray(np.roll(np.asarray(ids), -1, axis=1))


def _lm_loss(logits, tgt):
    return F.cross_entropy(logits.reshape((-1, V)), tgt.reshape((-1,)))


def _mlp():
    nn.manual_seed(0)
    model = nn.Sequential(nn.Linear(64, 512), nn.ReLU(),
                          nn.Linear(512, 512), nn.ReLU(),
                          nn.Linear(512, 8))
    opt = FusedAdam(list(model.parameters()), lr=1e-2)
    return model, opt


def _mlp_batch(b=64):
    rng = np.random.default_rng(1)
    return (jnp.asarray(rng.standard_normal((b, 64)), jnp.float32),
            jnp.asarray(rng.integers(0, 8, (b,))))


def test_auto_plan_parity_gpt2_small():
    """Acceptance: the planner's top plan under a memory cap trains the
    GPT-2-small-shaped model with loss parity to the SAME plan spelled
    out by hand through the explicit knobs, and step_cache.stats() pins
    1 compile + 1 dispatch per window."""
    ids, tgt = _lm_batch()
    m = _gpt2_small_shaped(hidden=64)
    opt = FusedAdam(list(m.parameters()), lr=1e-2)
    n_params = sum(int(np.prod(p.shape)) for p in m.parameters())
    # replicated state needs >= 20 bytes/param (masters 4 + Adam slots 8
    # + grad working set 8); 10 bytes/param admits only sharded plans
    cap = n_params * 10

    step_cache.reset_stats()
    step = make_train_step(m, opt, _lm_loss, half_dtype=None,
                           loss_scale=1.0, parallel="auto",
                           example_batch=(ids, tgt),
                           plan_options=dict(hbm_cap_bytes=cap))
    plan = step.plan
    assert plan.dp > 1 and plan.zero_stage >= 1
    assert step.plan_report is not None
    assert any("memory-infeasible" in r
               for _, r in step.plan_report.rejected)
    losses = [float(step(ids, tgt)) for _ in range(6)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    st = step_cache.stats()["by_kind"]["zero_train_step"]
    assert st["compiles"] == 1
    assert st["dispatches"] == 6        # one dispatch per window

    # the same plan, spelled out by hand through the explicit knobs
    m2 = _gpt2_small_shaped(hidden=64)
    opt2 = FusedAdam(list(m2.parameters()), lr=1e-2)
    kw = plan.step_kwargs(jax.devices())
    assert kw["zero_sharding"] and kw["zero_stage"] == plan.zero_stage
    hand = make_train_step(m2, opt2, _lm_loss, half_dtype=None,
                           loss_scale=1.0, **kw)
    hand_losses = [float(hand(ids, tgt)) for _ in range(6)]
    np.testing.assert_allclose(losses, hand_losses, rtol=1e-6, atol=1e-7)


def test_auto_plan_accum_window_dispatch():
    """A plan carrying K>1 keeps the one-executable window invariant:
    dispatches count windows, not microbatches."""
    x, y = _mlp_batch(b=32)
    model, opt = _mlp()
    plan = auto.Plan(dp=2, zero_stage=1, accum=4, n_devices=8)
    step_cache.reset_stats()
    step = make_train_step(model, opt, _loss_ce, half_dtype=None,
                           loss_scale=1.0, parallel=plan)
    for _ in range(3):
        loss = step(x, y)
    assert np.isfinite(float(loss))
    st = step_cache.stats()["by_kind"]["zero_train_step"]
    assert st["compiles"] == 1 and st["dispatches"] == 3


def _loss_ce(o, t):
    return F.cross_entropy(o, t)


def test_explicit_tp_plan_matches_unsharded_oracle():
    """parallel=Plan(dp=2, tp=4) drives the tested shard_map path: the
    per-step (global-mean) losses track the single-device oracle, and
    the wrapped program registers in the step cache under the plan."""
    ids, tgt = _lm_batch(b=8)

    m = _gpt2_small_shaped(tp_axis="tp")
    opt = FusedAdam(list(m.parameters()), lr=1e-2)
    plan = auto.Plan(dp=2, tp=4, tp_axis="tp", n_devices=8)
    step_cache.reset_stats()
    step = make_train_step(m, opt, _lm_loss, half_dtype=None,
                           loss_scale=1.0, parallel=plan)
    tp_losses = [float(step(ids, tgt)) for _ in range(4)]
    st = step_cache.stats()["by_kind"]["train_step"]
    assert st["compiles"] == 1 and st["dispatches"] == 4

    m2 = _gpt2_small_shaped()
    opt2 = FusedAdam(list(m2.parameters()), lr=1e-2)
    ref = make_train_step(m2, opt2, _lm_loss, half_dtype=None,
                          loss_scale=1.0)
    ref_losses = [float(ref(ids, tgt)) for _ in range(4)]
    np.testing.assert_allclose(tp_losses, ref_losses, rtol=3e-3,
                               atol=3e-3)
    assert tp_losses[-1] < tp_losses[0]


def test_zero_stage0_pure_dp_matches_single_device():
    """zero_stage=0 (what a dp-only zero=0 plan threads): replicated
    state, sharded batch — same losses as the plain jitted step."""
    x, y = _mlp_batch()
    model, opt = _mlp()
    ref = make_train_step(model, opt, _loss_ce, half_dtype=None,
                          loss_scale=1.0)
    ref_losses = [float(ref(x, y)) for _ in range(5)]

    model2, opt2 = _mlp()
    s0 = make_train_step(model2, opt2, _loss_ce, half_dtype=None,
                         loss_scale=1.0, zero_sharding=True, zero_stage=0)
    dp_losses = [float(s0(x, y)) for _ in range(5)]
    np.testing.assert_allclose(dp_losses, ref_losses, rtol=1e-5,
                               atol=1e-6)
    assert all(v.sharding.is_fully_replicated
               for v in s0.state.master_params)


@pytest.mark.parametrize("plan", [
    auto.Plan(dp=1, n_devices=8),
    auto.Plan(dp=1, accum=4, n_devices=8),
    auto.Plan(dp=8, zero_stage=0, n_devices=8),
    auto.Plan(dp=8, zero_stage=1, n_devices=8),
], ids=lambda p: p.name())
def test_memory_model_within_15pct_of_xla(plan):
    """Satellite acceptance: predicted per-device HBM within 15% of
    jax.jit(...).lower().compile().memory_analysis() for known configs
    (prediction extrapolates from probes at two SMALL batch sizes — it
    never sees the validated program)."""
    x, y = _mlp_batch()
    B = int(x.shape[0])
    model, opt = _mlp()
    prof = auto.profile_model(model, opt, _loss_ce,
                              (x[:8], y[:8]))      # probe at b=4/8
    predicted, _ = auto.predict_memory(plan, prof, auto.chip_spec(), B)

    m, o = _mlp()
    # donate_state=True: the HBM model prices the donated steady state
    # (the tpu/gpu production configuration); the default "auto" turns
    # donation off on this cpu backend, which would add the un-aliased
    # output buffers to XLA's measured footprint
    step = make_train_step(m, o, _loss_ce, half_dtype=None,
                           loss_scale=1.0, parallel=plan,
                           donate_state=True)
    step(x, y)
    if plan.dp > 1:
        shs = step._batch_shardings((x, y))
        comp = step._jitted(shs).lower(step.state, x, y).compile()
    else:
        ent = [e for e in step_cache.step_cache.entries()
               if e["kind"] == "train_step"][-1]
        comp = ent["fn"].lower(*ent["example"]).compile()
    measured = auto.measured_step_memory(comp)
    assert measured > 0
    assert abs(predicted - measured) / measured < 0.15, \
        (plan.name(), predicted, measured)


def test_auto_tune_reranks_by_measurement():
    """auto_tune=k compiles and times the top-k predicted plans through
    the real step and re-ranks by measurement."""
    x, y = _mlp_batch(b=32)
    model, opt = _mlp()
    step = make_train_step(model, opt, _loss_ce, half_dtype=None,
                           loss_scale=1.0, parallel="auto",
                           example_batch=(x, y), auto_tune=2)
    assert step.plan.measured_ms is not None
    measured = [p for p in step.plan_report.ranked
                if p.measured_ms is not None]
    assert len(measured) >= 2
    assert measured == sorted(measured, key=lambda p: p.measured_ms)
    assert np.isfinite(float(step(x, y)))


def test_parallel_owns_the_knobs():
    model, opt = _mlp()
    x, y = _mlp_batch(b=8)
    with pytest.raises(ValueError, match="owns the parallelism knobs"):
        make_train_step(model, opt, _loss_ce, parallel="auto",
                        example_batch=(x, y), axis_name="data")
    with pytest.raises(ValueError, match="owns gradient accumulation"):
        make_train_step(model, opt, _loss_ce, parallel="auto",
                        example_batch=(x, y), accum_steps=2)
    with pytest.raises(ValueError, match="example_batch"):
        make_train_step(model, opt, _loss_ce, parallel="auto")
    with pytest.raises(ValueError, match="'auto'"):
        make_train_step(model, opt, _loss_ce, parallel="fastest",
                        example_batch=(x, y))


def test_plan_capability_errors_at_apply():
    """A hand-built plan the model cannot run fails loudly at build, not
    deep inside tracing."""
    model, opt = _mlp()
    with pytest.raises(ValueError, match="without tp_axis"):
        make_train_step(model, opt, _loss_ce,
                        parallel=auto.Plan(dp=2, tp=4, tp_axis="tp",
                                           n_devices=8))
    with pytest.raises(ValueError, match="without sp_axis"):
        make_train_step(model, opt, _loss_ce,
                        parallel=auto.Plan(dp=4, sp=2, sp_axis="sp",
                                           n_devices=8))


def test_infeasible_everything_raises_with_report():
    model, opt = _mlp()
    x, y = _mlp_batch(b=8)
    with pytest.raises(RuntimeError, match="no feasible plan"):
        make_train_step(model, opt, _loss_ce, parallel="auto",
                        example_batch=(x, y),
                        plan_options=dict(hbm_cap_bytes=1024))


def test_abstract_example_batch():
    """example_batch may be ShapeDtypeStructs — nothing executes during
    planning (pure host-side lowering)."""
    model, opt = _mlp()
    x, y = _mlp_batch(b=16)
    ex = (jax.ShapeDtypeStruct(x.shape, x.dtype),
          jax.ShapeDtypeStruct(y.shape, y.dtype))
    step = make_train_step(model, opt, _loss_ce, half_dtype=None,
                           loss_scale=1.0, parallel="auto",
                           example_batch=ex)
    assert step.plan is not None
    assert np.isfinite(float(step(x, y)))
