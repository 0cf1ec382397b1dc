"""Planner v3: the joint pp × remat × offload × ep search.

Pins the ISSUE-19 contracts:
  * ``plan_from_key(static_plan_key(p)) == p`` over randomized plans
    including every new axis, and unknown segments are a clear error;
  * a toy deep-GPT profile where every dp×tp×zero-only plan predicts
    OOM on a v5e still gets a feasible pp×remat plan from the joint
    search, under a wall-clock budget on CPU;
  * heterogeneous fleets pipeline with stages apportioned via
    ``apportion_shares``, the slowest member's stage time bounds the
    step, and ``describe()`` names the per-member placement;
  * ``describe()`` for a pp×remat×ep plan reports bubble fraction,
    recompute FLOPs, offload bytes, and per-stage HBM.
"""
import dataclasses
import random
import time

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.parallel import auto
from apex_tpu.runtime.step_cache import static_plan_key


def _profile(**kw):
    """A hand-built analytic profile (the planner only reads fields)."""
    base = dict(
        n_params=500_000_000,
        param_shapes=((500_000_000,),),
        param_bytes_fp32=2_000_000_000,
        half_itemsize=2,
        slots_per_param=2,
        batch_ref=8,
        batch_bytes_per_example=8192.0,
        flops_per_example=3.0e12,
        flops_fixed=0.0,
        act_bytes_per_example=50_000_000.0,
        act_bytes_fixed=0.0,
        hbm_bytes_per_example=1.0e8,
        hbm_bytes_fixed=2.0e9,
        logits_bytes_per_example=0.0,
        seq_len=2048, vocab=50257, hidden=4096, layers=16, heads=16,
        tp_axis=None, sp_axis=None, source="analytic")
    base.update(kw)
    return auto.ModelProfile(**base)


# ---------------------------------------------------------------------------
# Satellite 1: key round-trip + unknown-segment rejection
# ---------------------------------------------------------------------------


def test_plan_key_roundtrip_property():
    """plan_from_key(static_plan_key(p)) == p over randomized plans
    covering every v3 axis (offload fractions drawn from the ladder so
    the %g text form is exact)."""
    rng = random.Random(19)
    remats = list(auto.REMAT_POLICIES)
    offs = [0.0, 0.25, 0.5, 0.75, 1.0]
    for _ in range(300):
        pp = rng.choice([1, 1, 2, 4, 8])
        plan = auto.Plan(
            dp=rng.choice([1, 2, 4, 8]) if pp == 1 else 1,
            tp=rng.choice([1, 1, 2]) if pp == 1 else 1,
            sp=rng.choice([1, 1, 2]) if pp == 1 else 1,
            zero_stage=rng.choice([0, 1, 2, 3]) if pp == 1 else 0,
            accum=rng.choice([1, 2, 8]) if pp == 1 else 1,
            chunked_loss=rng.choice([False, True]),
            pp=pp,
            micro=rng.choice([pp, 2 * pp, 4 * pp]) if pp > 1 else 1,
            remat=rng.choice(remats),
            ep=1, offload_opt=rng.choice(offs),
            offload_act=rng.choice(offs),
            n_devices=rng.choice([8, 16, 32]))
        if plan.pp == 1 and plan.tp == 1 and plan.sp == 1 and \
                rng.random() < 0.3:
            plan = dataclasses.replace(plan, ep=plan.dp, zero_stage=0)
        back = auto.plan_from_key(static_plan_key(plan),
                                  n_devices=plan.n_devices)
        assert back == plan, (plan.key(), back, plan)


def test_plan_key_prev_format_unchanged():
    """A default-v3 plan keys to the historical 6-tuple — old ledgers,
    manifests and step-cache keys stay valid verbatim."""
    p = auto.Plan(dp=4, zero_stage=2, accum=2, chunked_loss=True,
                  n_devices=8)
    assert p.key() == (4, 1, 1, 2, 2, True)
    assert auto.plan_from_key(p.key(), n_devices=8) == p


def test_plan_from_key_rejects_unknown_segment():
    with pytest.raises(ValueError, match="zz9"):
        auto.plan_from_key((1, 1, 1, 0, 1, False, "zz9"), n_devices=1)
    with pytest.raises(ValueError, match="remat"):
        auto.plan_from_key((1, 1, 1, 0, 1, False, "remat=sometimes"),
                           n_devices=1)
    # repeated fields are as corrupt as unknown ones
    with pytest.raises(ValueError, match="pp"):
        auto.plan_from_key((1, 1, 1, 0, 1, False, "pp2", "pp4"),
                           n_devices=8)


# ---------------------------------------------------------------------------
# Satellite 5: joint search rescues a profile every dp×tp plan OOMs on
# ---------------------------------------------------------------------------


def _deep_profile():
    """Deep-GPT toy: 32 GB of batch-independent activations plus an
    8 GB fp32 parameter set — no dp×tp×zero split fits one v5e (~14.7 GB
    usable) even with the deepest offload rung (at most half the
    activations can move to host), but a 1F1B pipeline holds one stage
    slice and a recompute ring."""
    return _profile(
        n_params=2_000_000_000,
        param_shapes=((2_000_000_000,),),
        param_bytes_fp32=8_000_000_000,
        act_bytes_per_example=50_000_000.0,
        act_bytes_fixed=32_000_000_000.0,
        pp_axis="pp", remat_capable=False)


def test_joint_search_finds_pp_remat_when_dp_tp_oom():
    prof = _deep_profile()
    ids = jnp.zeros((8, 16), jnp.int32)
    t0 = time.perf_counter()
    rep = auto.plan_training(None, None, None, (ids, ids),
                             profile=prof, fleet="v5e:8", accum_max=8)
    wall_s = time.perf_counter() - t0
    assert rep.best is not None, rep.describe()
    assert rep.best.pp > 1 and rep.best.remat == "full", rep.best.name()
    # every feasible plan pipelines: nothing dp/tp-only survived the
    # HBM model, and the OOM prunes are counted, not silent
    assert all(p.pp > 1 for p in rep.ranked)
    assert rep.pruned_oom > 0
    assert rep.explored >= rep.pruned_oom + len(rep.ranked)
    assert any(r.startswith("memory-infeasible") for _, r in rep.rejected)
    # search telemetry: recorded on the report and the registry, and
    # the whole joint enumeration stays cheap on CPU
    assert 0.0 < rep.search_ms < 30_000.0
    assert wall_s < 60.0
    from apex_tpu.observe import registry as obs
    assert obs.gauge("plan.explored").value == float(rep.explored)
    assert obs.gauge("plan.pruned_oom").value == float(rep.pruned_oom)
    # the winner's describe() explains the pipeline choice
    text = rep.best.describe()
    assert "pipeline:" in text and "bubble fraction" in text
    assert "per-stage HBM" in text


def test_pp_memory_model_orders_remat_policies():
    """More aggressive remat → strictly less activation memory, and
    offload moves bytes to host without changing the HBM-side params."""
    prof = _deep_profile()
    mems = []
    for remat in ("none", "selective", "full"):
        plan = auto.Plan(pp=4, micro=8, remat=remat, n_devices=8)
        mem, _ = auto.predict_memory(plan, prof, auto.CHIPS["v5e"], 8)
        mems.append(mem)
    assert mems[0] > mems[1] > mems[2]
    base = auto.Plan(pp=4, micro=8, remat="full", n_devices=8)
    off = dataclasses.replace(base, offload_opt=1.0)
    m0, _ = auto.predict_memory(base, prof, auto.CHIPS["v5e"], 8)
    m1, bd1 = auto.predict_memory(off, prof, auto.CHIPS["v5e"], 8)
    assert m1 < m0
    assert dict(bd1)["host_opt_bytes"] > 0


def test_offload_priced_not_free():
    """An offload rung costs predicted time (H2D/D2H traffic at the
    chip's h2d_bw, ≥25% exposed) — it only wins when memory demands it."""
    prof = _deep_profile()
    spec = auto.CHIPS["v5e"]
    base = auto.Plan(pp=4, micro=8, remat="full", n_devices=8)
    off = dataclasses.replace(base, offload_opt=1.0, offload_act=0.5)
    ms0, _, _ = auto.predict_time(base, prof, spec, 8)
    ms1, bd1, _ = auto.predict_time(off, prof, spec, 8)
    bd1 = dict(bd1)
    assert ms1 > ms0
    assert bd1["offload_bytes"] > 0 and bd1["offload_ms"] > 0


# ---------------------------------------------------------------------------
# Satellite 6: heterogeneous-fleet pipeline stages
# ---------------------------------------------------------------------------


def test_hetero_fleet_pipeline_stage_apportionment():
    prof = _profile(pp_axis="pp", layers=13,
                    act_bytes_fixed=32_000_000_000.0,
                    param_shapes=((2_000_000_000,),),
                    param_bytes_fp32=8_000_000_000,
                    n_params=2_000_000_000)
    fleet = auto.parse_fleet("v5e:4+v4:4")
    ids = jnp.zeros((8, 16), jnp.int32)
    rep = auto.plan_training(None, None, None, (ids, ids),
                             profile=prof, fleet=fleet, accum_max=8)
    assert rep.best is not None, rep.describe()
    best = rep.best
    assert best.pp > 1, best.name()
    # stages apportioned over the first pp members by sustained flops,
    # covering all 13 layers — apportion_shares semantics
    assert len(best.stage_layers) == best.pp
    assert sum(best.stage_layers) == 13
    members = fleet.specs[:best.pp]
    expected = auto.apportion_shares(
        [s.sustained_flops() for s in members], 13)
    assert best.stage_layers == tuple(expected)
    assert best.stage_members == tuple(s.name for s in members)
    # the slowest member's stage time bounds the step: warmup/drain
    # multiplies it, collectives/overhead only add
    bd = dict(best.breakdown)
    assert "stage_ms_bound" in bd and "bound_member" in bd
    assert best.predicted_ms >= bd["stage_ms_bound"]
    ticks = best.micro + best.pp - 1
    assert best.predicted_ms >= bd["stage_ms_bound"] * ticks / best.micro
    # describe() names the per-member placement
    text = best.describe()
    assert "stage placement:" in text
    for i, s in enumerate(members):
        assert f"stage {i} → {s.name}" in text


def test_hetero_fleet_rejects_pp_dp_composition():
    prof = _profile(pp_axis="pp")
    plan = auto.Plan(dp=2, pp=2, micro=2, n_devices=4)
    fleet = auto.parse_fleet("v5e:2+v4:2")
    reason = auto._structural_reject(plan, prof, 8, fleet=fleet)
    assert reason is not None and "pp" in reason


# ---------------------------------------------------------------------------
# describe() for the full pp × remat × ep composition
# ---------------------------------------------------------------------------


def _moe_pp_plan_described():
    prof = _profile(
        n_params=1_300_000_000, param_shapes=((1_300_000_000,),),
        param_bytes_fp32=5_200_000_000,
        act_bytes_per_example=900_000_000.0,
        flops_per_example=2.6e13, layers=48, hidden=2048,
        pp_axis="pp", remat_capable=True, moe_axis="data",
        n_experts=8, moe_layers=24, moe_param_frac=0.55)
    spec = auto.CHIPS["v5e"]
    plan = auto.Plan(dp=8, ep=8, pp=4, micro=8, remat="selective",
                     offload_opt=1.0, offload_act=0.0,
                     pp_axis="pp", dp_axis="data", n_devices=32)
    mem, mem_bd = auto.predict_memory(plan, prof, spec, 64)
    ms, time_bd, colls = auto.predict_time(plan, prof, spec, 64)
    return dataclasses.replace(
        plan, predicted_ms=ms, predicted_hbm=mem,
        breakdown=tuple(time_bd + mem_bd), collectives=tuple(colls))


def test_describe_pp_remat_ep_plan_reports_everything():
    plan = _moe_pp_plan_described()
    text = plan.describe()
    assert "bubble fraction" in text
    assert "recompute" in text and "GFLOP/step" in text
    assert "offload bytes" in text
    assert "per-stage HBM" in text
    assert "expert parallel: ep=8" in text
    assert "all-to-all" in text
    d = dict(plan.breakdown)
    assert d["bubble_frac"] == pytest.approx(3 / 11)
    assert d["recompute_gflops"] > 0
    assert d["host_opt_bytes"] > 0


def test_moe_a2a_term_scales_with_ep():
    """The all-to-all term prices (ep-1)/ep of the routed tokens — more
    experts move more of the batch across the axis."""
    prof = _profile(moe_axis="data", n_experts=8, moe_layers=6,
                    moe_param_frac=0.4)
    spec = auto.CHIPS["v5e"]
    times = {}
    for ep in (2, 8):
        plan = auto.Plan(dp=ep, ep=ep, dp_axis="data", n_devices=8)
        ms, _, colls = auto.predict_time(plan, prof, spec, 8)
        times[ep] = ms
        assert any("all-to-all" in c for c in colls)
    dense2 = auto.Plan(dp=2, dp_axis="data", n_devices=8)
    dense_ms, _, dense_colls = auto.predict_time(dense2, prof, spec, 8)
    assert not any("all-to-all" in c for c in dense_colls)
    assert times[2] > dense_ms


def test_enumerate_includes_ep_twin_for_moe_profile():
    prof = _profile(moe_axis="data", n_experts=4, moe_layers=2,
                    moe_param_frac=0.3)
    ids = jnp.zeros((8, 16), jnp.int32)
    rep = auto.plan_training(None, None, None, (ids, ids),
                             profile=prof, fleet="v5e:4", accum_max=4)
    assert any(p.ep == 4 for p in rep.ranked), \
        [p.name() for p in rep.ranked[:10]]
    ep_best = [p for p in rep.ranked if p.ep == 4][0]
    assert ep_best.dp_axis == "data"
    assert ep_best.step_kwargs().get("axis_name") == "data"


# ---------------------------------------------------------------------------
# apply_plan wires pp plans into the pipeline entry points
# ---------------------------------------------------------------------------


def _toy_stack(rng, n_stages, n_micro, remat_stage=False):
    import numpy as np
    from apex_tpu.parallel import PipelinedStack

    d = 8

    def stage_fn(params, x):
        w, b = params
        return jnp.tanh(x @ w + b)

    w = jnp.asarray(rng.standard_normal((n_stages, d, d)) * 0.5,
                    jnp.float32)
    b = jnp.asarray(rng.standard_normal((n_stages, d)) * 0.1,
                    jnp.float32)
    stack = PipelinedStack(stage_fn, (w, b), "pp", n_micro=n_micro,
                           remat_stage=remat_stage)
    x = jnp.asarray(rng.standard_normal((16, d)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((16, d)), jnp.float32)
    return stack, x, y


@pytest.mark.parametrize("remat,schedule", [("none", "gpipe"),
                                            ("full", "1f1b")])
def test_apply_plan_runs_pipeline_schedules(remat, schedule):
    import numpy as np
    from apex_tpu.optimizers import FusedAdam

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    rng = np.random.default_rng(0)
    stack, x, y = _toy_stack(rng, n_stages=4, n_micro=4)
    opt = FusedAdam(list(stack.parameters()), lr=1e-2)

    def loss_fn(out, y):
        return jnp.mean((out - y) ** 2)

    plan = auto.Plan(pp=4, micro=4, remat=remat, pp_axis="pp",
                     n_devices=4)
    step = auto.apply_plan(plan, stack, opt, loss_fn,
                           half_dtype=None, loss_scale=1.0)
    assert step.plan is plan
    losses = [float(step(x, y)) for _ in range(4)]
    assert all(jnp.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]       # it actually trains


def test_apply_plan_pp_validates_stack_shape():
    import numpy as np
    from apex_tpu.optimizers import FusedAdam

    rng = np.random.default_rng(0)
    stack, x, y = _toy_stack(rng, n_stages=4, n_micro=4)
    opt = FusedAdam(list(stack.parameters()), lr=1e-2)

    def loss_fn(out, y):
        return jnp.mean((out - y) ** 2)

    with pytest.raises(ValueError, match="n_micro"):
        auto.apply_plan(auto.Plan(pp=4, micro=8, remat="full",
                                  pp_axis="pp", n_devices=4),
                        stack, opt, loss_fn)
    with pytest.raises(ValueError, match="PipelinedStack"):
        auto.apply_plan(auto.Plan(pp=4, micro=4, n_devices=4),
                        object(), opt, loss_fn)
    with pytest.raises(ValueError, match="remat_stage"):
        auto.apply_plan(auto.Plan(pp=4, micro=4, remat="selective",
                                  pp_axis="pp", n_devices=4),
                        stack, opt, loss_fn)


def test_executor_h2d_ewma_feeds_planner():
    from apex_tpu.runtime import executor as ex
    ex.reset_h2d_bw()
    try:
        assert ex.measured_h2d_bw() is None
        ex.note_h2d(1 << 20, 1e-3)          # 1 MiB in 1 ms ≈ 1 GB/s
        bw1 = ex.measured_h2d_bw()
        assert bw1 == pytest.approx((1 << 20) / 1e-3)
        ex.note_h2d(1 << 20, 2e-3)
        bw2 = ex.measured_h2d_bw()
        assert bw2 == pytest.approx(0.8 * bw1 + 0.2 * (1 << 20) / 2e-3)
        ex.note_h2d(16, 1e-3)               # tiny: latency, not bandwidth
        assert ex.measured_h2d_bw() == bw2
    finally:
        ex.reset_h2d_bw()


def test_planner_telemetry_cataloged():
    from apex_tpu.observe import catalog
    for name in ("plan.search_ms", "plan.explored", "plan.pruned_oom",
                 "plan.bubble_frac"):
        entry = catalog.describe(name)
        assert entry is not None, name
        assert entry["kind"] == "gauge"
        assert entry["unit"] and entry["description"]
