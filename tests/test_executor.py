"""The one-runtime executor (apex_tpu/runtime/executor.py).

Pins the tentpole contract of the unified dispatch path:

* the eager optimizer surface and the fused train step run through the
  SAME executor — shared stats, shared donation policy, loss/param
  parity between the two surfaces (bitwise for fp32 SGD);
* 1 compile + 1 dispatch per window on BOTH surfaces under an lr
  schedule (the step-cache invariant, now executor-owned);
* ZeRO-1/3 all-gather prefetch is a pure schedule transformation:
  overlap on vs off is bitwise-identical (on this cpu backend XLA runs
  the collectives synchronously, so the parity is provable in-tree);
* ``Executor.drive`` + ``DataPrefetcher`` issue exactly one H2D
  transfer per accumulation window, double-buffered;
* resilience (BadStepGuard, elastic load_state) composes with
  executor-dispatched steps;
* the telemetry carry works across mesh plans (dp×tp) — the satellite
  fix for ``make_train_step(telemetry=True)`` refusing tp plans.
"""
import math

import jax.numpy as jnp
import numpy as np
import pytest

import apex_tpu.nn as nn
from apex_tpu.nn import functional as F
from apex_tpu.observe import get_registry
from apex_tpu.optimizers import FusedAdam, FusedSGD
from apex_tpu.parallel import auto
from apex_tpu.runtime import executor as rex
from apex_tpu.runtime import resilience, step_cache
from apex_tpu.runtime.resilience import BadStepGuard
from apex_tpu.training import make_train_step


@pytest.fixture(autouse=True)
def _fresh():
    step_cache.clear()
    step_cache.reset_stats()
    get_registry().clear_events()
    yield
    step_cache.clear()
    step_cache.reset_stats()


def _model(seed=7):
    nn.manual_seed(seed)
    return nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))


def _data(rng, b=8):
    x = jnp.asarray(rng.standard_normal((b, 16)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 4, (b,)))
    return x, y


def _loss(o, t):
    return F.cross_entropy(o, t)


# ---------------------------------------------------------------------------
# Program / submit / policy unit surface
# ---------------------------------------------------------------------------


def test_program_jit_memoized_and_uncounted():
    """executor.jit is the diagnostic surface: one jitted callable per
    Program (memoized), and building it never counts a dispatch."""
    prog = rex.Program("train_step", ("t-memo",), lambda a, b: a + b)
    f1 = rex.executor.jit(prog)
    f2 = rex.executor.jit(prog)
    assert f1 is f2
    s = rex.executor.stats()
    assert s["dispatches"] == 0 and s["compiles"] == 0


def test_submit_counts_compiles_and_dispatches():
    prog = rex.Program("train_step", ("t-count",), lambda a, b: a + b)
    a, b = jnp.ones((3,)), jnp.ones((3,))
    out1 = rex.executor.submit(prog, (a, b), step=1)
    out2 = rex.executor.submit(prog, (a, b), step=2)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    st = rex.executor.stats()["by_kind"]["train_step"]
    assert st["compiles"] == 1
    assert st["dispatches"] == 2
    assert st["cache_hits"] == 1
    # train-kind dispatches opened spans and heartbeat the watchdog
    spans = [e for e in get_registry().events("span")
             if e["span"] == "dispatch" and e["kind"] == "train_step"]
    assert len(spans) == 2
    assert [e["step"] for e in spans] == [1, 2]     # the caller's count


@pytest.mark.parametrize("kind,spans", [
    ("block_copy", True),           # rides in a serve tick
    ("decode_step", True),
    ("weight_publish", True),
    ("fused_adam_step", False),     # an eager kind: counted, no span
], ids=lambda v: str(v))
def test_which_kinds_span(kind, spans):
    """Serve kinds (the copy-on-write block copy among them), train and
    rollout kinds open a ``dispatch`` span inside the enclosing one;
    eager kinds only count, and nothing switches that."""
    from apex_tpu.observe import span
    assert not hasattr(step_cache, "set_dispatch_spans")
    prog = rex.Program(kind, ("t-span", kind), lambda a: a + 1)
    with span("serve.step", tick=4) as root:
        rex.executor.submit(prog, (jnp.ones((2,)),),
                            step=3 if spans else None)
    assert rex.executor.stats()["by_kind"][kind]["dispatches"] == 1
    recs = [e for e in get_registry().events("span")
            if e["span"] == "dispatch"]
    assert len(recs) == (1 if spans else 0)
    if spans:
        (rec,) = recs
        assert rec["kind"] == kind and rec["step"] == 3
        assert rec["parent"] == root["id"] and rec["tick"] == 4
        assert root["t0_ns"] <= rec["t0_ns"] <= rec["t1_ns"] <= root["t1_ns"]


def test_donation_policy_resolution():
    d = rex.DonationPolicy()
    assert d.mode == "auto"
    assert d.enabled is False          # cpu test backend: auto is off
    assert d.resolve(True) is True
    assert d.resolve(False) is False
    assert d.resolve("auto") is False
    d.set(True)
    assert d.enabled is True and d.resolve("auto") is True
    with pytest.raises(ValueError, match="donation mode"):
        d.set("maybe")


def test_step_cache_donation_is_executor_delegate():
    """set_donation/donation_enabled are thin views of the ONE policy on
    the executor — no second copy to drift."""
    assert step_cache.donation_enabled() is rex.donation.enabled is False
    step_cache.set_donation(True)
    try:
        assert rex.donation.enabled is True
        assert step_cache.donation_enabled() is True
    finally:
        step_cache.set_donation("auto")
    assert rex.donation.mode == "auto"


def test_overlap_knobs_resolution_and_validation():
    # cpu backend: "auto" resolves off for both dimensions
    assert rex.overlap_enabled("gather") is False
    assert rex.overlap_enabled("h2d") is False
    rex.set_overlap(gather=True)
    try:
        assert rex.overlap_enabled("gather") is True
        # a per-call override wins over the process knob
        assert rex.overlap_enabled("gather", override=False) is False
        # None/"auto" overrides defer to the knob
        assert rex.overlap_enabled("gather", override="auto") is True
        assert rex.overlap_enabled("h2d") is False   # other knob untouched
    finally:
        rex.set_overlap(gather="auto")
    assert rex.overlap_enabled("gather") is False
    with pytest.raises(ValueError, match="overlap gather"):
        rex.set_overlap(gather="sometimes")


# ---------------------------------------------------------------------------
# donation: input->output aliasing in the lowered HLO (executor-level —
# relocated from test_step_cache.py: the policy lives on the executor now)
# ---------------------------------------------------------------------------


def test_donation_alias_in_lowered_hlo(rng):
    # donation is "auto" (off on the copy-on-donate cpu backend); force it
    # on to inspect the aliasing the accelerator path compiles with
    rex.donation.set(True)
    try:
        from apex_tpu.nn import Parameter
        params = []
        for s in [(7,), (5, 3)]:
            p = Parameter(jnp.asarray(rng.standard_normal(s), jnp.float32))
            p.grad = jnp.asarray(rng.standard_normal(s), jnp.float32)
            params.append(p)
        opt = FusedAdam(params, lr=1e-2)
        opt.step()
        (entry,) = [e for e in rex.executor.cache.entries()
                    if e["kind"] == "fused_adam"]
        txt = entry["fn"].lower(*entry["example"]).as_text()
        # donated leaves: params + exp_avg + exp_avg_sq per bucket + the
        # step counter — every one must alias an output buffer
        n_donated = 3 * len(params) + 1
        assert txt.count("tf.aliasing_output") >= n_donated
    finally:
        rex.donation.set("auto")


# ---------------------------------------------------------------------------
# one executor, two surfaces: eager optimizer.step() vs fused train step
# ---------------------------------------------------------------------------


def test_eager_and_fused_sgd_match_bitwise(rng):
    """fp32 SGD, loss_scale=1.0: the eager backward+optimizer.step()
    surface and the fused train step — both dispatched by the one
    executor — produce bitwise-identical parameters."""
    x, y = _data(rng)
    crit = nn.CrossEntropyLoss()

    model_a = _model()
    opt_a = FusedSGD(list(model_a.parameters()), lr=0.05, momentum=0.9)
    for _ in range(4):
        loss = crit(model_a(x), y)
        loss.backward()
        opt_a.step()
        opt_a.zero_grad()

    model_b = _model()
    opt_b = FusedSGD(list(model_b.parameters()), lr=0.05, momentum=0.9)
    step = make_train_step(model_b, opt_b, _loss, half_dtype=None,
                           loss_scale=1.0)
    for _ in range(4):
        step(x, y)

    for pa, mb in zip(model_a.parameters(), step.state.master_params):
        np.testing.assert_array_equal(np.asarray(pa.data), np.asarray(mb))

    # both surfaces were counted by the SAME executor
    by = rex.executor.stats()["by_kind"]
    assert by["fused_sgd"]["dispatches"] == 4
    assert by["train_step"]["dispatches"] == 4


def test_eager_and_fused_adam_match(rng):
    x, y = _data(rng)
    crit = nn.CrossEntropyLoss()

    model_a = _model()
    opt_a = FusedAdam(list(model_a.parameters()), lr=1e-2)
    eager = []
    for _ in range(4):
        loss = crit(model_a(x), y)
        loss.backward()
        opt_a.step()
        opt_a.zero_grad()
        eager.append(float(loss))

    model_b = _model()
    opt_b = FusedAdam(list(model_b.parameters()), lr=1e-2)
    step = make_train_step(model_b, opt_b, _loss, half_dtype=None,
                           loss_scale=1.0)
    fused = [float(step(x, y)) for _ in range(4)]

    # tolerance, not bitwise: Adam's eps/sqrt denominator amplifies the
    # one-executable fusion's reassociation by a few ulp per step
    np.testing.assert_allclose(fused, eager, rtol=1e-5, atol=1e-6)
    for pa, mb in zip(model_a.parameters(), step.state.master_params):
        np.testing.assert_allclose(np.asarray(pa.data), np.asarray(mb),
                                   rtol=1e-4, atol=1e-5)


def test_one_compile_per_window_both_surfaces_under_cosine_lr(rng):
    """The retrace pin, at the executor: a cosine lr schedule keys NO new
    program on either surface — 1 compile, 1 dispatch per window."""
    lr_of = lambda i: 1e-2 * 0.5 * (1 + math.cos(math.pi * i / 10))  # noqa: E731

    # eager surface
    model_a = _model()
    opt_a = FusedAdam(list(model_a.parameters()), lr=1e-2)
    crit = nn.CrossEntropyLoss()
    x, y = _data(rng)
    for i in range(10):
        opt_a.param_groups[0]["lr"] = lr_of(i)
        loss = crit(model_a(x), y)
        loss.backward()
        opt_a.step()
        opt_a.zero_grad()
    st = rex.executor.stats()["by_kind"]["fused_adam"]
    assert st["compiles"] == 1 and st["dispatches"] == 10

    # fused surface, K=4 accumulation windows
    model_b = _model()
    opt_b = FusedAdam(list(model_b.parameters()), lr=1e-2)
    step = make_train_step(model_b, opt_b, _loss, half_dtype=None,
                           loss_scale=1.0, accum_steps=4,
                           accum_stacked=True)
    rng2 = np.random.default_rng(0)
    xb = jnp.asarray(rng2.standard_normal((4, 4, 16)), jnp.float32)
    yb = jnp.asarray(rng2.integers(0, 4, (4, 4)))
    for i in range(6):
        opt_b.param_groups[0]["lr"] = lr_of(i)
        step(xb, yb)
    st = rex.executor.stats()["by_kind"]["train_step"]
    assert st["compiles"] == 1
    assert st["dispatches"] == 6       # windows, not microbatches
    assert st["cache_hits"] == 5


# ---------------------------------------------------------------------------
# ZeRO all-gather prefetch: overlap on == overlap off, bitwise
# ---------------------------------------------------------------------------


def _zero_build(stage, overlap, lr=1e-2):
    nn.manual_seed(11)
    model = nn.Sequential(nn.Linear(16, 64), nn.ReLU(), nn.Linear(64, 8))
    opt = FusedAdam(list(model.parameters()), lr=lr)
    step = make_train_step(model, opt, _loss, half_dtype=None,
                           loss_scale=1.0, zero_sharding=True,
                           zero_stage=stage, accum_steps=4,
                           donate_state=False, overlap=overlap)
    return step


@pytest.mark.parametrize("stage", [1, 3])
def test_zero_gather_prefetch_bitwise_parity(stage, rng):
    """The prefetch pipelines the replicated parameter view one scan
    iteration early — a pure schedule change.  Forced on (the cpu "auto"
    default is off) it must be bitwise-identical to overlap off."""
    x = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 8, (32,)))

    off = _zero_build(stage, overlap=False)
    off_losses = [float(off(x, y)) for _ in range(3)]

    # the process-wide knob spelling: set_overlap + overlap="auto"
    rex.set_overlap(gather=True)
    try:
        on = _zero_build(stage, overlap="auto")
        on_losses = [float(on(x, y)) for _ in range(3)]
    finally:
        rex.set_overlap(gather="auto")

    assert on_losses == off_losses     # float() of bitwise-equal scalars
    for a, b in zip(on.state.master_params, off.state.master_params):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    st = rex.executor.stats()["by_kind"]["zero_train_step"]
    assert st["dispatches"] == 6       # 3 windows each build, 1 per window
    assert st["compiles"] == 2         # one program per build token


# ---------------------------------------------------------------------------
# Executor.drive: async H2D double-buffering, one transfer per window
# ---------------------------------------------------------------------------


def test_drive_one_h2d_per_window(rng):
    """drive() wraps a host iterable in a DataPrefetcher: K loader
    batches stack into one (K, B, ...) block and cross H2D as exactly ONE
    span("h2d") transfer per accumulation window."""
    model = _model()
    opt = FusedSGD(list(model.parameters()), lr=0.05)
    step = make_train_step(model, opt, _loss, half_dtype=None,
                           loss_scale=1.0, accum_steps=4,
                           accum_stacked=True)
    host_rng = np.random.default_rng(5)
    batches = [(host_rng.standard_normal((8, 16)).astype(np.float32),
                host_rng.integers(0, 4, (8,)))
               for _ in range(20)]                    # 20 batches = 5 windows

    rex.set_overlap(h2d=True)                         # force double-buffering
    try:
        losses = rex.executor.drive(step, batches, accum_steps=4)
    finally:
        rex.set_overlap(h2d="auto")

    assert len(losses) == 5
    assert all(np.isfinite(float(l)) for l in losses)
    h2d = [e for e in get_registry().events("span") if e["span"] == "h2d"]
    assert len(h2d) == 5                              # ONE transfer per window
    assert all(e["accum_steps"] == 4 for e in h2d)
    # the consumer's side of the queue: one wait per window and one for
    # the end of the data, on the step loop's thread, none inside a
    # worker's span
    waits = [e for e in get_registry().events("span")
             if e["span"] == "data.wait"]
    assert len(waits) == 6
    assert all(e["parent"] is None for e in waits + h2d)
    st = rex.executor.stats()["by_kind"]["train_step"]
    assert st["compiles"] == 1 and st["dispatches"] == 5

    # the pipeline is numerically inert: a plain loop over the same
    # blocks gives the same losses bitwise
    model2 = _model()
    opt2 = FusedSGD(list(model2.parameters()), lr=0.05)
    step2 = make_train_step(model2, opt2, _loss, half_dtype=None,
                            loss_scale=1.0, accum_steps=4,
                            accum_stacked=True)
    ref = []
    for w in range(5):
        blk = batches[4 * w:4 * w + 4]
        xb = jnp.asarray(np.stack([b[0] for b in blk]))
        yb = jnp.asarray(np.stack([b[1] for b in blk]))
        ref.append(float(step2(xb, yb)))
    assert [float(l) for l in losses] == ref


def test_drive_respects_max_steps(rng):
    model = _model()
    opt = FusedSGD(list(model.parameters()), lr=0.05)
    step = make_train_step(model, opt, _loss, half_dtype=None,
                           loss_scale=1.0)
    host_rng = np.random.default_rng(5)
    batches = [(host_rng.standard_normal((8, 16)).astype(np.float32),
                host_rng.integers(0, 4, (8,)))
               for _ in range(10)]
    losses = rex.executor.drive(step, batches, max_steps=3)
    assert len(losses) == 3
    assert rex.executor.stats()["by_kind"]["train_step"]["dispatches"] == 3


# ---------------------------------------------------------------------------
# resilience through the executor
# ---------------------------------------------------------------------------


def test_guard_observes_through_zero_step(rng):
    """BadStepGuard attaches to the (executor-dispatched) ZeRO wrapper:
    clean windows observed, overflow windows counted and escalated."""
    nn.manual_seed(3)
    model = nn.Sequential(nn.Linear(16, 64), nn.ReLU(), nn.Linear(64, 8))
    opt = FusedAdam(list(model.parameters()), lr=1e-3)
    step = make_train_step(model, opt, _loss, half_dtype=jnp.float16,
                           loss_scale="dynamic", zero_sharding=True,
                           donate_state=False)
    guard = BadStepGuard(patience=2, policy="warn")
    guard.attach(step)
    x = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 8, (32,)))
    step(x, y)
    step(x, y)
    guard.flush()
    assert guard.stats["observed"] == 2
    assert guard.stats["skipped"] == 0

    bad = x.at[0, 0].set(np.inf)
    with pytest.warns(UserWarning, match="BadStepGuard"):
        step(bad, y)
        step(bad, y)
        guard.flush()
    assert guard.stats["skipped"] == 2
    assert guard.stats["escalations"] == 1


def test_elastic_load_state_resumes_bitwise(rng):
    """snapshot -> fresh build -> load_state: the restored step continues
    bitwise-identically to the uninterrupted run, still 1 dispatch per
    window through the executor."""
    x = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 8, (32,)))
    plan = auto.Plan(dp=4, zero_stage=1, n_devices=8)

    def build(seed):
        nn.manual_seed(seed)
        model = nn.Sequential(nn.Linear(16, 64), nn.ReLU(),
                              nn.Linear(64, 8))
        opt = FusedAdam(list(model.parameters()), lr=1e-2)
        return make_train_step(model, opt, _loss, half_dtype=None,
                               loss_scale=1.0, parallel=plan)

    z = build(0)
    for _ in range(3):
        z(x, y)
    host = resilience.snapshot_state(z.state)

    z2 = build(1)                      # different init: restore must win
    z2.load_state(host)
    cont = [float(z2(x, y)) for _ in range(2)]
    ref = [float(z(x, y)) for _ in range(2)]
    assert cont == ref
    st = rex.executor.stats()["by_kind"]["zero_train_step"]
    assert st["dispatches"] == 7       # 3 + 2 + 2, one per window
    assert st["compiles"] == 2         # one program per build


# ---------------------------------------------------------------------------
# telemetry across mesh plans (the dp×tp carry fix)
# ---------------------------------------------------------------------------


def _tp_model():
    from apex_tpu.models import GptModel
    nn.manual_seed(11)
    return GptModel(vocab_size=64, hidden=32, layers=1, heads=4,
                    max_positions=8, dropout=0.0, attn_dropout=0.0,
                    tp_axis="tp")


def _lm_batch(b=8):
    host = np.random.default_rng(3)
    ids = jnp.asarray(host.integers(0, 64, (b, 8)))
    return ids, jnp.asarray(np.roll(np.asarray(ids), -1, axis=1))


def _lm_loss(logits, tgt):
    return F.cross_entropy(logits.reshape((-1, 64)), tgt.reshape((-1,)))


def test_telemetry_dp2_tp2_grad_norm_parity():
    """telemetry=True on a dp2×tp2 plan (which used to be refused): the
    drained loss_mean is the GLOBAL pmean — bitwise equal to the step's
    returned loss — and the grad norm (computed on the replicated
    post-exchange gradients, no extra collective) is bitwise reproducible
    across an independent rebuild."""
    ids, tgt = _lm_batch()
    plan = auto.Plan(dp=2, tp=2, tp_axis="tp", n_devices=4)

    def build(telemetry):
        m = _tp_model()
        opt = FusedAdam(list(m.parameters()), lr=1e-2)
        return make_train_step(m, opt, _lm_loss, half_dtype=None,
                               loss_scale=1.0, parallel=plan,
                               telemetry=telemetry, drain_every=1)

    step = build(telemetry=True)
    losses = [float(step(ids, tgt)) for _ in range(3)]
    recs = get_registry().events("train.telemetry")
    assert [r["step"] for r in recs] == [1, 2, 3]
    for r, l in zip(recs, losses):
        assert r["windows"] == 1
        # the accumulator pmeans the shard-local loss over the plan's
        # batch axes — same reduction as the returned loss: bitwise
        assert r["loss_mean"] == l
        assert np.isfinite(r["grad_norm"]) and r["grad_norm"] > 0
        assert r["loss_scale"] == 1.0 and r["overflow_count"] == 0

    # grad_norm is deterministic: an independent identical build drains
    # bitwise-equal norms
    get_registry().clear_events()
    step2 = build(telemetry=True)
    for _ in range(3):
        step2(ids, tgt)
    recs2 = get_registry().events("train.telemetry")
    assert [r["grad_norm"] for r in recs2] == \
        [r["grad_norm"] for r in recs]

    # and the carry is numerically inert: telemetry off, same trajectory
    step3 = build(telemetry=False)
    off_losses = [float(step3(ids, tgt)) for _ in range(3)]
    assert off_losses == losses
