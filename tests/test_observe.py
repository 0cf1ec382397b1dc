"""apex_tpu.observe: metrics registry / JSONL schema round-trip, the
zero-dispatch on-device telemetry carry (bitwise grad-norm parity with an
eager recompute, 1-compile/1-dispatch pin under accumulation), trace
spans, and the stall watchdog (fires under an injected chaos stall, stays
silent on a clean run)."""
import gc
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import apex_tpu.nn as nn
from apex_tpu import observe
from apex_tpu.nn import functional as F
from apex_tpu.nn.modules import Ctx
from apex_tpu.observe import (MetricsRegistry, SCHEMA_VERSION, StallWatchdog,
                              get_registry, heartbeat, last_span, span)
from apex_tpu.optimizers import FusedSGD
from apex_tpu.runtime import chaos, step_cache
from apex_tpu.training import make_train_step

pytestmark = pytest.mark.observe


@pytest.fixture(autouse=True)
def _no_automatic_collections():
    """The span tests read exact lists of records, and a collection is a
    ``host.gc`` record: a test that wants one collects by hand."""
    from apex_tpu.observe import spans
    was = gc.isenabled()
    gc.disable()
    spans._flush_gc()               # collections of the tests before
    yield
    if was:
        gc.enable()


def _mlp(seed=0, din=8, hidden=16, dout=4):
    nn.manual_seed(seed)
    return nn.Sequential(nn.Linear(din, hidden), nn.ReLU(),
                         nn.Linear(hidden, dout))


def _data(n=4, din=8, dout=4, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((n, din)), jnp.float32)
    y = jnp.asarray(rng.integers(0, dout, (n,)))
    return x, y


# ---------------------------------------------------------------------------
# registry + event log
# ---------------------------------------------------------------------------


def test_registry_jsonl_schema_roundtrip(tmp_path):
    reg = MetricsRegistry()
    path = str(tmp_path / "events.jsonl")
    reg.add_jsonl_sink(path)
    reg.counter("c").inc(3)
    reg.gauge("g").set(2.5)
    reg.histogram("h").observe(1.0)
    reg.histogram("h").observe(3.0)
    reg.event("alpha", k=1)
    reg.event("beta", arr=jnp.zeros(2))     # non-JSON value -> default=str
    reg.remove_jsonl_sink(path)

    lines = [json.loads(line) for line in open(path)]
    assert [ln["event"] for ln in lines] == ["alpha", "beta"]
    for ln in lines:
        assert ln["schema"] == SCHEMA_VERSION
        assert isinstance(ln["ts_ms"], float)
    assert lines[0]["k"] == 1
    assert isinstance(lines[1]["arr"], str)
    # monotonic timestamps order the stream
    assert lines[1]["ts_ms"] >= lines[0]["ts_ms"]
    # the in-memory buffer carries the same records
    assert reg.events("alpha")[0]["k"] == 1
    snap = reg.snapshot()
    assert snap["schema"] == SCHEMA_VERSION
    assert snap["counters"]["c"] == 3
    assert snap["gauges"]["g"] == 2.5
    h = snap["histograms"]["h"]
    assert h["count"] == 2 and h["min"] == 1.0 and h["max"] == 3.0 \
        and h["mean"] == 2.0
    # prefix removal resets one subsystem's slice only
    reg.remove("c")
    snap = reg.snapshot()
    assert "c" not in snap["counters"] and "g" in snap["gauges"]


def test_span_emits_event_and_last_span():
    reg = get_registry()
    reg.clear_events()
    with span("test.region", phase="fwd"):
        pass
    (ev,) = [e for e in reg.events("span") if e["span"] == "test.region"]
    assert ev["phase"] == "fwd" and ev["dur_ms"] >= 0
    assert ev["schema"] == SCHEMA_VERSION
    assert last_span()["span"] == "test.region"
    assert ev["dur_ms"] == (ev["t1_ns"] - ev["t0_ns"]) / 1e6


def test_nested_spans_record_a_tree():
    """A span's record names its parent (the span open on the same
    thread), lies inside it on the perf_counter_ns clock, and is of its
    parent's tick unless it gives its own."""
    reg = get_registry()
    reg.clear_events()
    before = time.perf_counter_ns()
    with span("t.root", tick=7) as root:
        with span("t.child", kind="k") as child:
            with span("t.leaf", tick=8):
                pass
        with span("t.second"):
            pass
        root["n_done"] = 2              # a field known only at the end
    recs = {e["span"]: e for e in reg.events("span")}
    assert recs["t.root"]["parent"] is None and recs["t.root"]["n_done"] == 2
    assert recs["t.child"]["parent"] == recs["t.root"]["id"] == root["id"]
    assert recs["t.leaf"]["parent"] == child["id"]
    assert recs["t.second"]["parent"] == root["id"]
    assert len({e["id"] for e in recs.values()}) == 4
    assert [recs[n]["tick"] for n in
            ("t.root", "t.child", "t.leaf", "t.second")] == [7, 7, 8, 7]
    for name in ("t.child", "t.leaf", "t.second"):
        kid = recs[name]
        assert recs["t.root"]["t0_ns"] <= kid["t0_ns"] <= kid["t1_ns"] \
            <= recs["t.root"]["t1_ns"]
        assert kid["dur_ms"] == (kid["t1_ns"] - kid["t0_ns"]) / 1e6
    assert before <= recs["t.root"]["t0_ns"] <= time.perf_counter_ns()
    # after the block nothing is open: the next span is a root again
    with span("t.after") as after:
        pass
    assert after["parent"] is None and "tick" not in after


def test_span_on_a_second_thread_has_no_parent_from_the_first():
    import threading
    get_registry().clear_events()
    seen = {}

    def worker():
        with span("t.worker") as rec:
            with span("t.worker.inner") as inner:
                pass
        seen["rec"], seen["inner"] = rec, inner

    with span("t.main", tick=3) as main:
        th = threading.Thread(target=worker)
        th.start()
        th.join(timeout=10)
        assert not th.is_alive()
    assert seen["rec"]["parent"] is None and "tick" not in seen["rec"]
    assert seen["inner"]["parent"] == seen["rec"]["id"]
    assert main["t0_ns"] <= seen["rec"]["t0_ns"] <= main["t1_ns"]


def test_each_event_name_has_its_own_ring():
    """A frequent event cannot push a rare one out of memory, and the
    rings merge back in arrival order."""
    reg = MetricsRegistry()
    reg.set_event_capacity("span", 8)
    reg.event("rare", n=0)
    for i in range(20):
        reg.event("span", i=i)
        if i == 10:
            reg.event("rare", n=1)
    assert [e["i"] for e in reg.events("span")] == list(range(12, 20))
    assert [e["n"] for e in reg.events("rare")] == [0, 1]
    merged = [(e["event"], e.get("i", e.get("n"))) for e in reg.events()]
    assert merged == [("rare", 0), ("rare", 1)] + \
        [("span", i) for i in range(12, 20)]
    reg.set_event_capacity("span", 4)       # shrinking keeps the newest
    assert [e["i"] for e in reg.events("span")] == [16, 17, 18, 19]
    reg.clear_events()
    assert reg.events() == []
    for i in range(6):
        reg.event("span", i=i)
    assert len(reg.events("span")) == 4     # the bound outlives a clear
    with pytest.raises(ValueError, match="capacity"):
        reg.set_event_capacity("span", 0)
    # the process-wide registry keeps SPAN_RING span records
    from apex_tpu.observe import spans
    assert spans.SPAN_RING == 131072
    get_registry().clear_events()
    with span("t.ring"):
        pass
    assert get_registry()._events["span"].maxlen == spans.SPAN_RING


def test_recorded_returns_records_oldest_first_since_a_time():
    from apex_tpu.observe import spans
    get_registry().clear_events()
    with span("t.old"):
        pass
    cut = time.perf_counter_ns()
    with span("t.outer"):
        with span("t.inner"):
            pass
    # the ring is in order of exit (inner first); recorded() is by start
    assert [e["span"] for e in get_registry().events("span")] == \
        ["t.old", "t.inner", "t.outer"]
    assert [r["span"] for r in spans.recorded()] == \
        ["t.old", "t.outer", "t.inner"]
    assert [r["span"] for r in spans.recorded(since_ns=cut)] == \
        ["t.outer", "t.inner"]
    assert spans.recorded(since_ns=time.perf_counter_ns()) == []


@pytest.mark.parametrize("fields,name,args", [
    ({"kind": "decode_step", "tick": 5}, "dispatch.decode_step",
     {"tick": 5}),
    ({"kind": "train_step", "step": 9}, "dispatch.train_step", {}),
    ({"what": "tokens"}, "dispatch", {}),
], ids=["kind_and_tick", "kind", "plain"])
def test_annotation_name_carries_the_kind(monkeypatch, fields, name, args):
    """The profiler's event is ``<name>.<kind>`` and carries the
    record's id (and tick), so an xplane host event joins to it."""
    import contextlib

    from apex_tpu.observe import spans
    made = []

    def fake(label, **kw):
        made.append((label, kw))
        return contextlib.nullcontext()
    spans._get_trace_annotation()           # probe before patching
    monkeypatch.setattr(spans, "_trace_annotation", fake)
    with span("dispatch", **fields) as rec:
        pass
    assert made == [(name, dict(args, id=rec["id"]))]
    assert spans.annotation_name("dispatch", fields) == name


def test_clock_ns_is_on_root_records_only():
    """A root record carries the profiler's clock less perf_counter,
    read beside its start; a record below it is mapped by its root's."""
    import threading
    get_registry().clear_events()
    before = time.time_ns() - time.perf_counter_ns()
    with span("t.root", tick=2) as root:
        with span("t.child") as child:
            pass

    def worker():
        with span("t.worker"):
            pass
    th = threading.Thread(target=worker)
    th.start()
    th.join(timeout=10)
    after = time.time_ns() - time.perf_counter_ns()
    recs = {e["span"]: e for e in get_registry().events("span")}
    for name in ("t.root", "t.worker"):
        assert recs[name]["parent"] is None
        # the two clocks' difference, to the time of two reads
        assert min(before, after) - 1_000_000 <= recs[name]["clock_ns"] \
            <= max(before, after) + 1_000_000
    assert "clock_ns" not in child and "clock_ns" in root
    assert "clock_ns" not in recs["t.child"]


def test_a_collection_is_a_host_gc_record_of_the_open_span(monkeypatch):
    """A collector pause is a ``host.gc`` record: below the span open on
    the collecting thread and of its tick, with its own annotation; one
    outside every span is a root with a clock."""
    import contextlib

    from apex_tpu.observe import spans
    made = []

    def fake(label, **kw):
        made.append((label, kw))
        return contextlib.nullcontext()
    spans._get_trace_annotation()           # probe before patching
    monkeypatch.setattr(spans, "_trace_annotation", fake)
    get_registry().clear_events()
    with span("t.root", tick=6):
        with span("t.child") as child:
            gc.collect(1)
    gc.collect(0)
    gcs = [r for r in spans.recorded() if r["span"] == "host.gc"]
    assert [r["generation"] for r in gcs] == [1, 0]
    inner, outer = gcs
    assert inner["parent"] == child["id"] and inner["tick"] == 6
    assert "clock_ns" not in inner
    assert child["t0_ns"] <= inner["t0_ns"] <= inner["t1_ns"] \
        <= child["t1_ns"]
    assert inner["dur_ms"] == (inner["t1_ns"] - inner["t0_ns"]) / 1e6
    assert outer["parent"] is None and "tick" not in outer
    assert "clock_ns" in outer
    assert ("host.gc", {"id": inner["id"], "tick": 6}) in made
    assert ("host.gc", {"id": outer["id"]}) in made


def test_no_span_histogram_is_left():
    """A span's duration is on its record; only the documented operator
    histograms (``serve.decode_tick_ms``, ...) are histograms."""
    reg = get_registry()
    with span("t.hist", kind="k"):
        pass
    assert not [h for h in reg.snapshot()["histograms"]
                if h.startswith("span.")]


# ---------------------------------------------------------------------------
# the on-device telemetry carry
# ---------------------------------------------------------------------------


def test_drained_grad_norm_bitwise_matches_eager_recompute():
    """At loss_scale=1.0 (static) the master grads are the raw f32 grads,
    so the carry's on-device sqrt(sum(g*g)) must be bitwise-identical to
    an eager jax.grad recompute over the same forward/env/key."""
    get_registry().clear_events()
    model = _mlp()
    params = [p for p in model.parameters()]
    opt = FusedSGD(params, lr=0.1, momentum=0.9)
    step = make_train_step(model, opt, lambda o, t: F.cross_entropy(o, t),
                           half_dtype=None, loss_scale=1.0,
                           telemetry=True, drain_every=1)
    x, y = _data()

    # eager reference from the PRE-step masters, replicating step_fn's
    # forward exactly: same env substitution, same step-derived RNG key,
    # same f32 cast + loss-scale multiply
    masters = [jnp.asarray(m) for m in step.state.master_params]
    step_ctr = step.state.step

    def scaled_loss(vals):
        env = {id(p): v for p, v in zip(params, vals)}
        key = jax.random.fold_in(jax.random.PRNGKey(0), step_ctr)
        ctx = Ctx(env=env, stats_out={}, training=True, key=key)
        out = model.forward(ctx, x)
        return F.cross_entropy(out, y).astype(jnp.float32) * \
            jnp.asarray(1.0, jnp.float32)

    grads = jax.grad(scaled_loss)(masters)
    gsq = jnp.zeros((), jnp.float32)
    for g in grads:
        gsq = gsq + jnp.sum(g * g)
    ref_norm = float(jnp.sqrt(gsq))

    loss = float(step(x, y))            # drain_every=1: drains immediately
    assert np.isfinite(loss)
    (rec,) = get_registry().events("train.telemetry")
    assert rec["windows"] == 1
    assert rec["grad_norm"] == ref_norm          # bitwise, not allclose
    assert rec["loss_scale"] == 1.0
    assert rec["overflow_count"] == 0


def test_telemetry_keeps_one_compile_one_dispatch_per_window():
    """The tentpole pin: with telemetry ON and a K-microbatch window, the
    step stays one executable and one dispatch per window; the drain
    happens outside jit and keys no new program."""
    get_registry().clear_events()
    model = _mlp(din=8)
    opt = FusedSGD(list(model.parameters()), lr=0.1, momentum=0.9)
    step = make_train_step(model, opt, lambda o, t: F.cross_entropy(o, t),
                           half_dtype=jnp.bfloat16, loss_scale="dynamic",
                           accum_steps=4, accum_stacked=True,
                           telemetry=True, drain_every=2)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 4, 8)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 4, (4, 4)))

    step_cache.reset_stats()
    windows = 6
    for _ in range(windows):
        step(x, y)
    st = step_cache.stats()["by_kind"]["train_step"]
    assert st["compiles"] == 1
    assert st["dispatches"] == windows
    assert st["cache_hits"] == windows - 1

    recs = get_registry().events("train.telemetry")
    assert [r["step"] for r in recs] == [2, 4, 6]    # drain_every=2
    for r in recs:
        assert r["windows"] == 2
        assert np.isfinite(r["loss_mean"]) and np.isfinite(r["grad_norm"])
    # drained gauges track the last drain
    assert get_registry().gauge("train.grad_norm").value == \
        recs[-1]["grad_norm"]


def test_telemetry_off_leaves_state_signature_unchanged():
    """telemetry=False (the default) keeps StepState.telem=None — an
    empty pytree subtree, so signatures and checkpoints are identical to
    pre-observe builds."""
    model = _mlp()
    opt = FusedSGD(list(model.parameters()), lr=0.1)
    step = make_train_step(model, opt, lambda o, t: F.cross_entropy(o, t))
    assert step.state.telem is None
    assert step.drain_telemetry() is None
    x, y = _data()
    step(x, y)
    assert step.state.telem is None


# ---------------------------------------------------------------------------
# stall watchdog
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_watchdog_fires_on_injected_stall():
    """A chaos train.step delay stalls the dispatch loop; the watchdog
    must emit exactly one typed diagnostic carrying the last step, the
    last span, the backend, and the "backend did not respond" hint."""
    get_registry().clear_events()
    model = _mlp()
    opt = FusedSGD(list(model.parameters()), lr=0.1)
    step = make_train_step(model, opt, lambda o, t: F.cross_entropy(o, t))
    x, y = _data()
    step(x, y)                          # compile outside the timed window

    heartbeat()                         # fresh anchor for THIS test
    wd = StallWatchdog(deadline_s=0.12, poll_s=0.03)
    with wd:
        with chaos.session(seed=0) as c:
            c.on("train.step", action="delay", delay_s=0.6, at=1)
            step(x, y)                  # call 1 (fast), beats
            step(x, y)                  # call 2: delayed 0.6s -> stall
    assert len(wd.stalls) == 1          # one diagnostic per stall, not per poll
    diag = wd.stalls[0]
    assert diag["deadline_s"] == 0.12
    assert diag["since_last_step_s"] >= 0.12
    assert diag["last_step"] == 2       # heartbeats carry the call count
    assert diag["backend"] == "cpu"
    assert diag["last_span"] is not None and "span" in diag["last_span"]
    assert "backend did not respond" in diag["hint"]
    (ev,) = get_registry().events("watchdog.stall")
    assert ev["hint"] == diag["hint"]


def test_watchdog_silent_on_clean_run():
    model = _mlp()
    opt = FusedSGD(list(model.parameters()), lr=0.1)
    step = make_train_step(model, opt, lambda o, t: F.cross_entropy(o, t))
    x, y = _data()
    step(x, y)                          # compile outside the timed window

    heartbeat()
    wd = StallWatchdog(deadline_s=0.6, poll_s=0.05)
    with wd:
        t0 = time.monotonic()
        while time.monotonic() - t0 < 1.0:   # longer than the deadline
            step(x, y)                  # each dispatch beats
            time.sleep(0.05)
    assert wd.stalls == []


def test_watchdog_rejects_nonpositive_deadline():
    with pytest.raises(ValueError):
        StallWatchdog(deadline_s=0.0)


def test_observe_exports():
    """The public surface other subsystems wire against."""
    for name in ("span", "last_span", "counter", "gauge", "histogram",
                 "event", "events", "get_registry", "MetricsRegistry",
                 "StallWatchdog", "heartbeat", "last_heartbeat",
                 "StepTelemetry", "init_telemetry", "accumulate",
                 "SCHEMA_VERSION", "STALL_HINT"):
        assert hasattr(observe, name), name
