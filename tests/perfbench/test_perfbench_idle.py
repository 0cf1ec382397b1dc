"""The reader that splits the device's idle time by what the host was
doing (``perfbench/readers/idle.py``), on hand-made records and synthetic
operation intervals, and the bounds ``tools/idle_split.py`` puts on how
much earlier the device plane stamps a program than the host plane."""
import os
import sys

import pytest

import pb_tiny  # noqa: F401  (puts perfbench/ on sys.path)
from pb import cells

sys.path.insert(0, os.path.join(pb_tiny.BENCH, "tools"))
import idle_split  # noqa: E402

US = 1_000          # ns
CLOCK = 7_000_000   # the program's clock_ns: perf_counter + CLOCK = host clock
ZERO = 3_000_000    # the trace's zero on the host clock (start + offset)


def _idle():
    return cells._module_at(cells.REPO, "readers", "idle")


def _tick(ids, tick, at, parts, clock=CLOCK, decode=True):
    """A ``serve.step`` record at ``at`` us, 100 us long, and its children
    ``[(label, t0, t1, [grandchildren])]`` in us from the tick's start."""
    root = {"span": "serve.step", "id": next(ids), "parent": None,
            "tick": tick, "t0_ns": at * US, "t1_ns": (at + 100) * US,
            "clock_ns": clock, "decode_batch": 16 if decode else 0}
    recs = [root]

    def lay(label, a, b, sub, parent):
        name, _, tag = label.partition(":")
        rec = {"span": name, "id": next(ids), "parent": parent["id"],
               "tick": tick, "t0_ns": (at + a) * US, "t1_ns": (at + b) * US}
        if name == "dispatch":
            rec["kind"] = tag
        elif name == "serve.fetch":
            rec["what"] = tag
        recs.append(rec)
        for s in sub:
            lay(*s, rec)
    for p in parts:
        lay(*p, root)
    harness = {"t0": (at - 1) / 1e6, "t1": (at + 101) / 1e6,
               "dispatches": ["decode_step"] if decode else []}
    return recs, harness


def _parts(gc_in_dispatch=False):
    inner = [("host.gc", 30, 35, [])] if gc_in_dispatch else []
    return [("serve.pack", 10, 20, []),
            ("dispatch:decode_step", 20, 50, inner),
            ("serve.fetch:tokens", 50, 90, []),
            ("serve.commit", 90, 98, [])]


def _op(a, b, shift=0):
    """A device operation from ``a`` to ``b`` us of the program's clock,
    on the trace's clock."""
    return ("fusion", (a * US + CLOCK - ZERO) + shift, (b - a) * US)


@pytest.fixture
def two_ticks(monkeypatch):
    """Tick 1 at 1000 us: the device runs 40 .. 80 (a dispatch with a
    collection inside it); tick 2 at 1110 us, 10 us after tick 1 ended:
    the device runs 60 .. 85, so one gap runs from the dispatch into
    the fetch."""
    from apex_tpu.observe import spans
    ids = iter(range(1, 100))
    r1, h1 = _tick(ids, 1, 1000, _parts(gc_in_dispatch=True))
    r2, h2 = _tick(ids, 2, 1110, _parts())
    records = sorted(r1 + r2, key=lambda r: r["t0_ns"])
    monkeypatch.setattr(spans, "recorded", lambda since_ns=None: records,
                        raising=False)
    ops = [_op(1040, 1080), _op(1170, 1195)]
    return {"counters": {"ticks": [h1, h2]},
            "trace": {"ops": ops, "start_ns": ZERO - 400 * US,
                      "device_offset_ns": 400 * US}}, records


def test_idle_is_split_by_interval_and_the_innermost_record(two_ticks,
                                                            capsys):
    ctx, _ = two_ticks
    sp = _idle().split(ctx)
    (_, t1), (_, t2) = sp["ticks"]
    # tick 1: pack 10, the dispatch 20 less the collection's 5 inside it,
    # the fetch's tail 10 after the device stopped, commit 8, and the
    # tick's own 10 before pack and 2 after commit
    assert t1 == pytest.approx({
        "serve.step self": 12 * US, "serve.pack": 10 * US,
        "dispatch.decode_step": 15 * US, "host.gc": 5 * US,
        "serve.fetch.tokens": 10 * US, "serve.commit": 8 * US,
        "outside the engine": 10 * US})
    # tick 2: one gap from the dispatch's start (20) to the device's (60)
    # crosses into the fetch: 30 to the dispatch, 10 to the fetch, and
    # the fetch's tail from 85 to 90 besides
    assert t2["dispatch.decode_step"] == pytest.approx(30 * US)
    assert t2["serve.fetch.tokens"] == pytest.approx(15 * US)
    assert "host.gc" not in t2 and "outside the engine" not in t2
    out = capsys.readouterr().out
    (line,) = [ln for ln in out.splitlines() if "[perfbench idle]" in ln]
    assert "2 ticks" in line and "host.gc 0.0000" in line


def test_the_parts_sum_to_the_idle_time(two_ticks, capsys):
    ctx, _ = two_ticks
    sp = _idle().split(ctx)
    total = sum(sp["labels"].values())
    # from tick 1's start to tick 2's end: 210 us, of which 40 + 25 busy
    assert sp["span_ns"] == pytest.approx(210 * US)
    assert sp["idle_ns"] == pytest.approx(145 * US)
    assert total == pytest.approx(sp["idle_ns"])
    assert "100.00% of it" in capsys.readouterr().out


def test_the_three_medians(two_ticks):
    ctx, _ = two_ticks
    idle = _idle()
    # dispatch 15 and 30; fetch 10 and 15; host (self, pack, the
    # collection, commit) 12 + 10 + 5 + 8 and 12 + 10 + 8: a median of
    # two is their mean
    assert idle.decode_idle_ms(ctx, "dispatch") == pytest.approx(0.0225)
    assert idle.decode_idle_ms(ctx, "fetch") == pytest.approx(0.0125)
    assert idle.decode_idle_ms(ctx, "host") == pytest.approx(0.0325)


def test_clock_ns_puts_each_tick_on_the_operations_clock(two_ticks):
    """A tick's records move with its own ``clock_ns``: the same tick
    read on a clock 15 us later sees the device 15 us earlier."""
    ctx, records = two_ticks
    idle = _idle()
    before = idle.split(ctx)["ticks"][1][1]
    for r in records:
        if r["span"] == "serve.step" and r["tick"] == 2:
            r["clock_ns"] += 15 * US
    ctx.pop("idle_split")
    after = idle.split(ctx)["ticks"][1][1]
    # the device now runs 45 .. 70 of tick 2: 5 us of the dispatch's
    # idle goes (it ends at 50), 5 us more of the fetch's tail comes
    assert after["dispatch.decode_step"] == pytest.approx(
        before["dispatch.decode_step"] - 5 * US)
    assert after["serve.fetch.tokens"] == pytest.approx(
        before["serve.fetch.tokens"] + 5 * US)
    # shifting the operations and every clock alike changes nothing
    for r in records:
        if r["span"] == "serve.step":
            r["clock_ns"] += 1_000 * US
    ctx["trace"]["start_ns"] += 1_000 * US
    ctx.pop("idle_split")
    assert idle.split(ctx)["ticks"][1][1] == pytest.approx(after)


@pytest.mark.parametrize("part", ["dispatch", "fetch", "host"])
@pytest.mark.parametrize("missing", ["clock_ns", "trace", "zero",
                                     "decode_tick"])
def test_nothing_to_read_is_none(two_ticks, part, missing):
    ctx, records = two_ticks
    if missing == "clock_ns":          # a parent commit's records
        for r in records:
            r.pop("clock_ns", None)
    elif missing == "trace":
        ctx["trace"] = None
    elif missing == "zero":            # the harness's reduction today
        del ctx["trace"]["device_offset_ns"]
    else:
        for tk in ctx["counters"]["ticks"]:
            tk["dispatches"] = ["prefill_step"]
    assert _idle().decode_idle_ms(ctx, part) is None


def test_between_ticks_is_outside_the_engine(monkeypatch):
    """The harness's work between two ``serve.step`` records, the last
    tick's included only up to its own end."""
    from apex_tpu.observe import spans
    ids = iter(range(1, 100))
    r1, h1 = _tick(ids, 1, 0, [])
    r2, h2 = _tick(ids, 2, 400, [], decode=False)
    records = r1 + r2
    monkeypatch.setattr(spans, "recorded", lambda since_ns=None: records,
                        raising=False)
    ctx = {"counters": {"ticks": [h1, h2]},
           "trace": {"ops": [_op(50, 60)], "start_ns": ZERO,
                     "device_offset_ns": 0}}
    sp = _idle().split(ctx)
    assert sp["labels"] == pytest.approx({
        "serve.step self": 190 * US, "outside the engine": 300 * US})


@pytest.mark.parametrize("enq_end,done,want", [
    ((5, 25), (40, 95), (5, 10)),      # bounded on both sides
    ((5, 25), (40, 70), None),         # the bounds cross
    ((5,), (40,), None),               # a program the host did not see
])
def test_the_device_offset_is_bounded_by_the_runtime(enq_end, done, want):
    """Programs at 0 .. 30 and 20 .. 80 on the device plane; each enqueue
    ends before its program starts and each completion is signalled
    after it ends, on the host plane."""
    modules = [("jit_fn(1)", 0, 30), ("jit_fn(2)", 20, 60)]
    enqueued = [(e - 1, 1) for e in enq_end]
    signalled = [(d, 1) for d in done]
    assert idle_split.device_offset(modules, enqueued, signalled) == want
