"""Readers of the program's own span records (``apex_tpu.observe``):
what the host did inside a serve tick and on the train input path.

The records are on the harness's clock (``time.perf_counter``, in ns),
so a serve tick of the window is the ``serve.step`` record that lies
inside the harness's ``t0`` .. ``t1`` of that tick.  A program that
keeps no such records (a parent commit) gives every reader nothing to
read.  The first reader called also prints, as information, one line
per span name and the tree of the longest tick since the window opened.
"""
from pb import sut  # noqa: F401  (puts the checkout's root on sys.path)
from pb.runenv import percentile


def _records(ctx) -> list:
    """Every span record in memory, oldest first; read (and reported)
    once a run, with ``ctx["span_children"]`` beside it."""
    if "span_records" not in ctx:
        from apex_tpu.observe import spans
        recorded = getattr(spans, "recorded", None)
        ctx["span_records"] = recorded() if recorded else []
        ctx["span_children"] = children_of(ctx["span_records"])
        _report(ctx)
    return ctx["span_records"]


def _ms(rec) -> float:
    return (rec["t1_ns"] - rec["t0_ns"]) / 1e6


def _label(rec) -> str:
    name = rec["span"]
    for key in ("kind", "what"):
        if rec.get(key) is not None:
            name += f".{rec[key]}"
    return name


def children_of(records) -> dict:
    """``{id: [child records, by start]}``."""
    kids = {}
    for r in records:
        if r.get("parent") is not None:
            kids.setdefault(r["parent"], []).append(r)
    return kids


def self_ms(rec, kids) -> float:
    """What no child covers (children of one thread do not overlap)."""
    return _ms(rec) - sum(_ms(k) for k in kids.get(rec["id"], []))


def tree_line(rec, kids) -> str:
    """``name ms (child ms (...), child ms, self ms)``."""
    mine = kids.get(rec["id"], [])
    if not mine:
        return f"{_label(rec)} {_ms(rec):.3f}"
    inner = ", ".join([tree_line(k, kids) for k in mine]
                      + [f"self {self_ms(rec, kids):.3f}"])
    return f"{_label(rec)} {_ms(rec):.3f} ({inner})"


def window_ticks(ctx, kind: str) -> list:
    """The ``serve.step`` record of each tick of the window that
    dispatched ``kind``, matched by the harness's tick times."""
    steps = [r for r in _records(ctx) if r["span"] == "serve.step"]
    out, i = [], 0
    for tk in ctx["counters"].get("ticks", []):
        lo, hi = tk["t0"] * 1e9, tk["t1"] * 1e9
        while i < len(steps) and steps[i]["t0_ns"] < lo:
            i += 1
        if i < len(steps) and steps[i]["t1_ns"] <= hi \
                and kind in tk["dispatches"]:
            out.append(steps[i])
    return out


def under_ms(rec, kids, span: str) -> float:
    """Summed time of the records named ``span`` below ``rec``."""
    return sum(_ms(k) if k["span"] == span else under_ms(k, kids, span)
               for k in kids.get(rec["id"], []))


def tick_ms(ctx, kind, part):
    """Median over the window's ticks that dispatched ``kind`` of one
    part of the tick: ``fetch``, the summed ``serve.fetch`` time (the
    host waiting for the device), or ``host``, the rest of
    ``serve.step`` (the host's own work, during which a synchronous
    engine leaves the device empty)."""
    ticks = window_ticks(ctx, kind)
    if not ticks:
        return None
    kids = ctx["span_children"]
    waits = [under_ms(r, kids, "serve.fetch") for r in ticks]
    if part == "fetch":
        return percentile(waits, 50)
    return percentile([_ms(r) - w for r, w in zip(ticks, waits)], 50)


def step_span_ms(ctx, span, kind=None):
    """Median of the last ``steps`` records named ``span`` (of ``kind``
    where given): the window's, one a step."""
    n = ctx["counters"].get("steps")
    recs = [r for r in _records(ctx)
            if r["span"] == span and (kind is None or r.get("kind") == kind)]
    if not n or len(recs) < n:
        return None
    return percentile([_ms(r) for r in recs[-n:]], 50)


def longest_tick_line(ticks):
    """For an untraced run: the span tree of the longest of ``ticks``
    (the harness's tick records), so that a run which stood still names
    the span it stood in.  ``None`` where the program keeps no records
    or the ring has lost that tick."""
    from apex_tpu.observe import spans
    recorded = getattr(spans, "recorded", None)
    if not ticks or recorded is None:
        return None
    tk = max(ticks, key=lambda t: t["t1"] - t["t0"])
    lo, hi = tk["t0"] * 1e9, tk["t1"] * 1e9
    recs = [r for r in recorded(int(lo))
            if lo <= r["t0_ns"] and r["t1_ns"] <= hi]
    root = next((r for r in recs if r["span"] == "serve.step"), None)
    if root is None:
        return None
    return tree_line(root, children_of(recs))


def _say(msg: str) -> None:
    print(f"[perfbench spans] {msg}", flush=True)


def _report(ctx) -> None:
    records = ctx["span_records"]
    by_name = {}
    for r in records:
        by_name.setdefault(_label(r), []).append(_ms(r))
    for name in sorted(by_name):
        xs = by_name[name]
        _say(f"{name}: {len(xs)} in the run, p50 {percentile(xs, 50):.3f} "
             f"p99 {percentile(xs, 99):.3f} max {max(xs):.3f} ms")
    ticks = ctx["counters"].get("ticks")
    if not ticks:
        return
    opened = ticks[0]["t0"] * 1e9
    steps = [r for r in records
             if r["span"] == "serve.step" and r["t0_ns"] >= opened]
    if not steps:
        return
    kids = ctx["span_children"]
    decode = [r for r in steps if r.get("decode_batch")]
    if decode:
        _say(f"serve.step self time (no child covers it): p50 "
             f"{percentile([self_ms(r, kids) for r in decode], 50):.3f} "
             f"max {max(self_ms(r, kids) for r in decode):.3f} ms over "
             f"{len(decode)} decode ticks since the window opened")
    longest = max(steps, key=_ms)
    _say(f"longest tick of {len(steps)} since the window opened: tick "
         f"{longest.get('tick')}, decode_batch "
         f"{longest.get('decode_batch')}, prefill_rid "
         f"{longest.get('prefill_rid')}: {tree_line(longest, kids)}")
