"""Readers of the program's residual-stream counters and of the mHC
kernels in the device trace.  Where the model's residual is several
streams, ``ServeEngine`` puts on a tick's ``serve.step`` record what the
tick's decode dispatch moves through the mappings and mixes
(docs/observability.md: ``hc_rows``, its live rows; ``hc_sublayers``, the
sublayers that mix).  A record is joined to the harness's
tick by the tick's ``t0`` .. ``t1``, as ``readers/ssm.py`` joins its own,
the routed counters riding along where the record has them.

Both metrics read the kernels' operations inside the executions of the
decode program alone (a prefill chunk's mappings are no decode tick's),
found by name in the trace and placed in a program's execution by their
start.  A program that keeps no such counters, or a trace with none of
the operations, gives every reader here nothing to read.
"""
import bisect
import os

from pb import cells, trace
from pb.counts import roofline_seconds

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_FIELDS = ("hc_rows", "hc_sublayers")
_ALSO = ("moe_pairs", "moe_experts_hit")


def _reader(name):
    return cells._module_at(_REPO, "readers", name)


def counted_ticks(ctx, kind: str) -> list:
    """The window's ticks that dispatched ``kind``, each with its
    record's counters beside its own keys; a tick whose record has none
    is left out."""
    ticks = [tk for tk in ctx["counters"].get("ticks", [])
             if kind in tk["dispatches"]]
    recs = _reader("spans").window_ticks(ctx, kind)
    if len(recs) != len(ticks):
        return []
    return [dict(tk, **{f: r[f] for f in _FIELDS + _ALSO if f in r})
            for tk, r in zip(ticks, recs) if "hc_rows" in r]


def _executions(ctx, kind):
    """``[(start, end)]`` ns of the trace's executions of the program
    ``kind``, matched to the harness's dispatch log in time order (as
    ``pb.trace.module_time_by_kind`` matches them), or None."""
    tr, c = ctx.get("trace"), ctx["counters"]
    if not tr or not tr.get("modules") or "dispatch_log" not in c:
        return None
    mods = sorted((m for m in tr["modules"]
                   if m[0].startswith(tuple(c["module_prefixes"]))),
                  key=lambda m: m[1])
    if not mods or len(mods) != len(c["dispatch_log"]):
        return None
    return [(s, s + d) for (_, s, d), k in zip(mods, c["dispatch_log"])
            if k == kind]


def _ops_within(ctx, ops, spans) -> float:
    """Seconds of the operations named ``ops`` that start inside one of
    ``spans`` (sorted, disjoint)."""
    starts = [s for s, _ in spans]
    total = 0.0
    for name, s, d in ctx["trace"].get("ops") or []:
        if trace.op_family(name) not in ops:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < spans[i][1]:
            total += d
    return total / 1e9


def kernel_roofline(ctx, ops, per, flops, nbytes):
    """The least time of the window's ``per`` ticks' mappings and mixes
    (the family's ``flops`` and ``nbytes`` of each tick, fed its own
    counters) over the device time of the operations named ``ops``
    inside the executions of ``per``, in %."""
    ticks = counted_ticks(ctx, per)
    spans = _executions(ctx, per)
    if not ticks or not spans or not ctx["peaks"]:
        return None
    seconds = _ops_within(ctx, ops, spans)
    if seconds <= 0:
        return None
    family, cfg = ctx["family"], ctx["cfg"]
    count_flops, count_bytes = getattr(family, flops), getattr(family, nbytes)
    least = sum(roofline_seconds(count_flops(cfg, tk), count_bytes(cfg, tk),
                                 ctx["peaks"])[0] for tk in ticks)
    return 100.0 * least / seconds


def step_share(ctx, ops, per):
    """The device time of the operations named ``ops`` inside the
    executions of ``per`` over those executions' own, in %."""
    spans = _executions(ctx, per)
    if not spans or not (ctx.get("trace") or {}).get("ops"):
        return None
    seconds = _ops_within(ctx, ops, spans)
    if seconds <= 0:
        return None
    return 100.0 * seconds / (sum(e - s for s, e in spans) / 1e9)
