"""Readers of the program's state-space counters: what the state-space
layers of a tick's decode dispatch read and wrote, which ``ServeEngine``
puts on the tick's ``serve.step`` record where the model keeps a state of
a session (docs/observability.md: ``ssm_sessions``, the live rows of the
dispatch; ``ssm_layers``, the layers that keep a state; ``ssm_state_bytes``,
what one such layer reads and writes for those sessions, both
directions).  A record is joined to the harness's tick by the tick's
``t0`` .. ``t1``, as ``readers/moe.py`` joins the routed counters, which
ride along where the record has them.  A program that keeps no such
counters gives every reader here nothing to read.
"""
import os

from pb import cells

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_FIELDS = ("ssm_sessions", "ssm_layers", "ssm_state_bytes")
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
            for tk, r in zip(ticks, recs) if "ssm_state_bytes" in r]


def kernel_roofline(ctx, ops, per, flops, nbytes):
    """``roofline.kernel_roofline`` with the family's counts fed each
    tick's own counters: the states its live sessions' layers moved, not
    the batch bucket's."""
    ticks = counted_ticks(ctx, per)
    if not ticks:
        return None
    joined = dict(ctx, counters=dict(ctx["counters"], ticks=ticks))
    return _reader("roofline").kernel_roofline(joined, ops, per, flops,
                                               nbytes)
