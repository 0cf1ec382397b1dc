"""Readers of the program's routed-expert counters: what each tick's
programs counted in their routed layers, which ``ServeEngine`` puts on
the tick's ``serve.step`` record (docs/observability.md: ``moe_pairs``,
the token-expert pairs that went to experts held here; ``moe_experts_hit``,
how often a held expert's matrices had to be streamed; ``moe_pairs_max``,
the most pairs one expert got; ``moe_layers`` x ``moe_held``, the experts
there are).  A record is joined to the harness's tick by the tick's
``t0`` .. ``t1``, as ``readers/spans.py`` joins them.  A program that
keeps no such counters gives every reader here nothing to read.
"""
import os

from pb import cells

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_FIELDS = ("moe_pairs", "moe_experts_hit", "moe_pairs_max", "moe_layers",
           "moe_held")


def _reader(name):
    return cells._module_at(_REPO, "readers", name)


def counted_ticks(ctx, kind: str) -> list:
    """The window's ticks that dispatched ``kind``, each with its
    record's counters beside its own keys; a tick whose record has none
    is left out."""
    spans = _reader("spans")
    ticks = [tk for tk in ctx["counters"].get("ticks", [])
             if kind in tk["dispatches"]]
    recs = spans.window_ticks(ctx, kind)
    if len(recs) != len(ticks):
        return []
    return [dict(tk, **{f: r[f] for f in _FIELDS})
            for tk, r in zip(ticks, recs) if "moe_pairs" in r]


def pairs_per_step(ctx):
    """Token-expert pairs a decode tick's programs sent to held experts,
    all routed layers together: the mean over the window's ticks."""
    ticks = counted_ticks(ctx, "decode_step")
    if not ticks:
        return None
    return sum(tk["moe_pairs"] for tk in ticks) / len(ticks)


def load_max_over_mean(ctx):
    """The most pairs one held expert got in a tick over the mean a held
    expert got in it: the mean over the window's ticks (1 is a perfectly
    even router)."""
    ticks = [tk for tk in counted_ticks(ctx, "decode_step")
             if tk["moe_pairs"]]
    if not ticks:
        return None
    return sum(tk["moe_pairs_max"] * tk["moe_layers"] * tk["moe_held"]
               / tk["moe_pairs"] for tk in ticks) / len(ticks)


def kernel_roofline(ctx, ops, per, flops, nbytes):
    """``roofline.kernel_roofline`` with the family's counts fed each
    tick's own counters: the matrices of the experts that were hit and
    the pairs that went through them, not an average."""
    ticks = counted_ticks(ctx, per)
    if not ticks:
        return None
    joined = dict(ctx, counters=dict(ctx["counters"], ticks=ticks))
    return _reader("roofline").kernel_roofline(joined, ops, per, flops,
                                               nbytes)
