"""Readers of the program's cache-row counters: what one layer of each
kind read in a tick's decode dispatch, which ``ServeEngine`` puts on the
tick's ``serve.step`` record where the model has layers that read a
window of keys (docs/observability.md: ``kv_rows_full``, the sum of the
sessions' depths; ``kv_rows_window``, the sum of the depths cut at the
window; ``kv_layers_full`` and ``kv_layers_window``, the layers of each
kind; ``kv_window``).  A record is joined to the harness's tick by the
tick's ``t0`` .. ``t1``, as ``readers/moe.py`` joins the routed
counters, which ride along where the record has them.  A program that
keeps no such counters gives every reader here nothing to read.
"""
import os

from pb import cells

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_FIELDS = ("kv_rows_full", "kv_rows_window", "kv_layers_full",
           "kv_layers_window", "kv_window")
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
            for tk, r in zip(ticks, recs) if "kv_rows_full" in r]


def rows_read_share(ctx):
    """Rows a window layer read over rows a full layer read, the mean
    over the window's decode ticks: how hard the band bites (1 where no
    session is deeper than the window)."""
    ticks = [tk for tk in counted_ticks(ctx, "decode_step")
             if tk["kv_rows_full"]]
    if not ticks:
        return None
    return sum(tk["kv_rows_window"] / tk["kv_rows_full"]
               for tk in ticks) / len(ticks)


def kernel_roofline(ctx, ops, per, flops, nbytes):
    """``roofline.kernel_roofline`` with the family's counts fed each
    tick's own counters: the rows its layers of each kind read, not the
    depths alone."""
    ticks = counted_ticks(ctx, per)
    if not ticks:
        return None
    joined = dict(ctx, counters=dict(ctx["counters"], ticks=ticks))
    return _reader("roofline").kernel_roofline(joined, ops, per, flops,
                                               nbytes)
