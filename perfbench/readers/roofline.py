"""Kernels' shares of their roofline: the least time the chip could take
for the work the algorithm needs (the cell's family counts it) over the
device time the trace shows for it, in %.  A reader that finds nothing
to read returns nothing."""
from pb import trace
from pb.counts import roofline_seconds


def _ticks_of(ctx, kind):
    return [tk for tk in ctx["counters"].get("ticks", [])
            if kind in tk["dispatches"]]


def decode_step_roofline(ctx):
    """Least bytes a decode step needs (weights once, the batch's live
    KV once, the new rows) over the peak bandwidth, over the step's
    device time."""
    kinds = trace.time_by_kind(ctx)
    ticks = _ticks_of(ctx, "decode_step")
    if not kinds or "decode_step" not in kinds or not ticks:
        return None
    family, cfg = ctx["family"], ctx["cfg"]
    least = 0.0
    for tk in ticks:
        least += roofline_seconds(family.decode_step_flops(cfg, tk),
                                  family.decode_step_bytes(cfg, tk),
                                  ctx["peaks"])[0]
    return 100.0 * least / kinds["decode_step"]["seconds"]


def kernel_roofline(ctx, ops, per, flops, nbytes):
    """A kernel by the name it has in the trace.  ``ops``: the device
    operations that are the kernel (``pb.trace.op_family`` of an
    ``XLA Ops`` event: its name without the trailing number); ``per``:
    the dispatch kind whose ticks each run it; ``flops`` and ``nbytes``:
    the family's functions ``(cfg, tick)`` that count what the kernel
    has to compute and move in one such tick.  The least time of the
    window's ticks over the device time of the window's ``ops``."""
    tr = ctx.get("trace")
    ticks = _ticks_of(ctx, per)
    if not tr or not tr.get("ops") or not ticks or not ctx["peaks"]:
        return None
    seconds = sum(dur for name, _, dur in tr["ops"]
                  if trace.op_family(name) in ops) / 1e9
    if seconds <= 0:
        return None
    family, cfg = ctx["family"], ctx["cfg"]
    count_flops, count_bytes = getattr(family, flops), getattr(family, nbytes)
    least = sum(roofline_seconds(count_flops(cfg, tk), count_bytes(cfg, tk),
                                 ctx["peaks"])[0] for tk in ticks)
    return 100.0 * least / seconds
