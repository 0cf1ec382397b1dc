"""The train step's flash-attention kernels by the names they have in
the trace, whatever the layout of their operands."""
from pb import trace
from pb.counts import roofline_seconds


def flash_attn_kernels_roofline(ctx, ops):
    """Causal attention of the train step, forward and backward: the
    least time of what the family counts a step (``flash_attn_flops_train``
    and ``flash_attn_bytes_train``), over the window's steps, against the
    device time of the operations named in ``ops`` (``pb.trace.op_family``
    of an ``XLA Ops`` event: its name without the trailing number), which
    are the kernels wherever their operands lie.  An attention that XLA
    implements as fusions carries none of the names: then nothing is
    read."""
    tr = ctx.get("trace")
    if not tr or not tr.get("ops") or not ctx.get("peaks"):
        return None
    seconds = sum(dur for name, _, dur in tr["ops"]
                  if trace.op_family(name) in ops) / 1e9
    if seconds <= 0:
        return None
    family, cfg, mix = ctx["family"], ctx["cfg"], ctx["mix"]
    steps, chips = ctx["counters"]["steps"], ctx["counters"]["chips"]
    shape = cfg, mix["global_batch"] // chips, mix["seq_len"]
    least = roofline_seconds(family.flash_attn_flops_train(*shape),
                             family.flash_attn_bytes_train(*shape),
                             ctx["peaks"])[0]
    return 100.0 * least * steps / seconds
