"""Kernels' shares of their roofline: the least time the chip could take
for the work the algorithm needs (``pb.counts``) over the device time
the trace shows for it, in %.  A reader that finds nothing to read
returns nothing."""
from pb import counts, trace


def decode_step_roofline(ctx):
    """Least bytes a decode step needs (weights once, the batch's live
    KV once, the new rows) over the peak bandwidth, over the step's
    device time."""
    kinds = trace.time_by_kind(ctx)
    ticks = [tk for tk in ctx["counters"].get("ticks", [])
             if "decode_step" in tk["dispatches"]]
    if not kinds or "decode_step" not in kinds or not ticks:
        return None
    least = 0.0
    for tk in ticks:
        nbytes = counts.decode_step_min_bytes(
            ctx["cfg"], tk["kv_tokens"], tk["decode_batch"])
        flops = counts.forward_flops(ctx["cfg"], tk["decode_batch"],
                                     tk["kv_tokens"])
        least += counts.roofline_seconds(flops, nbytes, ctx["peaks"])[0]
    return 100.0 * least / kinds["decode_step"]["seconds"]


def flash_attn_roofline(ctx):
    """Causal attention of the train step, forward and backward: the
    custom calls in the trace whose operands have the step's
    (batch x heads, sequence, head size) shape.  An attention that XLA
    implements as fusions carries no name to find it by (PERF.md, open
    questions): then nothing is read."""
    tr = ctx.get("trace")
    if not tr or not tr.get("ops"):
        return None
    cfg, mix, c = ctx["cfg"], ctx["mix"], ctx["counters"]
    rows = mix["global_batch"] // c["chips"]
    d = cfg["n_embd"] // cfg["n_head"]
    shape = f"[{rows * cfg['n_head']},{mix['seq_len']},{d}]"
    seconds = sum(dur for name, _, dur in tr["ops"]
                  if "custom-call" in name and shape in name) / 1e9
    if seconds <= 0:
        return None
    flops = counts.flash_attn_flops_train(cfg, rows, mix["seq_len"])
    nbytes = counts.flash_attn_bytes_train(cfg, rows, mix["seq_len"])
    least = counts.roofline_seconds(flops, nbytes, ctx["peaks"])[0]
    return 100.0 * least * c["steps"] / seconds
