"""Whole-step shares of the chip's peak: model FLOPs the algorithm
needs (the cell's family counts them) over the time the step had, over
the peak."""


def train_mfu(ctx):
    """Forward + backward FLOPs a token x tokens a second over the
    window, over chips x peak, in %."""
    c = ctx["counters"]
    if not ctx["peaks"]:
        return None
    flops = ctx["family"].train_flops_per_token(ctx["cfg"],
                                                ctx["mix"]["seq_len"])
    return 100.0 * flops * c["tokens_per_s"] / (
        c["chips"] * ctx["peaks"]["bf16_flops_per_s"])


def decode_step_mfu(ctx):
    """Model FLOPs of the tokens decoded in the window (every matmul
    parameter twice a token, attention over each session's live depth)
    over the window's seconds and the peak, in %."""
    ticks = [tk for tk in ctx["counters"].get("ticks", [])
             if "decode_step" in tk["dispatches"]]
    if not ticks or not ctx["peaks"]:
        return None
    flops = sum(ctx["family"].decode_step_flops(ctx["cfg"], tk)
                for tk in ticks)
    return 100.0 * flops / ctx["window_s"] / ctx["peaks"]["bf16_flops_per_s"]
