"""Readers of what the harness and the program count."""

def compiles_in_window(ctx):
    return float(ctx["counters"]["compiles_in_window"])


def peak_hbm_gb(ctx):
    return ctx["peak"]["total"] / 1e9


def decode_batch_mean(ctx):
    """Sessions per decode dispatch, over the window's ticks."""
    xs = [tk["decode_batch"] for tk in ctx["counters"].get("ticks", [])
          if "decode_step" in tk["dispatches"]]
    return sum(xs) / len(xs) if xs else None
