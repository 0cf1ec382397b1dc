"""Readers of the device trace."""
from pb import trace as _trace


def idle_share(ctx):
    """Share of the traced window in which no operation ran, in %."""
    tr = ctx.get("trace")
    if not tr or not tr.get("n_devices"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def step_device_ms(ctx, kinds):
    """Device milliseconds of one execution of the program of one of
    ``kinds`` (the executor's names for it): its executions' summed
    device time over their number."""
    found = _trace.time_by_kind(ctx) or {}
    for kind in kinds:
        if kind in found:
            return 1e3 * found[kind]["seconds"] / found[kind]["n"]
    return None
