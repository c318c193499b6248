"""Share of the traced training window with no op running on the device
(1 - union of op intervals / window)."""


def read(ctx):
    if not ctx.res.get("traced_steps") or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
