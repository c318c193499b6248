"""Share of the traced serving window with no op running on the device
(1 - union of op intervals / window)."""


def read(ctx):
    if ctx.res.get("driver") is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
