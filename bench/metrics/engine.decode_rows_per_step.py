"""Live decode rows per mixed step over the window: tokens delivered by
decode rows (every delivered token but each request's first, which a
chunk lane samples) over the engine's mixed steps in the window."""


def read(ctx):
    drv = ctx.res.get("driver")
    if drv is None:
        return None
    steps = drv.counters1["mixed_steps"] - drv.counters0["mixed_steps"]
    firsts = sum(1 for r in drv.recs.values()
                 if r.first is not None and r.first <= drv.seconds)
    if steps <= 0:
        return None
    return (drv.window_tokens - firsts) / steps
