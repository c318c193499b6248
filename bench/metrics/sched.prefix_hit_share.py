"""Share of the window's prompt tokens served from the prefix cache:
the engine's prefix_hit_tokens over prompt_tokens, both taken as
differences across the window (they count from the session's start)."""


def read(ctx):
    drv = ctx.res.get("driver")
    if drv is None:
        return None
    a, b = drv.counters0, drv.counters1
    prompt = b["prompt_tokens"] - a["prompt_tokens"]
    if prompt <= 0:
        return None
    return 100.0 * (b["prefix_hit_tokens"] - a["prefix_hit_tokens"]) / prompt
