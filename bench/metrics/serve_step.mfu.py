"""The serve step's share of the chip's bf16 peak over the traced ticks:
the model FLOPs of the live rows those ticks ran (decode rows at their
cache lengths, prefill tokens at the mean position of the window's
prompts, the LM head over sampled rows) over device-busy seconds."""
import flops
from serve_counts import tick_work


def read(ctx):
    drv = ctx.res.get("driver")
    if drv is None or not drv.traced or ctx.trace.busy_s <= 0:
        return None
    f = sum(flops.serve_flops(ctx.dims, w["rows"], w["ctx_sum"],
                              w["head_rows"])
            for w in tick_work(drv))
    return 100.0 * f / (ctx.trace.busy_s * ctx.peak["flops_per_s"])
