"""The train step's share of the chip's bf16 peak: model FLOPs of the
traced steps (forward and backward, no recomputation) over
device-busy seconds."""
import flops


def read(ctx):
    n = ctx.res.get("traced_steps")
    if not n or ctx.trace.busy_s <= 0:
        return None
    f = n * flops.train_step_flops(ctx.dims, ctx.mix["batch"],
                                   ctx.mix["seq_len"])
    return 100.0 * f / (ctx.trace.busy_s * ctx.peak["flops_per_s"])
