"""The training expert-FFN kernels' share of their roofline, forward and
backward together: each forward event (recomputed forwards included)
needs the least time of one layer's assignment rows; each backward
pair (the dx and dW kernels) that of one layer's backward."""
import flops

# A Pallas kernel's HLO instruction takes the name of the jitted function
# that calls it (checked on a v5e trace, tests/bench/data); the backward
# function makes two calls, the dx and the dW kernel.
FWD = [r"^_grouped_mlp_pallas_tables\.\d+$"]
BWD = [r"^_grouped_mlp_pallas_bwd\.\d+$"]
BWD_EVENTS_PER_CALL = 2


def read(ctx):
    if not ctx.res.get("traced_steps"):
        return None
    nf, sf = ctx.trace.kernel(FWD)
    nb, sb = ctx.trace.kernel(BWD)
    if nf + nb == 0 or sf + sb <= 0:
        return None
    rows = ctx.mix["batch"] * ctx.mix["seq_len"] * ctx.dims["k"]
    lf = flops.least_time(*flops.grouped_mlp_fwd(ctx.dims, rows, ctx.item),
                          ctx.peak)
    lb = flops.least_time(*flops.grouped_mlp_bwd(ctx.dims, rows, ctx.item),
                          ctx.peak)
    return 100.0 * (nf * lf + nb / BWD_EVENTS_PER_CALL * lb) / (sf + sb)
