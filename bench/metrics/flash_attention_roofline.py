"""The flash-attention kernels' share of their roofline in training,
forward and backward together: each forward event needs one layer's
causal attention, each backward pair (dq and dk/dv kernels) one layer's
backward."""
import flops

# A Pallas kernel's HLO instruction takes the name of the jitted function
# that calls it (checked on a v5e trace, tests/bench/data); the backward
# function makes two calls, the dq and the dk/dv kernel.
FWD = [r"^flash_attention_pallas\.\d+$"]
BWD = [r"^_flash_attention_pallas_bwd\.\d+$"]
BWD_EVENTS_PER_CALL = 2


def read(ctx):
    if not ctx.res.get("traced_steps"):
        return None
    nf, sf = ctx.trace.kernel(FWD)
    nb, sb = ctx.trace.kernel(BWD)
    if nf + nb == 0 or sf + sb <= 0:
        return None
    B, S = ctx.mix["batch"], ctx.mix["seq_len"]
    lf = flops.least_time(*flops.flash_fwd(ctx.dims, B, S, ctx.item),
                          ctx.peak)
    lb = flops.least_time(*flops.flash_bwd(ctx.dims, B, S, ctx.item),
                          ctx.peak)
    return 100.0 * (nf * lf + nb / BWD_EVENTS_PER_CALL * lb) / (sf + sb)
