"""The paged decode-attention kernel's share of its roofline: the least
time to read every live cached key and value of each traced tick's
decode rows, once per layer, over the device time of its events."""
import flops

# A Pallas kernel's HLO instruction takes the name of the jitted function
# that calls it (checked on a v5e trace, tests/bench/data).
PATTERNS = [r"^paged_decode_attention_pallas\.\d+$"]


def read(ctx):
    drv = ctx.res.get("driver")
    if drv is None or not drv.traced:
        return None
    n, secs = ctx.trace.kernel(PATTERNS)
    if n == 0 or secs <= 0:
        return None
    L = ctx.dims["L"]
    least = sum(L * flops.least_time(*flops.decode_attention(
        ctx.dims, t.dec_ctx, ctx.item), ctx.peak)
        for t in drv.traced if t.dec_ctx)
    return 100.0 * least / secs
