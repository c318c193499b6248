"""The serving expert-FFN kernel's share of its roofline: the least time
of its calls (live assignment rows of each traced tick, once per layer)
over the device time of its events."""
import flops
from serve_counts import tick_work

# A Pallas kernel's HLO instruction takes the name of the jitted function
# that calls it (checked on a v5e trace, tests/bench/data).
PATTERNS = [r"^_grouped_mlp_pallas_tables\.\d+$"]


def read(ctx):
    drv = ctx.res.get("driver")
    if drv is None or not drv.traced:
        return None
    n, secs = ctx.trace.kernel(PATTERNS)
    if n == 0 or secs <= 0:
        return None
    L, k = ctx.dims["L"], ctx.dims["k"]
    least = sum(L * flops.least_time(*flops.grouped_mlp_fwd(
        ctx.dims, w["rows"] * k, ctx.item), ctx.peak)
        for w in tick_work(drv) if w["rows"])
    return 100.0 * least / secs
