"""95th percentile of a window request's wait from its due time to its
``admitted`` event (host clock): time spent queued before the scheduler
gave it a slot and blocks."""
import numpy as np


def read(ctx):
    drv = ctx.res.get("driver")
    if drv is None:
        return None
    waits = [r.admitted - r.due for r in drv.window_recs()
             if r.admitted is not None]
    return 1e3 * float(np.percentile(waits, 95)) if waits else None
