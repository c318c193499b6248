"""Find a serving cell's knee: its mix at several fixed rates, one process.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 4,6,8

For each rate, a fresh session on one engine measures ``--seconds`` of
the cell's open-loop mix at that rate and prints one JSON line: TTFT and
TPOT percentiles, output tokens per second, how many of the window's
requests missed, and the queue depth when the window closed. The knee
is the highest rate whose queue does not grow through the window; the
cell's mix file then states about four fifths of it as a number. Needs
the chip, like ``run.py``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import numpy as np

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    a = ap.parse_args(argv)
    bench = run.load_bench()
    cell, conf, mix = run.cell_of(bench, a.workload)

    import jax

    if jax.devices()[0].platform != "tpu":
        run.log("sweep.py: needs a TPU")
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve import ServeEngine

    import model
    import serve
    import traffic

    enable_compile_cache()
    clock = run.CompileClock()
    cfg, dims = model.arch_of(conf), model.dims_of(conf)
    params = model.program_weights(cfg, dims)(model.key_of(a.seed))
    eng = ServeEngine(params, cfg, serve.serve_config(conf, False))
    for rate in [float(r) for r in a.rates.split(",")]:
        m = dict(mix, rate_rps=rate)
        reqs = traffic.serve_requests(m, a.seed, a.seconds, dims["V"])
        drv = serve.Driver(None, reqs, a.seconds)
        sess = eng.open_session(on_token=drv.on_token,
                                on_event=drv.on_event)
        drv.sess = sess
        serve.warm_up(sess, conf, np.random.default_rng(0))
        drv.run(0.0, m["drain_s"], clock)
        win = drv.window_recs()
        ttft = [r.first - r.due for r in win if r.first is not None]
        queued = sum(1 for r in win if r.admitted is None
                     or r.admitted > a.seconds)
        line = {"rate_rps": rate, **drv.metrics(),
                "ttft_p50_ms": 1e3 * float(np.median(ttft)),
                "window_requests": len(win), "missed": drv.failed(),
                "queued_at_close": queued,
                "mixed_steps": drv.counters1["mixed_steps"]
                - drv.counters0["mixed_steps"],
                "window_compiles": drv.window_compiles}
        print(json.dumps(line), flush=True)
        for r in drv.recs.values():
            if r.sent is not None and not r.done:
                sess.cancel(r.rid)
        sess.close()
        del sess, drv
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
