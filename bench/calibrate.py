"""Readings that set the limits of ``correct``, many seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed: one run of the cell as ``run.py`` makes it (a short window
at the cell's own load), with the numbers it compares; beside them the
control, the plain reference in the program's place computed in the next
precision down (bfloat16 for the configuration's float32), read on the
same prompts and served tokens, or the same training batches; and, for a
training cell, the reference with half of each batch left out. The
control and the fault are judged by the cell's limits, as a run is: one
JSON line per seed on standard output, and a last line that says whether
every sound run came out correct and every control and fault not. It
exits 1 where one did not. Needs the chip, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
import types

import run


def gap_summary(gaps) -> dict:
    """Share of served tokens that are not the reference's best, and
    quantiles of their gaps."""
    import numpy as np

    g = np.concatenate(gaps) if gaps else np.zeros(0)
    off = g[g > 0]
    q = (np.quantile(off, [0.5, 0.9]).tolist() if len(off)
         else [0.0, 0.0])
    return {"tokens": int(len(g)), "off_best": int(len(off)),
            "share": float(len(off) / max(len(g), 1)), "p50": q[0],
            "p90": q[1]}


def readings(conf, mix, seed, seconds, clock) -> dict:
    """One sound run of the cell and, on what it served or trained on,
    the control (and for training the half-batch fault), each judged by
    the cell's limits."""
    import jax.numpy as jnp

    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0,
                                 trace_dir=None)
    t0 = run.time.perf_counter()
    dims = run.model.dims_of(conf)
    if mix["kind"] == "serve":
        import serve

        res = serve.run(conf, mix, args, clock, t0, run.log)
        ctrl = serve.ref_gaps(dims, seed, res["sample"], control=True)
        faults = {"control": {"served_logit_gap": max(
            (float(g.max()) for g in ctrl), default=float("inf"))}}
        # how the gaps spread, for a number steadier than the widest
        extra = {"served_tokens": res["served_tokens"],
                 "gaps": {k: gap_summary(g) for k, g in
                          (("program", res["gaps"]), ("control", ctrl))}}
    else:
        import train

        res = train.run(conf, mix, args, clock, t0, run.log)
        data, want = res["data"], res["want"]
        ctrl = train.reference_steps(dims, mix, seed, data,
                                     dtype=jnp.bfloat16)
        half = train.reference_steps(
            dims, mix, seed, data,
            batch_fn=lambda i: {k: v[: v.shape[0] // 2] for k, v in
                                data.batch(i).items()})
        faults = {"control": train.compare(ctrl, want),
                  "half_batch": train.compare(half, want)}
        extra = {"losses": res["prog"]["losses"]}
    limits = conf["limits"]
    judged = {name: {"correct": run.judge(
        {k: v for k, v in c.items() if not k.startswith("_")}, limits),
        **c} for name, c in faults.items()}
    return {"seed": seed, "program": res["checks"],
            "program_correct": run.judge(res["checks"], limits), **judged,
            **extra, "metrics": res["metrics"], "setup_s": res["setup_s"],
            "attempted": res["attempted"], "failed": res["failed"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)
    bench = run.load_bench()
    cell, conf, mix = run.cell_of(bench, a.workload)

    import jax

    if jax.devices()[0].platform != "tpu":
        run.log("calibrate.py: needs a TPU")
        return 3
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = run.CompileClock()
    sound = True
    for seed in [int(s) for s in a.seeds.split(",")]:
        line = readings(conf, mix, seed, a.seconds, clock)
        print(json.dumps(line, default=str), flush=True)
        sound &= line["program_correct"] and not any(
            v["correct"] for k, v in line.items()
            if isinstance(v, dict) and "correct" in v)
    print(json.dumps({"workload": a.workload, "separated": sound}),
          flush=True)
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
