"""Record the small chip trace the scope tests read: the test-size
fine-tune run by the benchmark's own training code (``bench/train.py``)
with its profiler on (``trace_steps`` steps of ``data/tiny_train.json``
traced), and the compiled text of the step that ran.

    python tests/bench/record_trace.py tests/bench/data/tiny_train_scoped

Run from the root of a checkout on a machine with one TPU chip. Writes
``<out>.xplane.pb`` and ``<out>.hlo.txt.gz`` and prints the recorded
window's ``[trace] by scope`` line.
"""
import argparse
import gzip
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

from _bench_path import DATA, load

import run
import scopes
import train


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("out", help="path prefix of the two files written")
    ap.add_argument("--seed", type=int, default=2718281828)
    a = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        run.log("record_trace.py: needs a TPU chip")
        return 3
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    conf = load(DATA / "tiny.json")
    mix = load(DATA / "tiny_train.json")
    trace = run.bench_module("trace")
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as d:
        args = SimpleNamespace(seed=a.seed, seconds=3.0, trace=1,
                               trace_dir=Path(d))
        res = train.run(conf, mix, args, run.CompileClock(),
                        time.perf_counter(), run.log)
        if res["traced_steps"] != mix["trace_steps"]:
            run.log(f"record_trace.py: traced {res['traced_steps']} steps")
            return 1
        shutil.copy(trace.find_xplane(Path(d)), f"{a.out}.xplane.pb")
    text = scopes.train_step_hlo(conf, mix)
    with gzip.open(f"{a.out}.hlo.txt.gz", "wt") as f:
        f.write(text)
    red = trace.load(Path(f"{a.out}.xplane.pb"))
    run.log(scopes.split(red, scopes.op_names(text)).line())
    return 0


if __name__ == "__main__":
    sys.exit(main())
