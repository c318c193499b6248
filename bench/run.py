"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the TPU chips the cell
asks for; it refuses to run anywhere else. A cell names a configuration
(``bench/configs/<name>.json``, which names its family,
``bench/families/<family>.py``) and a traffic mix
(``bench/traffic/<name>.json``, whose ``kind`` names its driver,
``bench/<kind>.py``); per-layer metrics are readers in
``bench/metrics/<name>.py``. Set-up makes the weights from ``--seed``
and warms up the cell's shapes; the window then measures ``--seconds``.
With ``--trace 1`` a few seconds of the window are profiled and the
per-layer metrics reported instead of the end-to-end ones. Afterwards
what the timed path produced is compared with the family's plain
reference in ``bench/reference/``; each compared number is printed
beside its limit, last on standard error and under ``checks`` in the
result line, the last line of standard output.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH))

import model  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileClock:
    """Compiler seconds and persistent-cache loads, from JAX's monitoring
    events: backend compiles (cold) and cache hits (warm)."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_load_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.cache_load_s += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def load_bench(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_of(bench: dict, name: str) -> tuple[dict, dict, dict]:
    """The cell named ``name``, its configuration file and its mix."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r}; have "
                         f"{sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    conf = json.loads((ROOT / entry["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    return cell, conf, mix


def metrics_of(bench: dict, cell: dict, kind: str) -> list[dict]:
    """The cell's end-to-end (``kind="end_to_end"``) or per-layer
    metrics: those that list the cell, or list no cells and (for a
    per-layer metric) move an end-to-end metric the cell reports."""
    name = cell["name"]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if kind == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if name in m.get("workloads", [])
            or ("workloads" not in m and m["moves"] in moved)]


def peak_of(kind: str) -> dict:
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in peaks:
        raise SystemExit(f"run.py: device kind {kind!r} is not in "
                         f"bench/peaks.json ({sorted(peaks)})")
    return peaks[kind]


def bench_module(name: str, path: Path | None = None):
    """Load a module of the benchmark by its file."""
    return model.load_by_path(f"bench_{name}", path or BENCH / f"{name}.py")


def driver_of(mix: dict):
    """The driver module the mix's ``kind`` names: ``bench/<kind>.py``
    (``train``, ``serve``), whose ``run(conf, mix, args, clock, t_start,
    log)`` sets up, measures and checks one run. A family whose cells
    need a driver of their own brings it as a new file and a mix that
    names it."""
    kind = mix["kind"]
    if not (kind.isidentifier() and (BENCH / f"{kind}.py").is_file()):
        raise SystemExit(f"run.py: mix kind {kind!r} names no driver "
                         f"bench/{kind}.py")
    return importlib.import_module(kind)


def read_layer_metric(name: str, ctx):
    return bench_module(name, BENCH / "metrics" / f"{name}.py").read(ctx)


def layer_metrics(bench: dict, cell: dict, ctx) -> dict:
    """Every per-layer metric of the cell, read from a traced run. Each
    lists the cells in which it has something to read, so one that reads
    nothing there is a fault of the run (its name patterns no longer
    match the trace, or the traced window held none of its work): the
    run stops and names it, with the patterns it looked for."""
    out = {}
    for m in metrics_of(bench, cell, "per_layer"):
        v = read_layer_metric(m["name"], ctx)
        if v is None:
            mod = bench_module(m["name"],
                               BENCH / "metrics" / f"{m['name']}.py")
            pats = {a: getattr(mod, a) for a in ("PATTERNS", "FWD", "BWD")
                    if hasattr(mod, a)}
            raise SystemExit(f"run.py: per-layer metric {m['name']!r} "
                             f"read nothing in {cell['name']}'s traced "
                             f"window {pats}")
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def judge(checks: dict, limits: dict) -> bool:
    for k, v in checks.items():
        if k not in limits:
            raise SystemExit(f"run.py: no limit for check {k!r}")
        if not (isinstance(v, (int, float)) and math.isfinite(v)
                and v <= limits[k]):
            return False
    return True


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return ap.parse_args(argv)


def measure(bench, cell, conf, mix, args, devices, peak, clock,
            t_start=T_START) -> dict:
    """Set up, measure and check one run; returns the result line."""
    dev = devices[0]
    with contextlib.ExitStack() as stack:
        args.trace_dir = (Path(stack.enter_context(
            tempfile.TemporaryDirectory(prefix="bench_trace_")))
            if args.trace else None)
        res = driver_of(mix).run(conf, mix, args, clock, t_start, log)
        log(f"[setup] setup_s={res['setup_s']!r} compiles={clock.compiles} "
            f"compile_s={clock.compile_s!r} cache_hits={clock.cache_hits} "
            f"cache_load_s={clock.cache_load_s!r}")
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices), "memory_peak_bytes": res["peak"]}
        out = {}
        if args.trace:
            trace = bench_module("trace")
            red = trace.load(trace.find_xplane(args.trace_dir))
            ctx = SimpleNamespace(
                dims=model.dims_of(conf), peak=peak,
                item=4 if conf["dtype"] == "float32" else 2,
                trace=red, mix=mix, conf=conf, res=res)
            metrics = layer_metrics(bench, cell, ctx)
            device |= {"busy_s": red.busy_s, "window_s": red.window_s}
            out["breakdown"] = {"device_ops": red.top_ops(),
                                "idle_gaps": red.idle_gaps()}
        else:
            vals = dict(res["metrics"], setup_s=res["setup_s"])
            metrics = {m["name"]: {"value": vals[m["name"]],
                                   "unit": m["unit"]}
                       for m in metrics_of(bench, cell, "end_to_end")}
    limits = conf["limits"]
    return {"correct": judge(res["checks"], limits),
            "attempted": res["attempted"], "failed": res["failed"],
            "metrics": metrics, "device": device, **out,
            "checks": {k: {"value": v, "limit": limits[k]}
                       for k, v in res["checks"].items()}}


def main(argv=None) -> int:
    args = parse(argv)
    bench = load_bench()
    cell, conf, mix = cell_of(bench, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"run.py: {cell['name']} needs {cell['chips']} TPU chip(s); "
            f"JAX found {len(devices)} {devices[0].platform} device(s)")
        return 3
    peak = peak_of(devices[0].device_kind)
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"[setup] {cell['name']}: {devices[0].device_kind!r} "
        f"x{len(devices)} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} compile_cache={cache}")
    line = measure(bench, cell, conf, mix, args, devices, peak,
                   CompileClock())
    for k, v in line["checks"].items():
        log(f"check {k} = {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
