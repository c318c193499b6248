"""The harness: cells found from their files by name, traffic drawn from
the seed, the result line's schema, the refusal without a TPU, and the
shape of BENCHMARK.json."""
import json
import os
import re
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from _bench_path import BENCH, DATA, ROOT, load, tiny, with_serving

import run
import traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_resolves_to_its_files():
    bench = with_serving(run.load_bench())
    for w in bench["workloads"]:
        cell, conf, mix = run.cell_of(bench, w["name"])
        assert conf["name"] == cell["config"]
        assert mix["kind"] in ("serve", "train")
        assert set(conf["limits"])
        assert run.metrics_of(bench, cell, "end_to_end")
        assert run.metrics_of(bench, cell, "per_layer")
    for m in bench["per_layer"]:
        mod = run.bench_module(m["name"], BENCH / "metrics"
                               / f"{m['name']}.py")
        assert callable(mod.read)
    with pytest.raises(SystemExit, match="no workload"):
        run.cell_of(bench, "no.such.cell")


def test_benchmark_json_shape():
    bench = run.load_bench()
    assert bench["command"] == ["python3", "bench/run.py"]
    for p in bench["paths"]:
        assert (ROOT / p).is_dir()
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert "bound" not in m
    for c in bench["configs"]:
        conf = load(ROOT / c["file"])
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        for k in c["reduced"]:
            assert not k.endswith(("_dim", "_rank", "_size"))
    assert os.path.getsize(ROOT / "BENCHMARK.json") < 64 * 1024


def _chat(seed, seconds=30.0):
    mix = load(BENCH / "traffic/chat.json")
    return traffic.serve_requests(mix, seed, seconds, 49155)


def test_traffic_repeats_under_a_seed_and_varies_across_seeds():
    big = 2 ** 31 + 123457
    a, b, c = _chat(big), _chat(big), _chat(big + 1)
    assert len(a) == len(b) == len(c)
    for x, y in zip(a, b):
        assert x["due"] == y["due"] and x["max_new"] == y["max_new"]
        np.testing.assert_array_equal(x["prompt"], y["prompt"])
    assert any(x["due"] != y["due"] or len(x["prompt"]) != len(y["prompt"])
               for x, y in zip(a, c))
    # every seed carries the same work inside the window
    win = lambda rs: sorted((len(r["prompt"]), r["max_new"]) for r in rs
                            if r["window"])
    assert sorted(len(r["prompt"]) for r in a if r["window"]) == sorted(
        len(r["prompt"]) for r in c if r["window"])
    assert sorted(r["max_new"] for r in a if r["window"]) == sorted(
        r["max_new"] for r in c if r["window"])
    rate = load(BENCH / "traffic/chat.json")["rate_rps"]
    assert len(win(a)) == round(rate * 30)
    assert all(r["due"] < 30.0 for r in a if r["window"])
    assert all(r["due"] >= 30.0 for r in a if not r["window"])


def test_train_batches_repeat_and_all_rows_differ():
    mix = load(BENCH / "traffic/finetune.json")
    a = traffic.train_batch(mix, 99, 0, 49155)
    b = traffic.train_batch(mix, 99, 0, 49155)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    rows = [traffic.train_batch(mix, 99, i, 49155)["tokens"]
            for i in range(3)]
    flat = np.concatenate(rows)
    assert flat.shape == (6, 4096)
    assert len({r.tobytes() for r in flat}) == 6
    np.testing.assert_array_equal(a["targets"][:, :-1], a["tokens"][:, 1:])


def test_result_line_schema_on_a_test_size_run():
    bench = with_serving(run.load_bench())
    cell = {w["name"]: w for w in bench["workloads"]}["granite1b.chat"]
    conf = tiny()
    mix = load(DATA / "tiny_chat.json")
    args = types.SimpleNamespace(seed=2 ** 31 + 5, seconds=1.5, trace=0)
    line = run.measure(bench, cell, conf, mix, args, jax.devices(), {},
                       run.CompileClock())
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {
        m["name"] for m in run.metrics_of(bench, cell, "end_to_end")}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["checks"]["served_logit_gap"]["limit"] == conf["limits"][
        "served_logit_gap"]
    json.dumps(line)


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "granite3b.finetune",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs 1 TPU chip" in p.stderr
    assert not p.stdout.strip()
