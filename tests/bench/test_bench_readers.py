"""The per-layer readers on a synthetic reduced trace: each finds its
kernel's events by its patterns, divides the algorithm's least time by
their device time, and returns nothing where it finds nothing."""
import types

import pytest

from _bench_path import BENCH, load, with_serving

import flops
import model
import run
from serve import Rec, TickLog

trace = run.bench_module("trace", BENCH / "trace.py")
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _reader(name):
    return run.bench_module(name, BENCH / "metrics" / f"{name}.py")


def _serve_ctx(ops, ticks):
    conf = load(BENCH / "configs/granite-moe-1b-a400m.json")
    drv = types.SimpleNamespace(
        traced=ticks, seconds=30.0, window_tokens=4000,
        recs={i: Rec(i, 800, 100, 0.0, True, admitted=0.2 + i / 100,
                     first=0.5, last=3.0, n=100) for i in range(40)},
        counters0={"mixed_steps": 0, "prefix_hit_tokens": 0,
                   "prompt_tokens": 0},
        counters1={"mixed_steps": 99, "prefix_hit_tokens": 800,
                   "prompt_tokens": 32000})
    drv.window_recs = lambda: list(drv.recs.values())
    red = trace.Reduced((0.0, 1.0), [ops], [(0.0, 1.0, "bench.tick")])
    return types.SimpleNamespace(
        dims=model.dims_of(conf), peak=PEAK, item=4, trace=red,
        mix=load(BENCH / "traffic/chat.json"), conf=conf,
        res={"driver": drv})


def test_serve_readers():
    ticks = [TickLog(dec_ctx=[1000] * 32, first=1, prefill=256)
             for _ in range(10)]
    ops = []
    for i in range(10):
        t = i * 0.1
        for layer in range(24):
            j = i * 24 + layer
            ops.append(trace.Op(f"_grouped_mlp_pallas_tables.{j}",
                                t + layer * 1e-3, 5e-4))
            ops.append(trace.Op(f"paged_decode_attention_pallas.{j}",
                                t + layer * 1e-3 + 5e-4, 2e-4))
            # an op that only shares the kernel's prefix is not the kernel
            ops.append(trace.Op(f"_grouped_mlp_pallas_tables_pad.{j}",
                                t + layer * 1e-3 + 7e-4, 1e-5))
    ctx = _serve_ctx(ops, ticks)
    dims = ctx.dims
    want = 240 * flops.least_time(*flops.grouped_mlp_fwd(
        dims, (32 + 256) * 8, 4), PEAK)
    got = run.read_layer_metric("grouped_mlp_roofline.serve", ctx)
    assert got == pytest.approx(100 * want / (240 * 5e-4))
    want = 240 * flops.least_time(*flops.decode_attention(
        dims, [1000] * 32, 4), PEAK)
    got = run.read_layer_metric("decode_attention_roofline", ctx)
    assert got == pytest.approx(100 * want / (240 * 2e-4))
    busy = 240 * 7.1e-4
    assert run.read_layer_metric("device.idle_share.serve", ctx) == \
        pytest.approx(100 * (1 - busy))
    assert run.read_layer_metric("sched.prefix_hit_share", ctx) == \
        pytest.approx(2.5)
    assert run.read_layer_metric("engine.decode_rows_per_step", ctx) == \
        pytest.approx((4000 - 40) / 99)
    assert 0 < run.read_layer_metric("serve_step.mfu", ctx) < 100
    assert run.read_layer_metric("sched.queue_wait_p95_ms", ctx) == \
        pytest.approx(1e3 * (0.2 + 0.95 * 39 / 100), rel=1e-6)


def test_readers_return_nothing_without_their_events():
    ctx = _serve_ctx([trace.Op("fusion.1", 0.0, 0.1)],
                     [TickLog(dec_ctx=[10], prefill=0)])
    assert run.read_layer_metric("grouped_mlp_roofline.serve", ctx) is None
    assert run.read_layer_metric("decode_attention_roofline", ctx) is None
    assert run.read_layer_metric("train_step.mfu", ctx) is None
    assert run.read_layer_metric("flash_attention_roofline", ctx) is None


def test_a_listed_metric_that_reads_nothing_stops_the_run():
    ctx = _serve_ctx([trace.Op("fusion.1", 0.0, 0.1)],
                     [TickLog(dec_ctx=[10], prefill=0)])
    bench = with_serving(run.load_bench())
    cell = {w["name"]: w for w in bench["workloads"]}["granite1b.chat"]
    with pytest.raises(SystemExit, match="grouped_mlp_roofline.serve"):
        run.layer_metrics(bench, cell, ctx)


def test_train_readers():
    conf = load(BENCH / "configs/granite-moe-3b-a800m.json")
    mix = load(BENCH / "traffic/finetune.json")
    ops = []
    for i in range(8):  # one step: 8 layers
        t = i * 0.1
        ops += [
            trace.Op(f"flash_attention_pallas.{i}", t, 0.01),
            trace.Op(f"_flash_attention_pallas_bwd.{2 * i}", t + 0.01, 0.01),
            trace.Op(f"_flash_attention_pallas_bwd.{2 * i + 1}", t + 0.02,
                     0.01),
            trace.Op(f"_grouped_mlp_pallas_tables.{i}", t + 0.03, 0.02),
            trace.Op(f"_grouped_mlp_pallas_bwd.{2 * i}", t + 0.05, 0.02),
            trace.Op(f"_grouped_mlp_pallas_bwd.{2 * i + 1}", t + 0.07,
                     0.02)]
    red = trace.Reduced((0.0, 1.0), [ops], [])
    ctx = types.SimpleNamespace(dims=model.dims_of(conf), peak=PEAK,
                                item=4, trace=red, mix=mix, conf=conf,
                                res={"traced_steps": 1})
    dims = ctx.dims
    lf = flops.least_time(*flops.flash_fwd(dims, 2, 4096, 4), PEAK)
    lb = flops.least_time(*flops.flash_bwd(dims, 2, 4096, 4), PEAK)
    assert run.read_layer_metric("flash_attention_roofline", ctx) == \
        pytest.approx(100 * 8 * (lf + lb) / (8 * 0.03))
    rows = 2 * 4096 * 8
    lf = flops.least_time(*flops.grouped_mlp_fwd(dims, rows, 4), PEAK)
    lb = flops.least_time(*flops.grouped_mlp_bwd(dims, rows, 4), PEAK)
    assert run.read_layer_metric("grouped_mlp_roofline.train", ctx) == \
        pytest.approx(100 * 8 * (lf + lb) / (8 * 0.06))
    mfu = run.read_layer_metric("train_step.mfu", ctx)
    assert mfu == pytest.approx(100 * flops.train_step_flops(
        dims, 2, 4096) / (0.72 * 197e12))
    assert run.read_layer_metric("device.idle_share.train", ctx) == \
        pytest.approx(28.0)
