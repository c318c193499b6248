"""Observability layer: tracker protocol, sinks, spans, histograms,
engine/fleet/train row schemas, autoscaling, and the determinism
contract (two identical seeded fleet chaos runs export identical
metrics once wall-clock fields are stripped)."""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import model_zoo as zoo
from repro.models import param as pm
from repro.obs import (
    NULL,
    Histogram,
    JsonlSink,
    MemorySink,
    NullTracker,
    Tracker,
    deterministic_rows,
)
from repro.serve import Request, ServeConfig, ServeEngine
from repro.serve.fleet import (
    AutoscaleConfig,
    Autoscaler,
    Fleet,
    FleetChaosConfig,
    FleetConfig,
)
from repro.serve.router import TimelineWriter

BS = 8


def _dropless(cfg):
    if cfg.moe is None:
        return cfg
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.num_experts)
        )
    )


@pytest.fixture(scope="module")
def granite():
    cfg = _dropless(get_reduced("granite-moe-1b-a400m"))
    p = zoo.init_params(jax.random.PRNGKey(0), cfg)
    vals, _ = pm.split(p)
    return cfg, vals


def _engine(granite, **kw):
    cfg, vals = granite
    base = dict(max_batch=3, max_len=64, paged=True, block_size=BS,
                chunk_size=8, chunks_per_step=2, audit_invariants=True)
    base.update(kw)
    return ServeEngine(vals, cfg, ServeConfig(**base))


def _req(rid, plen=8, arrival=0, max_new=6, **kw):
    prompt = [(37 * rid + 11 * i) % 97 + 1 for i in range(plen)]
    return Request(rid=rid, prompt=prompt, max_new=max_new,
                   arrival=arrival, **kw)


# ---------------------------------------------------------------------------
# tracker core (host-side, no jax)
# ---------------------------------------------------------------------------


def test_sink_fanout_and_bind():
    a, b = MemorySink(), MemorySink()
    trk = Tracker((a, b), clock=lambda: 7, tags={"run": "x"})
    trk.count("hits")
    trk.count("hits", 2)
    trk.gauge("depth", 3.5, t=9)
    assert len(a.rows) == len(b.rows) == 3
    assert a.rows == b.rows
    # clock stamps t unless given explicitly; tags ride every row
    assert a.rows[0] == {"kind": "counter", "name": "hits", "t": 7,
                         "inc": 1, "value": 1, "run": "x"}
    assert a.rows[1]["value"] == 3  # cumulative
    assert a.rows[2]["t"] == 9
    # a bound child shares sinks, merges tags, has its OWN counters,
    # and closing it never closes the shared sinks
    child = trk.bind(engine=2)
    child.count("hits")
    assert a.rows[-1]["value"] == 1 and a.rows[-1]["engine"] == 2
    child.close()
    assert not a.closed and not b.closed
    trk.close()
    assert a.closed and b.closed


def test_span_nesting_and_monotonicity():
    sink = MemorySink()
    trk = Tracker((sink,), clock=lambda: 0)
    with trk.span("tick"):
        with trk.span("admission"):
            pass
        with trk.span("mixed_step"):
            with trk.span("dispatch"):
                pass
    spans = [r for r in sink.rows if r["kind"] == "span"]
    # children exit before parents; paths are slash-joined
    assert [s["path"] for s in spans] == [
        "tick/admission", "tick/mixed_step/dispatch",
        "tick/mixed_step", "tick",
    ]
    assert [s["depth"] for s in spans] == [2, 3, 2, 1]
    by = {s["path"]: s for s in spans}
    # durations are non-negative and an enclosing span is at least as
    # long as each child
    assert all(s["dur_ms"] >= 0 for s in spans)
    assert by["tick"]["dur_ms"] >= by["tick/admission"]["dur_ms"]
    assert (by["tick/mixed_step"]["dur_ms"]
            >= by["tick/mixed_step/dispatch"]["dur_ms"])
    # span durations accumulate into histograms without observe rows
    assert not [r for r in sink.rows if r["kind"] == "observe"]
    assert set(trk.hists) == {f"span.{p}" for p in by}
    trk.close()
    summaries = [r for r in sink.rows if r["kind"] == "summary"]
    assert {s["name"] for s in summaries} == set(trk.hists)
    assert all(s["count"] == 1 for s in summaries)


def test_histogram_percentiles_vs_numpy():
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=3.0, sigma=1.0, size=5000)
    h = Histogram()
    for x in xs:
        h.record(float(x))
    s = h.summary()
    assert s["count"] == 5000
    assert s["min"] == xs.min() and s["max"] == xs.max()
    np.testing.assert_allclose(s["sum"], xs.sum(), rtol=1e-9)
    # geometric sqrt(2) buckets: estimate within one bucket of truth
    for q in (50, 99):
        ratio = h.percentile(q) / np.percentile(xs, q)
        assert 1 / 1.45 < ratio < 1.45, (q, ratio)
    # tight linear bounds -> near-exact percentiles
    h2 = Histogram(bounds=range(0, 101))
    ys = rng.integers(0, 100, size=2000)
    for y in ys:
        h2.record(float(y))
    for q in (50, 90, 99):
        assert abs(h2.percentile(q) - np.percentile(ys, q)) <= 1.5


def test_jsonl_roundtrip_and_flush_per_row(tmp_path):
    path = str(tmp_path / "rows.jsonl")
    sink = JsonlSink(path, keep_rows=True)
    trk = Tracker((sink,))
    trk.count("a", t=1)
    trk.row("engine", t=2, occupancy=0.5)
    # flushed on EVERY row: the file is complete BEFORE close
    with open(path) as f:
        lines = [json.loads(ln) for ln in f]
    assert lines == sink.rows and len(lines) == 2
    trk.close()
    assert sink.closed
    sink.close()  # idempotent
    # context-manager exit closes even when the body raises
    s2 = JsonlSink(str(tmp_path / "crash.jsonl"))
    with pytest.raises(RuntimeError):
        with s2:
            s2.write({"kind": "event", "name": "boom", "t": 0})
            raise RuntimeError("mid-run crash")
    assert s2.closed
    with open(tmp_path / "crash.jsonl") as f:
        assert json.loads(f.readline())["name"] == "boom"


def test_null_tracker_is_inert_until_bound():
    n = NullTracker()
    assert not n.enabled and not NULL.enabled
    n.count("x")
    n.gauge("y", 1)
    with n.span("z"):
        pass
    assert n.bind(engine=1) is n  # tag-only bind stays null
    sink = MemorySink()
    real = n.bind(extra_sinks=(sink,), clock=lambda: 3)
    assert real.enabled
    real.count("x")
    assert sink.rows[0]["t"] == 3


def test_deterministic_rows_strips_wall_nondeterminism():
    rows = [
        {"kind": "span", "path": "tick", "dur_ms": 1.0, "t": 0},
        {"kind": "summary", "name": "span.tick", "p50": 1.0, "t": 0},
        {"kind": "summary", "name": "latency", "p50": 4.0, "t": 0},
        {"kind": "train", "t": 1, "loss": 2.0, "step_ms": 9.9},
        {"kind": "engine", "t": 1, "tokens": 5, "tokens_per_s": 123.0},
    ]
    det = deterministic_rows(rows)
    assert det == [
        {"kind": "summary", "name": "latency", "p50": 4.0, "t": 0},
        {"kind": "train", "t": 1, "loss": 2.0},
        {"kind": "engine", "t": 1, "tokens": 5},
    ]


def test_timeline_writer_kind_filter():
    tl = TimelineWriter(None)
    tl.write({"kind": "engine", "t": 0})
    tl.write({"kind": "fleet", "t": 0})
    tl.write({"kind": "span", "path": "tick", "t": 0})
    tl.write({"kind": "counter", "name": "x", "t": 0})
    tl.write({"tick": 3})  # legacy row without kind passes through
    assert [r.get("kind", "legacy") for r in tl.rows] == [
        "engine", "fleet", "legacy"]


# ---------------------------------------------------------------------------
# autoscaler policy units (host-side)
# ---------------------------------------------------------------------------


def test_autoscaler_streaks_cooldown_and_bounds():
    asc = AutoscaleConfig(min_engines=1, max_engines=2, up_occupancy=0.8,
                          up_backlog=4, up_ticks=2, down_occupancy=0.1,
                          down_ticks=3, cooldown=5)
    busy = [dict(occupancy=0.9, active=2)]
    idle = [dict(occupancy=0.0, active=0)]
    a = Autoscaler(asc)
    # sustained overload: no action until the streak reaches up_ticks
    assert a.decide(0, n_live=1, signals=busy, backlog=0,
                    shed_delta=0) is None
    assert a.decide(1, n_live=1, signals=busy, backlog=0,
                    shed_delta=0) == "up"
    # cooldown gates the next action even under continued overload
    for t in range(2, 6):
        assert a.decide(t, n_live=2, signals=busy, backlog=9,
                        shed_delta=1) is None
    # ...and max_engines caps growth once the cooldown expires
    assert a.decide(6, n_live=2, signals=busy, backlog=9,
                    shed_delta=0) is None
    # backlog and shed retries each count as overload on their own
    b = Autoscaler(asc)
    assert b.decide(0, n_live=1, signals=idle, backlog=4,
                    shed_delta=0) is None
    assert b.decide(1, n_live=1, signals=idle, backlog=0,
                    shed_delta=2) == "up"
    # sustained idleness drains, but never below min_engines
    c = Autoscaler(asc)
    for t in range(3):
        assert c.decide(t, n_live=1, signals=idle, backlog=0,
                        shed_delta=0) is None  # at the floor
    d = Autoscaler(asc)
    assert d.decide(0, n_live=2, signals=idle, backlog=0,
                    shed_delta=0) is None
    assert d.decide(1, n_live=2, signals=idle, backlog=0,
                    shed_delta=0) is None
    assert d.decide(2, n_live=2, signals=idle, backlog=0,
                    shed_delta=0) == "down"
    # an active slot or any backlog breaks the idle streak
    e = Autoscaler(asc)
    e.decide(0, n_live=2, signals=idle, backlog=0, shed_delta=0)
    e.decide(1, n_live=2, signals=[dict(occupancy=0.0, active=1)],
             backlog=0, shed_delta=0)
    assert e.down_streak == 0
    assert e.decide(2, n_live=2, signals=[], backlog=0,
                    shed_delta=0) is None  # nothing alive to measure


# ---------------------------------------------------------------------------
# engine + fleet integration (jax)
# ---------------------------------------------------------------------------


def test_solo_serve_engine_rows_spans_counters(granite):
    sink = MemorySink()
    trk = Tracker((sink,))
    eng = _engine(granite)
    reqs = [_req(r, arrival=r // 2) for r in range(4)]
    outs, fin = eng.serve(reqs, tracker=trk)
    assert all(rec["status"] == "completed" for rec in fin.values())
    # tracking must not mint jit signatures or add host syncs
    assert eng.last_stats["compile_count"] == 1
    erows = [r for r in sink.rows if r["kind"] == "engine"]
    assert len(erows) >= eng.last_stats["mixed_steps"] > 0
    assert erows[-1]["mixed_steps"] == eng.last_stats["mixed_steps"]
    # t = engine step, monotonic non-decreasing; schema per obs/README.md
    ts = [r["t"] for r in erows]
    assert ts == sorted(ts) and len(set(ts)) > 1
    for r in erows:
        for k in ("occupancy", "free_blocks", "queue_depth", "active",
                  "decoding", "stall_ticks", "tokens", "mixed_steps",
                  "compiles"):
            assert k in r, k
    assert erows[-1]["tokens"] == sum(len(outs[q.rid]) - len(q.prompt)
                                      for q in reqs)
    assert erows[-1]["compiles"] == 1
    # tick-phase spans + their close()-time summaries
    paths = {r["path"] for r in sink.rows if r["kind"] == "span"}
    assert {"tick", "tick/admission", "tick/mixed_step",
            "tick/host_sync", "tick/emit"} <= paths
    summaries = {r["name"] for r in sink.rows if r["kind"] == "summary"}
    assert "span.tick/mixed_step" in summaries
    # scheduler counters
    counters = {r["name"]: r["value"] for r in sink.rows
                if r["kind"] == "counter"}
    assert counters["serve.admissions"] == 4
    assert counters["serve.terminal.completed"] == 4


def _host_events(log_dir):
    from jax.profiler import ProfileData

    path = sorted(log_dir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(path))
    return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for plane in pd.planes if plane.name.startswith("/host:CPU")
            for line in plane.lines for ev in line.events]


def test_tick_spans_are_profiler_host_events_without_a_sink(granite,
                                                            tmp_path):
    eng = _engine(granite)
    reqs = [_req(r, arrival=r // 2) for r in range(3)]
    with jax.profiler.trace(str(tmp_path)):
        outs, fin = eng.serve(reqs)  # the default NULL tracker
    assert all(rec["status"] == "completed" for rec in fin.values())
    events = _host_events(tmp_path)
    ticks = [(a, b) for a, b, n in events if n == "tick"]
    phases = [(a, b, n) for a, b, n in events if n.startswith("tick/")]
    assert len(ticks) >= eng.last_stats["mixed_steps"] > 0
    assert {"tick/admission", "tick/prefix", "tick/mixed_step",
            "tick/host_sync", "tick/emit"} <= {n for _, _, n in phases}
    for a, b, n in phases:
        assert any(ta <= a and b <= tb for ta, tb in ticks), n


def test_fleet_autoscales_up_under_overload_and_down_when_idle(granite):
    sink = MemorySink()
    eng = _engine(granite)
    fleet = Fleet(eng, FleetConfig(
        num_engines=1,
        autoscale=AutoscaleConfig(min_engines=1, max_engines=3,
                                  up_backlog=4, up_ticks=2,
                                  down_occupancy=0.10, down_ticks=3,
                                  cooldown=3),
    ), tracker=Tracker((sink,)))
    # 8 instant arrivals swamp the single 3-slot replica; one straggler
    # far in the future keeps the loop alive through the idle window
    reqs = [_req(r) for r in range(8)] + [_req(8, arrival=80, max_new=4)]
    outs, fin = fleet.run(reqs)
    assert all(rec["status"] == "completed" for rec in fin.values())
    st = fleet.last_stats
    assert st["scale_ups"] >= 1
    assert st["scale_downs"] >= 1
    frows = [r for r in sink.rows if r["kind"] == "fleet"]
    # replica-count time series reflects the scaling actions
    assert max(r["fleet"]["replicas"] for r in frows) >= 2
    assert frows[-1]["fleet"]["scale_ups"] == st["scale_ups"]
    assert frows[-1]["fleet"]["scale_downs"] == st["scale_downs"]
    # engine rows from the spawned replica carry its eid tag
    eids = {r["engine"] for r in sink.rows if r["kind"] == "engine"}
    assert len(eids) >= 2
    counters = {r["name"]: r["value"] for r in sink.rows
                if r["kind"] == "counter" and "engine" not in r}
    assert counters["fleet.scale_ups"] == st["scale_ups"]
    assert counters["fleet.scale_downs"] == st["scale_downs"]


def test_timeline_flushes_rows_and_closes_on_mid_tick_error(
        granite, tmp_path):
    path = str(tmp_path / "timeline.jsonl")
    eng = _engine(granite)
    fleet = Fleet(eng, FleetConfig(num_engines=2, timeline_path=path))
    seen = []

    def on_token(rid, tok):
        seen.append((rid, tok))
        if len(seen) == 5:
            raise RuntimeError("injected mid-tick consumer crash")

    with pytest.raises(RuntimeError, match="mid-tick"):
        fleet.run([_req(r) for r in range(4)], on_token=on_token)
    # the timeline sink is closed by the crash path...
    assert fleet.timeline is not None and fleet.timeline.closed
    # ...and every row written before the crash is on disk, complete
    # (flush-per-row: nothing buffered, nothing torn)
    with open(path) as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    assert rows, "pre-crash rows must already be flushed"
    assert all(r["kind"] in ("engine", "fleet") for r in rows)


def test_fleet_chaos_metrics_deterministic_across_runs(granite):
    def one_run():
        sink = MemorySink()
        eng = _engine(granite)
        fleet = Fleet(eng, FleetConfig(
            num_engines=2,
            chaos=FleetChaosConfig(seed=11, kills=((6, 1),)),
            restart_after=5,
        ), tracker=Tracker((sink,)))
        _, fin = fleet.run([_req(r, arrival=r // 2) for r in range(6)])
        assert set(fin) == set(range(6))
        return deterministic_rows(sink.rows)

    r1, r2 = one_run(), one_run()
    assert r1 == r2
    # the projection still carries the full engine + fleet time series
    assert any(r["kind"] == "engine" for r in r1)
    assert any(r["kind"] == "fleet" for r in r1)
    # and strips everything wall-clock
    assert not any(r["kind"] == "span" for r in r1)
    assert not any(k in r for r in r1 for k in ("dur_ms", "step_ms"))


# ---------------------------------------------------------------------------
# trainer + checkpoint emissions
# ---------------------------------------------------------------------------


def test_trainer_emits_train_rows_every_step(tmp_path):
    from repro.data import make_iterator
    from repro.optim import adafactor, constant
    from repro.training import TrainConfig, Trainer

    cfg = get_reduced("tinyllama-1.1b")
    sink = MemorySink()
    it = make_iterator(cfg, global_batch=4, seq_len=32, host_index=0,
                       host_count=1)
    tr = Trainer(cfg, adafactor(constant(1e-3)), it, str(tmp_path),
                 tc=TrainConfig(checkpoint_every=100, log_every=100),
                 log_fn=lambda s: None, tracker=Tracker((sink,)))
    tr.run(3)
    trows = [r for r in sink.rows if r["kind"] == "train"]
    assert [r["t"] for r in trows] == [1, 2, 3]  # EVERY step, t = step
    for r in trows:
        for k in ("loss", "ce", "grad_norm", "skipped", "skipped_steps",
                  "step_ms"):
            assert k in r, k
        assert np.isfinite(r["loss"]) and r["grad_norm"] >= 0
        assert r["skipped"] == 0.0 and r["skipped_steps"] == 0


def test_checkpoint_manager_counts_retries_and_fallbacks(tmp_path):
    from repro.checkpoint import CheckpointManager

    sink = MemorySink()
    trk = Tracker((sink,))
    fails = {"n": 2}

    def fault(op, attempt):
        if op == "save" and fails["n"] > 0:
            fails["n"] -= 1
            raise OSError("flaky mount")

    mgr = CheckpointManager(str(tmp_path), fault_hook=fault,
                            sleep=lambda s: None, tracker=trk)
    tree = {"w": np.arange(4, dtype=np.float32)}
    mgr.save(1, tree)
    counters = {r["name"]: r["value"] for r in sink.rows
                if r["kind"] == "counter"}
    assert counters["checkpoint.io_retries"] == 2
    # corrupt the newest step's payload -> restore falls back, counted
    mgr2 = CheckpointManager(str(tmp_path), tracker=trk)
    mgr2.save(2, {"w": np.ones(4, dtype=np.float32)})
    leaf = tmp_path / "step_00000002" / "leaf_00000.npy"
    leaf.write_bytes(b"\x93NU")  # truncated-after-COMMIT torn payload
    restored, step, _ = mgr2.restore_latest({"w": tree["w"]})
    assert step == 1
    np.testing.assert_array_equal(restored["w"], tree["w"])
    counters = {r["name"]: r["value"] for r in sink.rows
                if r["kind"] == "counter"}
    assert counters["checkpoint.fallbacks"] == 1
