"""``bench/scopes.py`` and the four readers built on it: from a traced
op, through the compiled step's text, to the program's named scope.

Synthetic texts and traces pin the rules (innermost scope, phase by
path, a compiler-made instruction takes its fusion root's or its
inputs' path, self time, unresolved ops stop the run). A small trace of
the scoped test-size fine-tune, recorded on a v5e with its compiled
text by ``record_trace.py``, holds every scope; the trace recorded
before the program had scopes resolves against the scoped step's text
instruction for instruction, and every accepted reader reads on both
what it read before the scopes."""
import gzip
import re
import types

import pytest

from _bench_path import BENCH, DATA, load

import model
import run
import scopes

trace = run.bench_module("trace", BENCH / "trace.py")
SCOPED = DATA / "tiny_train_scoped"
NEW = ["train_step.moe_dispatch_share", "train_step.lm_head_share",
       "train_step.optimizer_share", "train_step.recompute_share"]
KERNELS = [p for name, attrs in [("grouped_mlp_roofline.train",
                                  ("FWD", "BWD")),
                                 ("flash_attention_roofline",
                                  ("FWD", "BWD"))]
           for a in attrs
           for p in getattr(run.bench_module(
               name, BENCH / "metrics" / f"{name}.py"), a)]


def _ctx(red, hlo, traced=2):
    conf = load(DATA / "tiny.json")
    peak = load(BENCH / "peaks.json")["devices"]["TPU v5 lite"]
    return types.SimpleNamespace(
        dims=model.dims_of(conf), peak=peak, item=4, trace=red,
        mix=load(DATA / "tiny_train.json"), conf=conf,
        res={"traced_steps": traced}, hlo=hlo)


def _recorded():
    red = trace.load(SCOPED.with_suffix(".xplane.pb"))
    with gzip.open(SCOPED.with_suffix(".hlo.txt.gz"), "rt") as f:
        return red, f.read()


@pytest.mark.parametrize("path,scope,phase", [
    ("jit(train_step)/jvp()/while/body/closed_call/moe.dispatch/sort",
     "moe.dispatch", "fwd"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/moe.route/top_k", "moe.route", "recompute"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "attn/bshk,hkd->bsd", "attn", "bwd"),
    ("jit(train_step)/transpose(jvp(loss))/jit(log_softmax)/add_any",
     "loss", "bwd"),
    ("jit(train_step)/jvp(lm_head)/mul", "lm_head", "fwd"),
    ("jit(train_step)/optimizer/mul", "optimizer", "fwd"),
    ("jit(mixed)/while/body/attn/kv.write/scatter", "kv.write", "fwd"),
    ("jit(train_step)/transpose(jvp())/while", "unscoped", "bwd"),
    ("", "unscoped", "fwd"),
])
def test_scope_and_phase_of_a_path(path, scope, phase):
    assert scopes.scope_of(path) == scope
    assert scopes.phase_of(path) == phase


LATENT = "jit(train_step)/jvp()/while/body/closed_call/attn/attn.latent/dot"


@pytest.mark.parametrize("path,listed,scope", [
    # a scope no list names folds into the enclosing listed one
    (LATENT, scopes.SCOPES, "attn"),
    (LATENT, scopes.SCOPES + ("attn.latent",), "attn.latent"),
    ("jit(train_step)/transpose(jvp(attn.latent))/dot",
     scopes.SCOPES + ("attn.latent",), "attn.latent"),
    ("jit(f)/attn.latent/moe.route/top_k", scopes.SCOPES + ("attn.latent",),
     "moe.route"),
])
def test_the_innermost_scope_of_the_list_given_wins(path, listed, scope):
    assert scopes.scope_of(path, listed) == scope


HLO = """HloModule m, is_scheduled=true

%fused_computation.1 (param_0.1: f32[4]) -> (f32[4], f32[4]) {
  %param_0.1 = f32[4]{0} parameter(0)
  %exp.1 = f32[4]{0} exponential(%param_0.1), metadata={op_name="jit(f)/jvp(loss)/exp"}
  ROOT %tuple.1 = (f32[4]{0}, f32[4]{0}) tuple(%exp.1, %param_0.1)
}

ENTRY %main.2 (p.1: f32[4]) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0)
  %neg.1 = f32[4]{0} negate(%p.1), metadata={op_name="jit(f)/moe.route/neg"}
  %fusion.1 = (f32[4]{0}, f32[4]{0}) fusion(%neg.1), kind=kLoop, calls=%fused_computation.1
  %gte.1 = f32[4]{0} get-tuple-element(%fusion.1), index=0
  %copy.1 = f32[4]{0} copy(%gte.1)
  ROOT %copy.2 = f32[4]{0} copy(%p.1)
}
"""


def test_an_instruction_without_an_op_name_takes_its_roots_or_inputs():
    names = scopes.op_names(HLO)
    assert names["neg.1"] == "jit(f)/moe.route/neg"
    # a multi-output fusion's tuple root: the path of what the root holds
    assert names["fusion.1"] == "jit(f)/jvp(loss)/exp"
    # a compiler-made copy: the path of its input
    assert names["copy.1"] == "jit(f)/jvp(loss)/exp"
    # nothing named upstream
    assert names["copy.2"] == ""


def _synthetic():
    red = trace.Reduced((0.0, 2.0), [[
        trace.Op("while.1", 0.0, 1.0),  # the loop: 0.5 s of its own
        trace.Op("fusion.1", 0.1, 0.3),
        trace.Op("neg.1", 0.5, 0.2),
        trace.Op("mystery.7", 1.5, 0.05),  # not in the text
        trace.Op("neg.1", 1.9, 0.2),  # clipped to the window
    ]])
    names = {"while.1": "jit(f)/transpose(jvp())/while",
             "fusion.1": "jit(f)/jvp(loss)/exp",
             "neg.1": "jit(f)/transpose(jvp())/checkpoint/"
                      "rematted_computation/moe.route/neg"}
    return red, names


def test_split_counts_self_time_by_scope_and_phase():
    red, names = _synthetic()
    s = scopes.split(red, names)
    assert s.seconds == pytest.approx({
        ("unscoped", "bwd"): 0.5, ("loss", "fwd"): 0.3,
        ("moe.route", "recompute"): 0.3})
    assert s.unresolved == pytest.approx({"mystery": 0.05})
    assert s.busy_s == pytest.approx(1.15)
    assert s.share(("loss",)) == pytest.approx(100 * 0.3 / 1.15)
    assert s.share(phases=("recompute",)) == pytest.approx(100 * 0.3 / 1.15)
    # every busy second is counted once
    assert s.share() + 100 * s.unresolved_s / s.busy_s == pytest.approx(100)


def test_unresolved_ops_over_a_hundredth_of_busy_time_stop_the_run(capsys):
    red, names = _synthetic()
    text = "\n".join(f'  %{n} = f32[] op(), metadata={{op_name="{p}"}}'
                     for n, p in names.items())
    ctx = _ctx(red, "ENTRY %main (p: f32[]) -> f32[] {\n" + text + "\n}")
    with pytest.raises(SystemExit, match="mystery"):
        run.read_layer_metric("train_step.optimizer_share", ctx)
    assert "[trace] by scope" in capsys.readouterr().err


def test_the_readers_read_nothing_outside_a_traced_training_run():
    red, names = _synthetic()
    for name in NEW:
        assert run.read_layer_metric(name, _ctx(red, "", traced=0)) is None


def test_the_new_readers_on_the_recorded_scoped_trace(capsys):
    red, text = _recorded()
    ctx = _ctx(red, text)
    got = {name: run.read_layer_metric(name, ctx) for name in NEW}
    assert all(v is not None and 0 < v < 100 for v in got.values()), got
    line = capsys.readouterr().err
    assert "[trace] by scope" in line and '"unscoped"' in line
    split = ctx.scopes
    assert split.unresolved_s < scopes.MAX_UNRESOLVED * split.busy_s
    # each recorded step holds every scope of the train step
    assert set(split.table()) >= {"embed", "attn", "moe.route",
                                  "moe.dispatch", "moe.experts",
                                  "moe.combine", "lm_head", "loss",
                                  "optimizer"}
    # the shares and the kernels' time fit in the busy time
    _, kernel_s = red.kernel(KERNELS)
    shares = sum(got[n] for n in NEW[:3])
    assert shares / 100 * split.busy_s + kernel_s <= split.busy_s
    # every busy second is counted once
    assert split.share() + 100 * split.unresolved_s / split.busy_s == \
        pytest.approx(100, abs=1)


def test_the_scopes_left_the_compiled_step_as_it_was():
    """The trace recorded before the program had scopes names its ops
    as the scoped step's text does: not one instruction moved."""
    _, text = _recorded()
    old = trace.load(DATA / "tiny_train.xplane.pb")
    split = scopes.split(old, scopes.op_names(text))
    assert split.unresolved == {}


def test_a_program_without_scopes_reads_zero_not_nothing():
    """A program without the scopes (the parent of the change that added
    them) has its ops resolved and its shares read 0, so its traced run
    goes on; the recompute share reads the paths JAX writes itself."""
    red, text = _recorded()
    bare = re.sub(r"(jvp|transpose)\((?:"
                  + "|".join(re.escape(s) for s in scopes.SCOPES)
                  + r")\)", r"\1()", text)
    bare = re.sub(r"/(?:" + "|".join(re.escape(s) for s in scopes.SCOPES)
                  + r")(?=/|\")", "", bare)
    ctx = _ctx(red, bare)
    got = {name: run.read_layer_metric(name, ctx) for name in NEW}
    assert got["train_step.moe_dispatch_share"] == 0.0
    assert got["train_step.lm_head_share"] == 0.0
    assert got["train_step.optimizer_share"] == 0.0
    assert got["train_step.recompute_share"] > 0


# The accepted readers on the recorded train traces, as they read before
# the scopes were added (the parent's bench code on the same files).
BEFORE = {
    ("tiny_train", "train_step.mfu"): 0.038442093745014186,
    ("tiny_train", "grouped_mlp_roofline.train"): 6.622657763832362,
    ("tiny_train", "flash_attention_roofline"): 2.8724593384020825,
    ("tiny_train", "device.idle_share.train"): 74.88280705597307,
    ("tiny_train_scoped", "train_step.mfu"): 0.03835868703497624,
    ("tiny_train_scoped", "grouped_mlp_roofline.train"): 6.39792098807736,
    ("tiny_train_scoped", "flash_attention_roofline"): 2.87250230262929,
    ("tiny_train_scoped", "device.idle_share.train"): 98.40686477865097,
}


@pytest.mark.parametrize("recording,name", [
    (rec, name) for rec in ("tiny_train", "tiny_train_scoped")
    for name in ("train_step.mfu", "grouped_mlp_roofline.train",
                 "flash_attention_roofline", "device.idle_share.train")])
def test_accepted_readers_read_as_before(recording, name):
    red = trace.load(DATA / f"{recording}.xplane.pb")
    got = run.read_layer_metric(name, _ctx(red, None))
    assert got == pytest.approx(BEFORE[recording, name], rel=1e-12)
