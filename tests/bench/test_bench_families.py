"""A configuration's family (``bench/families/<family>.py``).

Granite through its family gives exactly what the harness gave when it
was wired to granite: the same sizes, ``ArchConfig``, whole-model FLOP
counts and reference weights (the literals below are that harness's).
And a family that exists only as new files -- a copy of ``bench/`` with
a family, its configurations, traffic and ``BENCHMARK.json`` entries
added and no file edited -- runs a train and a chat cell ``correct``,
while a wrong reference in such a family fails them: the check uses the
family's own reference. Such a family also brings its own scopes and
readers: the traced split counts its scope, a reader reads that scope's
share and another a counter of the train step's, and a family scope
that repeats a shared one is refused.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from _bench_path import BENCH, DATA, ROOT, load, with_serving

import flops
import model
import run

CONFS = {
    "granite-moe-1b-a400m": BENCH / "configs/granite-moe-1b-a400m.json",
    "granite-moe-3b-a800m": BENCH / "configs/granite-moe-3b-a800m.json",
    "granite-tiny": DATA / "tiny.json",
}
GRANITE = dict(theta=10000.0, eps=1e-06, aux_weight=0.01, router_std=0.02)
# the dims, train step (batch, seq) and one mixed serve step (rows,
# ctx_sum, head_rows) of each file, with their counts, and the sha256 of
# the reference's weights from seed 2**31 + 17 (the granite files at
# their widths one layer deep, so that the test holds a few hundred MB)
SEED = {
    "granite-moe-1b-a400m": dict(
        dims=dict(d=1024, L=24, H=16, Kh=8, dh=64, f=512, E=32, k=8,
                  V=49155, group=4096, noise_std=0.0, expert_init="copy",
                  **GRANITE),
        train=((2, 4096), 26014767906816.0),
        serve=((576, 64 * 2048 + 512 * 1024.5, 66), 506865266688.0),
        weights="8fcf77a970a7b11ffd6bb4d6b5714f93"
                "474def2d9f318e01f23a325772938468"),
    "granite-moe-3b-a800m": dict(
        dims=dict(d=1536, L=8, H=24, Kh=8, dh=64, f=512, E=40, k=8,
                  V=49155, group=4096, noise_std=0.0, expert_init="copy",
                  **GRANITE),
        train=((2, 4096), 16104743239680.0),
        serve=((576, 64 * 2048 + 512 * 1024.5, 66), 274685577216.0),
        weights="8cff6b42118fb12da8918c0bce1512da"
                "5b5c00ed9b187255da124759e84b8e05"),
    "granite-tiny": dict(
        dims=dict(d=64, L=2, H=4, Kh=2, dh=16, f=32, E=8, k=4, V=259,
                  group=64, noise_std=0.05, expert_init="copy_noise",
                  **GRANITE),
        train=((2, 64), 76431360.0),
        serve=((36, 4 * 40 + 32 * 20.5, 6), 5998848.0),
        weights="84ae01fcf0e4088943f3d56409064e7a"
                "b7c9c8ffee6bcaccb1eaac244101483e"),
}


def _seed_arch(conf):
    """The ``ArchConfig`` the granite-wired harness built, field by field
    (its defaults are the class's)."""
    from repro.configs import ArchConfig, MoECfg

    d = SEED[conf["name"]]["dims"]
    return ArchConfig(
        name=conf["name"], family="moe", structure="decoder_only",
        n_layers=d["L"], d_model=d["d"], n_heads=d["H"], n_kv_heads=d["Kh"],
        d_head=d["dh"], d_ff=d["f"], vocab_size=d["V"], gated_mlp=True,
        norm="rmsnorm", pos_emb="rope", rope_theta=10000.0,
        tie_embeddings=True, act="silu", source=conf["source"],
        moe=MoECfg(num_experts=d["E"], router="top_k", top_k=d["k"],
                   capacity_factor=float(d["E"]), layer_pattern="all",
                   group_size=d["group"], aux_loss_weight=0.01,
                   normalize_combine_weights=False,
                   expert_init=d["expert_init"],
                   init_noise_std=d["noise_std"], router_init_std=0.02))


def _digest(tree) -> str:
    import jax

    h = hashlib.sha256()
    for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.ascontiguousarray(np.asarray(v))
        h.update(jax.tree_util.keystr(p).encode())
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.data)
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(CONFS))
def test_dims_and_arch_are_the_granite_harness_s(name):
    conf = load(CONFS[name])
    assert conf["family"] == "granite"
    assert model.dims_of(conf) == {**SEED[name]["dims"], "family": "granite"}
    assert model.arch_of(conf) == _seed_arch(conf)


@pytest.mark.parametrize("name", sorted(CONFS))
def test_whole_model_counts_are_the_granite_harness_s(name):
    dims = model.dims_of(load(CONFS[name]))
    (b, s), want = SEED[name]["train"]
    assert flops.train_step_flops(dims, b, s) == want
    shape, want = SEED[name]["serve"]
    assert flops.serve_flops(dims, *shape) == want


@pytest.mark.parametrize("name", sorted(CONFS))
def test_reference_weights_are_the_granite_harness_s(name):
    dims = model.dims_of(load(CONFS[name]))
    if name != "granite-tiny":
        dims["L"] = 1
    w = model.reference_weights(dims)(model.key_of(2 ** 31 + 17))
    assert _digest(w) == SEED[name]["weights"]


@pytest.mark.parametrize("spec", [{"name": "x"}, {"family": "nosuch"},
                                  {"family": "../reference/granite"}])
def test_a_missing_or_unknown_family_is_an_error_naming_those_on_disk(spec):
    with pytest.raises(ValueError,
                       match=r"is not one of .*: \[.*'granite'.*\]"):
        model.family_of(spec)


def test_a_mix_kind_without_a_driver_file_is_refused():
    assert run.driver_of({"kind": "train"}).run
    with pytest.raises(SystemExit, match="names no driver"):
        run.driver_of({"kind": "no_such_kind"})


# -- a family that joins as new files only -----------------------------------

TOP_K = "    top_w, top_e = jax.lax.top_k(probs, k)\n"
RENORM = TOP_K + "    top_w = top_w / top_w.sum(-1, keepdims=True)\n"
FAMILY = '''"""granite's family under another name, with reference {ref}."""
import model
from reference import {ref} as reference  # noqa: F401

_granite = model.family_of({{"family": "granite"}})
dims_of, arch_of = _granite.dims_of, _granite.arch_of
matmul_params_per_token = _granite.matmul_params_per_token
attn_flops = _granite.attn_flops
'''
DRIVE = '''import json, sys, types
sys.path.insert(0, "bench")
import jax
import run
bench = run.load_bench()
out = {}
for name in sys.argv[1:]:
    cell, conf, mix = run.cell_of(bench, name)
    args = types.SimpleNamespace(seed=2 ** 31 + 77, seconds=1.5, trace=0)
    out[name] = run.measure(bench, cell, conf, mix, args, jax.devices(),
                            {}, run.CompileClock())
print(json.dumps(out))
'''
# family -> its reference module: granite's own, or one that renormalises
# the top-k weights (granite's recipe leaves them as the softmax gives)
NEW = {"granite_alias": "granite", "granite_renorm": "granite_renorm"}
# a family with a scope of its own, and one whose scope repeats a shared
# one; the first has a training cell and two readers of its own
SCOPED = {"granite_scoped": ("attn.latent",),
          "granite_clash": ("attn.latent", "moe.dispatch")}
SCOPED_CELL = "granite_scoped.finetune"
READERS = {
    "granite_scoped.latent_share": '''"""Share of busy time under the
family's own scope."""
import scopes


def read(ctx):
    split = scopes.of(ctx)
    return None if split is None else split.share(("attn.latent",))
''',
    "granite_scoped.aux_loss": '''"""The traced steps' mean aux loss, a
counter of the train step's."""
import statistics


def read(ctx):
    got = ctx.res.get("step_metrics", {}).get("aux_loss")
    return statistics.fmean(got) if got else None
''',
}
# ops and their paths for the scoped cell's split: the family's scope
# inside a shared one, at the top of a differentiated function, and an
# unscoped loop (seconds of own time: attn 0.2, attn.latent 0.2 fwd +
# 0.3 bwd, unscoped 0.1, of 0.8 busy)
SCOPED_OPS = [("fusion.1", 0.0, 0.4, ".../attn/bshk,hkd->bsd"),
              ("dot.2", 0.1, 0.2, ".../attn/attn.latent/dot"),
              ("dot.3", 0.5, 0.3, "jit(train_step)/transpose("
                                  "jvp(attn.latent))/dot"),
              ("while.4", 0.8, 0.1, "jit(train_step)/while")]
SCOPED_DRIVE = '''import json, sys, tempfile, time, types
from pathlib import Path
sys.path.insert(0, "bench")
import model, run, scopes, train
trace = run.bench_module("trace")
bench = run.load_bench()
cell, conf, mix = run.cell_of(bench, sys.argv[1])
ops = json.loads(sys.argv[2])
out = {}
for t, kind in enumerate(("untraced", "traced")):
    with tempfile.TemporaryDirectory() as d:
        args = types.SimpleNamespace(seed=2 ** 31 + 78, seconds=1.5,
                                     trace=t, trace_dir=Path(d))
        res = train.run(conf, mix, args, run.CompileClock(),
                        time.perf_counter(), run.log)
    out[kind] = {"traced_steps": res["traced_steps"],
              "step_metrics": res["step_metrics"]}
red = trace.Reduced((0.0, 1.0), [[trace.Op(n, a, d) for n, a, d, _ in ops]])
hlo = "ENTRY %main (p: f32[]) -> f32[] {\\n" + "\\n".join(
    f\'  %{n} = f32[] op(), metadata={{op_name="{p}"}}\' for n, _, _, p in ops
) + "\\n}"
ctx = types.SimpleNamespace(dims=model.dims_of(conf), peak={}, item=4,
                            trace=red, mix=mix, conf=conf, res=res, hlo=hlo)
out["metrics"] = run.layer_metrics(bench, cell, ctx)
out["table"] = ctx.scopes.table()
ctx.res = {"traced_steps": 2}
try:
    run.layer_metrics(bench, cell, ctx)
except SystemExit as e:
    out["missing"] = str(e)
try:
    scopes.scopes_of(model.dims_of(conf | {"family": "granite_clash"}))
except ValueError as e:
    out["clash"] = str(e)
print(json.dumps(out))
'''


def _tiny(**moe):
    """The test-size configuration with the limits it states itself
    (0.001 each): the alias family reads about 1e-6 there, the wrong
    reference 0.01 (loss) and 0.24 (served logits) on the CPU."""
    conf = load(DATA / "tiny.json")
    conf["moe"].update(moe)
    return conf


def _add_family_as_new_files(root):
    """A checkout at ``root``: ``bench/`` copied, then only files added
    and entries appended to ``BENCHMARK.json``. Returns the cells of
    ``NEW``'s families."""
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(ROOT / "src")
    b = root / "bench"
    src = (b / "reference/granite.py").read_text()
    assert src.count(TOP_K) == 1
    (b / "reference/granite_renorm.py").write_text(src.replace(TOP_K, RENORM))
    for mix in ("tiny_train", "tiny_chat"):
        shutil.copy(DATA / f"{mix}.json", b / "traffic" / f"{mix}.json")
    bench = with_serving(run.load_bench())
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = []
    for fam, ref in NEW.items():
        (b / "families" / f"{fam}.py").write_text(FAMILY.format(ref=ref))
        for cell, mix, conf, metrics in (
                (f"{fam}.finetune", "tiny_train", _tiny(expert_init="copy"),
                 ["train_tok_s"]),
                (f"{fam}.chat", "tiny_chat", _tiny(),
                 ["ttft_p95_ms", "tpot_p95_ms", "output_tok_s"])):
            conf |= {"name": f"{cell}-tiny", "family": fam}
            path = f"bench/configs/{conf['name']}.json"
            (root / path).write_text(json.dumps(conf))
            bench["configs"].append({
                "name": conf["name"], "source": conf["source"],
                "file": path, "reduced": [], "why": "test size"})
            bench["workloads"].append({
                "name": cell, "config": conf["name"], "traffic": mix,
                "chips": 1, "why": "test size"})
            for m in metrics:
                e2e[m]["workloads"].append(cell)
            cells.append(cell)
    for fam, own in SCOPED.items():
        (b / "families" / f"{fam}.py").write_text(
            FAMILY.format(ref="granite") + f"SCOPES = {own!r}\n")
    conf = _tiny(expert_init="copy") | {"name": "granite-scoped-tiny",
                                        "family": "granite_scoped"}
    path = f"bench/configs/{conf['name']}.json"
    (root / path).write_text(json.dumps(conf))
    bench["configs"].append({"name": conf["name"], "source": conf["source"],
                             "file": path, "reduced": [], "why": "test size"})
    bench["workloads"].append({"name": SCOPED_CELL, "config": conf["name"],
                               "traffic": "tiny_train", "chips": 1,
                               "why": "test size"})
    e2e["train_tok_s"]["workloads"].append(SCOPED_CELL)
    for name, src in READERS.items():
        (b / "metrics" / f"{name}.py").write_text(src)
        bench["per_layer"].append({
            "name": name, "unit": "%", "better": "lower",
            "source": "device_trace", "layer": "family",
            "moves": "train_tok_s", "workloads": [SCOPED_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cells


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    return root, _add_family_as_new_files(root)


def _drive(root, script, *argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", script, *argv], cwd=root,
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def new_family_runs(checkout):
    root, cells = checkout
    return _drive(root, DRIVE, *cells)


@pytest.fixture(scope="module")
def scoped_family_run(checkout):
    return _drive(checkout[0], SCOPED_DRIVE, SCOPED_CELL,
                  json.dumps(SCOPED_OPS))


@pytest.mark.parametrize("kind", ["finetune", "chat"])
def test_a_family_added_as_new_files_runs_correct(new_family_runs, kind):
    line = new_family_runs[f"granite_alias.{kind}"]
    assert line["correct"] is True, line["checks"]
    want = {"finetune": {"train_tok_s", "setup_s"},
            "chat": {"ttft_p95_ms", "tpot_p95_ms", "output_tok_s",
                     "setup_s"}}[kind]
    assert set(line["metrics"]) == want


@pytest.mark.parametrize("kind", ["finetune", "chat"])
def test_the_check_uses_the_family_s_own_reference(new_family_runs, kind):
    line = new_family_runs[f"granite_renorm.{kind}"]
    assert line["correct"] is False, line["checks"]


def test_a_family_s_own_scope_counts_in_the_split(scoped_family_run):
    got = scoped_family_run
    assert got["metrics"]["granite_scoped.latent_share"]["value"] == \
        pytest.approx(100 * 0.5 / 0.8)
    # the family's scope follows the shared ones, unscoped time last
    assert list(got["table"]) == ["attn", "attn.latent", "unscoped"]
    assert got["table"]["attn"]["fwd"] == pytest.approx(0.2)
    assert got["table"]["attn.latent"] == pytest.approx(
        {"fwd": 0.2, "recompute": 0.0, "bwd": 0.3})


def test_a_family_scope_that_repeats_a_shared_one_is_refused(
        scoped_family_run):
    msg = scoped_family_run["clash"]
    assert "'granite_clash'" in msg
    assert "repeats the shared scope 'moe.dispatch'" in msg


def test_the_train_driver_hands_readers_the_traced_steps_counters(
        scoped_family_run):
    got = scoped_family_run
    untraced, traced = got["untraced"], got["traced"]
    assert untraced == {"traced_steps": 0, "step_metrics": {}}
    n = traced["traced_steps"]
    assert n == load(DATA / "tiny_train.json")["trace_steps"]
    counters = traced["step_metrics"]
    assert {"loss", "aux_loss", "dropped_frac_sum"} <= set(counters)
    assert all(len(v) == n for v in counters.values()), counters
    aux = counters["aux_loss"]
    assert got["metrics"]["granite_scoped.aux_loss"]["value"] == \
        pytest.approx(sum(aux) / n)
    # a reader whose counter is missing reads nothing: the run stops
    assert "'granite_scoped.aux_loss' read nothing" in got["missing"]
