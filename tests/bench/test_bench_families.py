"""A configuration's family (``bench/families/<family>.py``).

Granite through its family gives exactly what the harness gave when it
was wired to granite: the same sizes, ``ArchConfig``, whole-model FLOP
counts and reference weights (the literals below are that harness's).
And a family that exists only as new files -- a copy of ``bench/`` with
a family, its configurations, traffic and ``BENCHMARK.json`` entries
added and no file edited -- runs a train and a chat cell ``correct``,
while a wrong reference in such a family fails them: the check uses the
family's own reference.
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


def _tiny(**moe):
    """The test-size configuration with the limits it states itself
    (0.001 each): the alias family reads about 1e-6 there, the wrong
    reference 0.01 (loss) and 0.24 (served logits) on the CPU."""
    conf = load(DATA / "tiny.json")
    conf["moe"].update(moe)
    return conf


def _add_family_as_new_files(root):
    """A checkout at ``root``: ``bench/`` copied, then only files added
    and entries appended to ``BENCHMARK.json``."""
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
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cells


@pytest.fixture(scope="module")
def new_family_runs(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    cells = _add_family_as_new_files(root)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", DRIVE, *cells], cwd=root,
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


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
