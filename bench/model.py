"""A configuration file -> its family (``bench/families/``), the
program's ``ArchConfig``, the reference's sizes, and weights made from a
seed.

The benchmark makes the dense parent itself (``reference.dense_parent``)
and hands it to the program's ``upcycle_params``: the users' first step,
dense checkpoint -> MoE. The reference upcycles the same parent with its
own code, so it takes no weight the program made.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

import jax

FAMILIES = Path(__file__).resolve().parent / "families"


def load_config(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_by_path(key: str, path: Path):
    """The module in the file ``path``, loaded once as
    ``sys.modules[key]``, so that a name such as ``trace`` cannot resolve
    to another module of that name."""
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


def family_of(conf: dict):
    """The family module that ``conf`` (a configuration file or its
    ``dims``) names under ``"family"``: ``bench/families/<family>.py``.
    A missing or unknown family is an error that names the families on
    disk; there is no default."""
    name = conf.get("family")
    path = FAMILIES / f"{name}.py"
    if not (isinstance(name, str) and name.isidentifier()
            and path.is_file()):
        have = sorted(p.stem for p in FAMILIES.glob("*.py"))
        raise ValueError(f"family {name!r} of {conf.get('name', 'dims')!r}"
                         f" is not one of {FAMILIES}: {have}")
    return load_by_path(f"bench_family_{name}", path)


def dims_of(conf: dict) -> dict:
    """The family's sizes and settings of ``conf``, with the family's
    name, so that whatever is handed ``dims`` finds the family again."""
    return {**family_of(conf).dims_of(conf), "family": conf["family"]}


def arch_of(conf: dict):
    """The program's ``ArchConfig`` for ``conf``, by its family."""
    return family_of(conf).arch_of(conf)


def key_of(seed: int):
    """A PRNG key from any non-negative seed, also past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def program_weights(cfg, dims):
    """Jitted ``seed key -> MoE value tree``: the reference's dense
    parent, wrapped with the program's axes and upcycled by the
    program's ``upcycle_params``, all in one device call."""
    from repro.core.upcycle import upcycle_params
    from repro.models import model_zoo as zoo
    from repro.models import param as pm

    ref = family_of(dims).reference
    dense_cfg = cfg.dense_parent()
    axes = pm.split(jax.eval_shape(
        lambda: zoo.init_params(jax.random.PRNGKey(0), dense_cfg)))[1]

    def build(key):
        k_dense, k_moe = jax.random.split(key)
        dense = ref.dense_parent(k_dense, dims)
        wrapped = pm.wrap(dense, axes)
        return pm.split(upcycle_params(wrapped, dense_cfg, cfg, k_moe))[0]

    return jax.jit(build)


def reference_weights(dims):
    """Jitted ``seed key -> MoE value tree`` by the reference alone."""
    ref = family_of(dims).reference

    def build(key):
        k_dense, k_moe = jax.random.split(key)
        return ref.upcycle(ref.dense_parent(k_dense, dims), k_moe, dims)

    return jax.jit(build)

