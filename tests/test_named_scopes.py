"""The program names its parts with ``jax.named_scope``, and the names
reach the compiled HLO: the train step and the paged mixed serve step,
compiled at a tiny size, carry every scope in the ``op_name`` metadata
of their instructions — the train step's forward, backward and
recomputed forward alike, through every MoE dispatch path."""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced
from repro.models import model_zoo as zoo
from repro.optim import adafactor, constant
from repro.training.train_loop import init_train_state, make_train_step

TRAIN = {"embed", "attn", "moe.route", "moe.dispatch", "moe.experts",
         "moe.combine", "lm_head", "loss", "optimizer"}
SERVE = {"embed", "attn", "kv.write", "moe.route", "moe.dispatch",
         "moe.experts", "moe.combine", "lm_head", "sample"}
MOE = {"moe.route", "moe.dispatch", "moe.experts", "moe.combine"}


@pytest.fixture(scope="module")
def cfg():
    c = get_reduced("granite-moe-1b-a400m")
    return dataclasses.replace(c, moe=dataclasses.replace(
        c.moe, capacity_factor=float(c.moe.num_experts)))


def _paths(hlo_text):
    return re.findall(r'op_name="([^"]*)"', hlo_text)


def _scopes(path):
    """The path's segments with their transforms peeled off:
    ``transpose(jvp(loss))`` -> ``loss``."""
    out = set()
    for seg in path.split("/"):
        while m := re.fullmatch(r"[\w-]+\((.*)\)", seg):
            seg = m.group(1)
        out.add(seg)
    return out


def _sds(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)


@pytest.mark.parametrize("dispatch", ["sorted", "gather", "einsum"])
def test_train_step_hlo_names_every_scope(cfg, dispatch):
    opt = adafactor(constant(1e-3))
    ac = zoo.ApplyCfg(dispatch=dispatch, sorted_block=8, remat="full")
    step = jax.jit(make_train_step(cfg, opt, ac=ac))
    state = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), cfg, opt))
    tok = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    batch = {"tokens": tok, "targets": tok}
    text = step.lower(state, batch, jax.ShapeDtypeStruct((), jnp.float32)
                      ).compile().as_text()
    paths = _paths(text)
    seen = set().union(*map(_scopes, paths))
    assert TRAIN <= seen, TRAIN - seen
    # the backward and the forward that remat="full" recomputes keep the
    # MoE scopes in their paths
    bwd = set().union(*(_scopes(p) for p in paths if "transpose(" in p))
    rec = set().union(*(_scopes(p) for p in paths
                        if "rematted_computation" in p))
    assert {"moe.route", "moe.dispatch", "moe.combine"} <= bwd
    assert MOE <= rec, MOE - rec


def test_mixed_serve_step_hlo_names_every_scope(cfg):
    B, NC, C, nb, bs, blocks = 3, 2, 8, 4, 8, 16
    params = zoo.init_params(jax.random.PRNGKey(0), cfg)
    from repro.models import param as pm

    params = _sds(pm.split(params)[0])
    cache = _sds(zoo.init_paged_serve_cache(cfg, blocks, bs,
                                            dtype=jnp.float32))
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    ac = zoo.ApplyCfg(dispatch="sorted", sorted_block=8)
    step = jax.jit(functools.partial(zoo.paged_mixed_step, cfg=cfg, ac=ac))
    text = step.lower(params, i32((B, 1)), i32((NC, C)), cache,
                      i32((B, nb)), i32((B,)), i32((NC, nb)), i32((NC,)),
                      i32((NC,))).compile().as_text()
    seen = set().union(*map(_scopes, _paths(text)))
    assert SERVE <= seen, SERVE - seen
