"""Per-assigned-architecture smoke tests (reduced configs).

Each of the 10 assigned archs (+ the paper's own T5/ViT upcycling configs)
instantiates its reduced config and runs one forward + one train step on
CPU, asserting output shapes and finiteness.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import assigned_archs, get_reduced
from repro.data import make_iterator
from repro.models import model_zoo as zoo
from repro.models import param as pm
from repro.optim import adafactor, constant
from repro.training.train_loop import init_train_state, make_train_step

ALL = assigned_archs() + ["t5-base-upcycled", "vit-b16-upcycled"]


@pytest.mark.parametrize("arch", ALL)
def test_smoke_forward_and_train_step(arch):
    cfg = get_reduced(arch)
    it = make_iterator(cfg, global_batch=4, seq_len=32,
                       host_index=0, host_count=1)
    batch = next(it)
    opt = adafactor(constant(1e-3))
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt)

    if cfg.structure == "encoder_only":
        logits, _ = zoo.forward_train(state["params"], batch, cfg)
        assert logits.shape == (4, cfg.vocab_size)
    else:
        logits, mets = zoo.forward_train(state["params"], batch, cfg)
        S = batch["targets"].shape[1]
        assert logits.shape == (4, S, cfg.vocab_size)
        if cfg.moe is not None:
            assert float(mets["moe_layer_count"]) > 0
    assert bool(jnp.isfinite(logits).all()), arch

    step = jax.jit(make_train_step(cfg, opt))
    state2, mets = step(state, batch)
    assert np.isfinite(float(mets["loss"])), arch
    assert int(state2["step"]) == 1
    # params actually changed
    d0 = jax.tree.leaves(state["params"])[0]
    d1 = jax.tree.leaves(state2["params"])[0]
    assert float(jnp.abs(d0 - d1).max()) > 0


@pytest.mark.parametrize("arch", [a for a in ALL])
def test_smoke_full_config_registered(arch):
    from repro.configs import get_config

    cfg = get_config(arch)
    red = get_reduced(arch)
    assert cfg.family == red.family
    assert cfg.structure == red.structure
    assert (cfg.moe is None) == (red.moe is None)


def _stack_init_per_layer(rng, cfg, descs):
    """The per-layer loop ``stack.stack_init`` replaces with ``vmap``."""
    from repro.models import stack as stk

    out, layer = [], 0
    for reps, pdescs in stk.find_segments(descs):
        per_pos = {f"pos{i}": [] for i in range(len(pdescs))}
        for _ in range(reps):
            for i, d in enumerate(pdescs):
                per_pos[f"pos{i}"].append(stk.layer_init(
                    jax.random.fold_in(rng, layer), cfg, d
                ))
                layer += 1
        out.append({k: pm.stack_layers(v) for k, v in per_pos.items()})
    return {"segments": out}


@pytest.mark.parametrize("arch", ALL)
def test_stack_init_matches_per_layer_loop(arch):
    from repro.models import stack as stk

    cfg = get_reduced(arch)
    rng = jax.random.PRNGKey(3)
    stacks = ["decoder"] + (["encoder"] if cfg.structure == "encoder_decoder"
                            else [])
    for which in stacks:
        descs = stk.layer_descs(cfg, stack=which)
        got = stk.stack_init(rng, cfg, descs)
        want = _stack_init_per_layer(rng, cfg, descs)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        is_p = lambda x: isinstance(x, pm.Param)
        for g, w in zip(jax.tree.leaves(got, is_leaf=is_p),
                        jax.tree.leaves(want, is_leaf=is_p)):
            assert g.axes == w.axes
            np.testing.assert_array_equal(g.value, w.value)


def test_attention_projections_init_by_d_model():
    """q/k/v projections are (d, heads, head_dim): their fan-in is d, so
    q.k logits start O(1) and the softmax is not a near-argmax that a
    bf16 rounding of its operands reshuffles."""
    from repro.models.attention import attention_init

    cfg = get_reduced("granite-moe-1b-a400m")
    p = attention_init(jax.random.PRNGKey(0), cfg)
    # std of a [-2, 2] truncated unit normal
    trunc_std = 0.8796
    for name in ("wq", "wk", "wv"):
        std = float(jnp.std(p[name].value)) * cfg.d_model ** 0.5
        assert abs(std - trunc_std) < 0.05, (name, std)
