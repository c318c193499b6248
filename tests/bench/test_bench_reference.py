"""The benchmark's plain reference against the program, at a test size
on the CPU: the same weights from a seed, the same logits and loss."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _bench_path import tiny

import model
from reference import granite as ref


@pytest.mark.parametrize("expert_init", ["copy", "copy_noise"])
def test_program_upcycle_gives_the_reference_weights(expert_init):
    conf = tiny(expert_init=expert_init)
    cfg, dims = model.arch_of(conf), model.dims_of(conf)
    key = model.key_of(2 ** 31 + 17)
    got = model.program_weights(cfg, dims)(key)
    want = model.reference_weights(dims)(key)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    experts = want["stack"]["segments"][0]["pos0"]["ffn"]["experts"]["wi"]
    same = bool(jnp.all(experts[:, 0] == experts[:, 1]))
    assert same == (expert_init == "copy")


def test_reference_logits_and_loss_match_the_program():
    from repro.models import model_zoo as zoo

    conf = tiny()
    cfg, dims = model.arch_of(conf), model.dims_of(conf)
    key = model.key_of(5)
    w = model.reference_weights(dims)(key)
    toks = jax.random.randint(key, (2, 64), 0, dims["V"])
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}
    ac = zoo.ApplyCfg(dispatch="sorted", sorted_block=8)
    with jax.default_matmul_precision("highest"):
        got, _ = zoo.forward_train(w, batch, cfg, ac=ac)
        want = jnp.stack([ref.logits(w, toks[i], dims, q_block=16)
                          for i in range(2)])
        loss, mets = zoo.loss_fn(w, batch, cfg, ac=ac)
        rloss, rce = ref.loss(w, batch, dims, q_block=16)
    # float32 summation order only
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(
        jnp.abs(want).max())
    assert abs(float(loss) - float(rloss)) < 1e-5
    assert abs(float(mets["ce"]) - float(rce)) < 1e-5


def test_bfloat16_reference_departs_from_float32():
    conf = tiny()
    dims = model.dims_of(conf)
    w = model.reference_weights(dims)(model.key_of(3))
    toks = jnp.arange(64) % dims["V"]
    with jax.default_matmul_precision("highest"):
        a = ref.logits(w, toks, dims, q_block=16)
    b = ref.logits(w, toks, dims, dtype=jnp.bfloat16, q_block=16)
    assert b.dtype == jnp.float32
    assert 1e-4 < float(jnp.abs(a - b).max()) < 0.5
