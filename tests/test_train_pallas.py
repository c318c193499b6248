"""End-to-end training through the Pallas kernels (interpret on CPU).

Acceptance: a full train_step under implementation="pallas" runs through
the custom-VJP kernels — grouped expert FFN and flash attention forward AND
backward — without falling back to XLA einsums, and matches the XLA step.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.data import make_iterator
from repro.models import model_zoo as zoo
from repro.optim import adafactor, constant
from repro.training import make_train_step
from repro.training.train_loop import init_train_state


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced("grok-1-314b")  # MoE decoder (attn + expert FFN)
    it = make_iterator(cfg, global_batch=2, seq_len=16, host_index=0,
                       host_count=1)
    return cfg, next(it)


def _one_step(cfg, batch, ac):
    opt = adafactor(constant(1e-3))
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
    step = jax.jit(make_train_step(cfg, opt, ac=ac))
    return step(state, batch)


def test_train_step_pallas_matches_xla(setup):
    cfg, batch = setup
    _, m_p = _one_step(
        cfg, batch,
        zoo.ApplyCfg(moe_impl="pallas", attn_impl="pallas"),
    )
    _, m_x = _one_step(
        cfg, batch, zoo.ApplyCfg(moe_impl="xla", attn_impl="xla")
    )
    assert np.isfinite(float(m_p["loss"]))
    np.testing.assert_allclose(
        float(m_p["loss"]), float(m_x["loss"]), rtol=1e-5
    )
    np.testing.assert_allclose(
        float(m_p["grad_norm"]), float(m_x["grad_norm"]), rtol=1e-3
    )


def test_train_step_pallas_moe_remat(setup):
    """The MoE-boundary remat policy composes with the Pallas VJPs."""
    cfg, batch = setup
    _, m = _one_step(
        cfg, batch,
        zoo.ApplyCfg(moe_impl="pallas", attn_impl="pallas", remat="moe"),
    )
    _, m_x = _one_step(
        cfg, batch, zoo.ApplyCfg(moe_impl="xla", attn_impl="xla")
    )
    np.testing.assert_allclose(
        float(m["loss"]), float(m_x["loss"]), rtol=1e-5
    )


def test_applycfg_auto_resolves_to_backend_default():
    ac = zoo.ApplyCfg().resolve()
    assert ac.moe_impl in ("xla", "pallas")
    assert ac.attn_impl == ac.moe_impl
    # On the CPU test runner "auto" must pick the XLA path.
    if jax.default_backend() == "cpu":
        assert ac.moe_impl == "xla"


def test_train_step_sorted_dispatch_matches_ref(setup):
    """A full train_step through the sorted dispatch's XLA ragged_dot
    path matches the same step on the one-hot ``ref`` expert FFN."""
    cfg, batch = setup
    _, m_s = _one_step(cfg, batch, zoo.ApplyCfg(moe_impl="xla"))
    _, m_g = _one_step(cfg, batch, zoo.ApplyCfg(moe_impl="ref"))
    assert np.isfinite(float(m_s["loss"]))
    np.testing.assert_allclose(
        float(m_s["loss"]), float(m_g["loss"]), rtol=1e-5
    )
    np.testing.assert_allclose(
        float(m_s["grad_norm"]), float(m_g["grad_norm"]), rtol=1e-3
    )


def test_train_step_sorted_dispatch_pallas_kernels(setup):
    """implementation="pallas": the grouped-GEMM custom-VJP kernels
    (interpret mode on CPU) carry the train step and match the one-hot
    ``ref`` expert FFN."""
    cfg, batch = setup
    _, m_p = _one_step(
        cfg, batch,
        zoo.ApplyCfg(moe_impl="pallas", attn_impl="xla", sorted_block=8),
    )
    _, m_x = _one_step(cfg, batch, zoo.ApplyCfg(moe_impl="ref"))
    np.testing.assert_allclose(
        float(m_p["loss"]), float(m_x["loss"]), rtol=1e-5
    )
