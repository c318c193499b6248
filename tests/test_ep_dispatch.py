"""Expert-parallel sorted dispatch parity on a forced multi-device CPU
mesh: sorted-EP (shard_map ragged all-to-all, core/ep.py) vs the
single-device sorted path — outputs AND gradients, all three routers, uneven expert load and empty local
experts.

Needs >= 8 CPU devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest tests/test_ep_dispatch.py

scripts/verify.sh runs exactly that; in the plain tier-1 run (1 device)
the whole module skips.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core.moe import moe_apply, moe_init
from repro.models import param as pm

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(see scripts/verify.sh)",
)

ROUTERS = ["top_k", "expert_choice", "switch"]


def _mesh_ctx():
    from repro.launch.mesh import ep_degree, make_debug_mesh
    from repro.sharding import ShardCtx

    mesh = make_debug_mesh((2, 4), ("data", "model"))
    assert ep_degree(mesh) == 4
    return mesh, ShardCtx.for_mesh(mesh)


@pytest.fixture(scope="module")
def setup():
    from repro.launch.mesh import ep_degree

    mesh, ctx = _mesh_ctx()
    cfg = get_reduced("grok-1-314b")  # E=4: divides the 4-wide model axis
    # 8 groups of 16 tokens -> one group per device on the 8-device mesh;
    # budget factor >= ep guarantees no EP overflow drops (core/ep.py).
    moe = dataclasses.replace(
        cfg.moe, group_size=16, ep="a2a",
        ep_budget_factor=2.0 * ep_degree(mesh),
    )
    cfg = dataclasses.replace(cfg, moe=moe)
    p = moe_init(jax.random.PRNGKey(0), cfg, cfg.moe)
    vals, _ = pm.split(p)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, cfg.d_model))
    return cfg, vals, x, ctx


def _apply(vals, x, cfg, moe, router, ctx, impl="xla"):
    return moe_apply(
        vals, x, cfg, moe, router_kind=router,
        ctx=ctx, implementation=impl, sorted_block=8,
    )


@pytest.mark.parametrize("router", ROUTERS)
def test_ep_matches_single_device_sorted_and_gather(setup, router):
    """Sorted-EP over the 8-device mesh reproduces the single-device
    sorted path exactly (no EP drops)."""
    cfg, vals, x, ctx = setup
    y_ep, m_ep = _apply(vals, x, cfg, cfg.moe, router, ctx)
    y_1d, m_1d = _apply(vals, x, cfg, cfg.moe, router, None)
    np.testing.assert_allclose(
        np.asarray(y_ep), np.asarray(y_1d), rtol=1e-4, atol=1e-5
    )
    assert float(m_ep["ep_overflow_frac"]) == 0.0
    assert float(m_ep["dropped_frac"]) == float(m_1d["dropped_frac"])


@pytest.mark.parametrize("router", ROUTERS)
def test_ep_gradients_match_single_device_sorted(setup, router):
    """Full jax.grad parity (router + expert weights + input) between
    the shard_map EP path and the single-device sorted path — the
    replicated-weight psum and a2a transposes must be exact."""
    cfg, vals, x, ctx = setup

    def loss(v, xv, ctx_):
        y, m = _apply(v, xv, cfg, cfg.moe, router, ctx_)
        return jnp.sum(y ** 2) + m["aux_loss"]

    g_ep = jax.grad(loss, argnums=(0, 1))(vals, x, ctx)
    g_1d = jax.grad(loss, argnums=(0, 1))(vals, x, None)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(g_ep),
        jax.tree_util.tree_leaves(g_1d),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
            err_msg=jax.tree_util.keystr(path),
        )


@pytest.mark.parametrize("router", ["top_k", "switch"])
def test_ep_empty_local_experts(setup, router):
    """Experts 2..3 get router weight columns of -30 (softmax mass
    ~1e-13: never in any top-k), so the mesh devices owning them
    receive zero rows — the grouped kernel's empty-segment contract
    must hold through the a2a (outputs + grads finite and matching the
    single-device path)."""
    cfg, vals, x, ctx = setup
    w = np.asarray(vals["router"]["w"]).copy()
    w[:, 2:] = -30.0
    vals = dict(vals, router={"w": jnp.asarray(w)})

    def loss(v, ctx_):
        y, m = _apply(v, x, cfg, cfg.moe, router, ctx_)
        return jnp.sum(y ** 2), y

    (l_ep, y_ep), g_ep = jax.value_and_grad(loss, has_aux=True)(vals, ctx)
    (l_1d, y_1d), g_1d = jax.value_and_grad(loss, has_aux=True)(vals, None)
    assert bool(jnp.isfinite(y_ep).all())
    np.testing.assert_allclose(
        np.asarray(y_ep), np.asarray(y_1d), rtol=1e-4, atol=1e-5
    )
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(g_ep),
        jax.tree_util.tree_leaves(g_1d),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
            err_msg=jax.tree_util.keystr(path),
        )


@pytest.mark.parametrize("router", ROUTERS)
def test_ep_uneven_load(setup, router):
    """Skewed router (one dominant expert) with a generous capacity
    factor: per-peer recv counts are far from balanced, parity must
    still hold (the budget covers the skew)."""
    cfg, vals, x, ctx = setup
    moe = dataclasses.replace(cfg.moe, capacity_factor=4.0)
    w = np.asarray(vals["router"]["w"]).copy()
    w[:, 0] += 3.0  # expert 0 draws most assignments
    vals = dict(vals, router={"w": jnp.asarray(w)})
    y_ep, m_ep = _apply(vals, x, cfg, moe, router, ctx)
    y_1d, _ = _apply(vals, x, cfg, moe, router, None)
    np.testing.assert_allclose(
        np.asarray(y_ep), np.asarray(y_1d), rtol=1e-4, atol=1e-5
    )
    assert float(m_ep["ep_overflow_frac"]) == 0.0


def test_ep_budget_overflow_drops(setup):
    """A starved send-buffer budget (factor << 1) drops assignments:
    the overflow metric reports it and outputs stay finite."""
    cfg, vals, x, ctx = setup
    moe = dataclasses.replace(
        cfg.moe, ep_budget_factor=0.25, capacity_factor=4.0
    )
    w = np.asarray(vals["router"]["w"]).copy()
    w[:, 0] += 5.0  # pile onto one peer to force overflow
    vals = dict(vals, router={"w": jnp.asarray(w)})
    y, m = _apply(vals, x, cfg, moe, "top_k", ctx)
    assert bool(jnp.isfinite(y).all())
    assert float(m["ep_overflow_frac"]) > 0.0


@pytest.mark.parametrize("router", ["top_k"])
def test_ep_pallas_kernel_through_shard_map(setup, router):
    """The Pallas grouped-GEMM custom-VJP kernel (interpret mode on CPU)
    runs inside the shard_map EP path: outputs and grads match the XLA
    EP path. One router only — interpret-mode Pallas under shard_map is
    the slowest test here, and router coverage is already carried by the
    XLA-path parity tests above (the kernel is router-agnostic)."""
    cfg, vals, x, ctx = setup

    def loss(v, impl):
        y, m = _apply(v, x, cfg, cfg.moe, router, ctx, impl)
        return jnp.sum(y ** 2), y

    (l_p, y_p), g_p = jax.value_and_grad(loss, has_aux=True)(
        vals, "pallas"
    )
    (l_x, y_x), g_x = jax.value_and_grad(loss, has_aux=True)(vals, "xla")
    np.testing.assert_allclose(
        np.asarray(y_p), np.asarray(y_x), rtol=1e-4, atol=1e-5
    )
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(g_p),
        jax.tree_util.tree_leaves(g_x),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
            err_msg=jax.tree_util.keystr(path),
        )


def test_ep_group_count_divisibility_error(setup):
    """G not divisible by the device count raises the documented error
    instead of silently producing a wrong layout."""
    cfg, vals, _, ctx = setup
    x_bad = jax.random.normal(
        jax.random.PRNGKey(2), (3, 16, cfg.d_model)
    )  # 48 tokens -> G=3
    with pytest.raises(ValueError, match="divisible"):
        _apply(vals, x_bad, cfg, cfg.moe, "top_k", ctx)


def test_ep_fallback_without_capable_mesh(setup):
    """ep='a2a' with ctx=None (or an EP-incapable mesh) falls back to
    the single-device sorted path — same outputs, no error."""
    cfg, vals, x, _ = setup
    y1, m1 = _apply(vals, x, cfg, cfg.moe, "top_k", None)
    moe_off = dataclasses.replace(cfg.moe, ep="none")
    y2, _ = _apply(vals, x, cfg, moe_off, "top_k", None)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))
    assert float(m1["ep_overflow_frac"]) == 0.0
