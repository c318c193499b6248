"""MoE layer tests: the sorted ragged dispatch against a plain dense
reference (tests/_moe_ref.py), gradients, grouping."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _moe_ref import moe_reference
from repro.configs import MoECfg, get_reduced
from repro.core.moe import moe_apply, moe_init
from repro.models import param as pm


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced("grok-1-314b")
    p = moe_init(jax.random.PRNGKey(0), cfg, cfg.moe)
    vals, axes = pm.split(p)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    return cfg, vals, x


def test_group_padding(setup):
    cfg, vals, _ = setup
    moe = dataclasses.replace(cfg.moe, group_size=24)  # 64 tokens -> pad
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, cfg.d_model))
    y, m = moe_apply(vals, x, cfg, moe)
    assert y.shape == x.shape
    assert bool(jnp.isfinite(y).all())


def test_router_gradients_flow(setup):
    cfg, vals, x = setup

    def loss(v):
        y, m = moe_apply(v, x, cfg, cfg.moe)
        return jnp.sum(y ** 2) + m["aux_loss"]

    g = jax.grad(loss)(vals)
    assert float(jnp.linalg.norm(g["router"]["w"])) > 0
    for k, gw in g["experts"].items():
        assert float(jnp.abs(gw).max()) > 0, k


def test_pallas_expert_impl_matches_xla(setup):
    cfg, vals, x = setup
    y1, _ = moe_apply(vals, x, cfg, cfg.moe, implementation="xla")
    y2, _ = moe_apply(vals, x, cfg, cfg.moe, implementation="pallas")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=2e-5, rtol=2e-5)


def test_moe_capacity_increase_reduces_drops(setup):
    cfg, vals, x = setup
    drops = []
    for c in [0.5, 1.0, 4.0]:
        moe = dataclasses.replace(cfg.moe, capacity_factor=c)
        _, m = moe_apply(vals, x, cfg, moe)
        drops.append(float(m["dropped_frac"]))
    assert drops[0] >= drops[1] >= drops[2]
    assert drops[2] == 0.0


# ---------------------------------------------------------------------------
# sorted ragged dispatch (grouped-GEMM path)
# ---------------------------------------------------------------------------

ROUTERS = ["top_k", "expert_choice", "switch"]


@pytest.mark.parametrize("block", [8, 128])
@pytest.mark.parametrize("router", ROUTERS)
def test_sorted_matches_reference(setup, router, block):
    """The sorted ragged dispatch reproduces the dense reference's
    outputs and drops for every router, at a small block and at the
    chip's 128-row block."""
    cfg, vals, x = setup
    y, m = moe_apply(vals, x, cfg, cfg.moe, router_kind=router,
                     sorted_block=block)
    want, m_ref = moe_reference(vals, x, cfg, cfg.moe, router)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert float(m["dropped_frac"]) == float(m_ref["dropped_frac"])


@pytest.mark.parametrize("router", ["top_k", "expert_choice"])
def test_moe_reference_matches_token_loop(router):
    """The reference itself, at a tiny size, against an explicit loop
    over each token's kept assignments and its experts' FFNs."""
    from repro.core import routing as R
    from repro.models.layers import activation

    cfg = get_reduced("grok-1-314b")
    moe = MoECfg(num_experts=4, router=router, top_k=2, group_size=8,
                 capacity_factor=0.75)
    vals, _ = pm.split(moe_init(jax.random.PRNGKey(5), cfg, moe))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 8, cfg.d_model))
    got, _ = moe_reference(vals, x, cfg, moe, router)

    xg = np.asarray(x, np.float64)  # group_size 8: one group per row
    logits = jnp.einsum("Ggd,de->Gge", x, vals["router"]["w"],
                        preferred_element_type=jnp.float32)
    r = R.route(logits, moe, router)
    tok, eid, w = (np.asarray(a) for a in R.assignment_stream(r, 4, 8))
    ex = {k: np.asarray(v, np.float64) for k, v in vals["experts"].items()}
    act = activation(cfg.act)
    want = np.zeros_like(xg)
    for G in range(xg.shape[0]):
        for t, e, wt in zip(tok[G], eid[G], w[G]):
            if t >= 8 or e >= 4:
                continue  # dropped assignment
            h = np.asarray(act(jnp.asarray(xg[G, t] @ ex["wi"][e])),
                           np.float64)
            if "wg" in ex:
                h = h * (xg[G, t] @ ex["wg"][e])
            want[G, t] += wt * (h @ ex["wo"][e])
    if router == "top_k":
        assert (eid >= 4).any()  # capacity 0.75 drops assignments
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("router", ROUTERS)
def test_sorted_matches_reference_dropped_tokens(setup, router):
    """Parity under capacity pressure (capacity_factor < 1): the sorted
    path must drop exactly the assignments the routers' capacity
    bookkeeping drops."""
    cfg, vals, _ = setup
    moe = dataclasses.replace(cfg.moe, capacity_factor=0.5)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, cfg.d_model))
    y1, m1 = moe_reference(vals, x, cfg, moe, router)
    y2, m2 = moe_apply(vals, x, cfg, moe, router_kind=router,
                       sorted_block=8)
    assert float(m1["dropped_frac"]) == float(m2["dropped_frac"])
    if router != "expert_choice":
        assert float(m1["dropped_frac"]) > 0.0  # pressure actually drops
    np.testing.assert_allclose(
        np.asarray(y1), np.asarray(y2), rtol=1e-4, atol=1e-5
    )


@pytest.mark.parametrize("router", ROUTERS)
def test_sorted_pad_tokens_reference(setup, router):
    """Group padding (group_size does not divide the token count): padded
    token rows round-trip the sorted path as they do the reference."""
    cfg, vals, _ = setup
    moe = dataclasses.replace(cfg.moe, group_size=24)  # 64 tokens -> pad
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, cfg.d_model))
    y1, _ = moe_reference(vals, x, cfg, moe, router)
    y2, _ = moe_apply(vals, x, cfg, moe, router_kind=router,
                      sorted_block=8)
    assert y2.shape == x.shape
    assert bool(jnp.isfinite(y2).all())
    np.testing.assert_allclose(
        np.asarray(y1), np.asarray(y2), rtol=1e-4, atol=1e-5
    )


TOKEN_CHOICE = ["top_k", "switch"]


@pytest.mark.parametrize(
    "capacity_factor, router, dead_tokens",
    [pytest.param(c, r, False, id=f"{c}-{r}")
     for r in ROUTERS for c in (0.5, 2.0)]
    + [pytest.param(c, r, True, id=f"{c}-{r}-dead_tokens")
       for r in TOKEN_CHOICE for c in (0.5, 2.0)],
)
def test_sorted_gradients_match_reference(setup, router, capacity_factor,
                                          dead_tokens):
    """Full jax.grad parity (router + expert weights + input) between the
    sorted dispatch and the reference, with and without capacity drops,
    and output parity. With a ``token_mask`` of dead tokens (token-choice
    routers only), dead tokens claim no rows and give zero outputs."""
    cfg, vals, x = setup
    moe = dataclasses.replace(cfg.moe, capacity_factor=capacity_factor)
    mask = (jax.random.bernoulli(jax.random.PRNGKey(4), 0.7, x.shape[:-1])
            if dead_tokens else None)

    def loss(v, xv, layer):
        y, m = layer(v, xv)
        return jnp.sum(y ** 2) + m["aux_loss"], y

    grad = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
    (_, y1), g1 = grad(vals, x, lambda v, xv: moe_reference(
        v, xv, cfg, moe, router, token_mask=mask))
    (_, y2), g2 = grad(vals, x, lambda v, xv: moe_apply(
        v, xv, cfg, moe, router_kind=router, sorted_block=8,
        token_mask=mask))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-5)
    if dead_tokens:
        assert not bool(mask.all())
        assert float(jnp.abs(y2[~mask]).max()) == 0.0
    flat1 = jax.tree_util.tree_leaves_with_path(g1)
    flat2 = jax.tree_util.tree_leaves(g2)
    for (path, a), b in zip(flat1, flat2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
            err_msg=jax.tree_util.keystr(path),
        )


@pytest.mark.parametrize("router", ROUTERS)
def test_sorted_pallas_impl_matches_reference(setup, router):
    """The Pallas grouped-GEMM kernel (interpret mode on CPU) through the
    full moe_apply sorted path: outputs AND gradients match the
    reference at rtol 1e-4 for every router."""
    cfg, vals, x = setup

    def loss(v, layer):
        y, m = layer(v)
        return jnp.sum(y ** 2) + m["aux_loss"], y

    (l1, y1), g1 = jax.value_and_grad(loss, has_aux=True)(
        vals, lambda v: moe_reference(v, x, cfg, cfg.moe, router)
    )
    (l2, y2), g2 = jax.value_and_grad(loss, has_aux=True)(
        vals, lambda v: moe_apply(v, x, cfg, cfg.moe, router_kind=router,
                                  sorted_block=8, implementation="pallas")
    )
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-5)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(g1),
        jax.tree_util.tree_leaves(g2),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
            err_msg=jax.tree_util.keystr(path),
        )


def test_token_major_routing_matches_slot_table():
    """Token-choice routers' token-major view (token_expert/token_weight)
    carries exactly the slot table's assignments and weights."""
    from repro.configs import MoECfg
    from repro.core import routing as R

    moe = MoECfg(num_experts=4, router="top_k", top_k=2,
                 capacity_factor=0.75)
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 4))
    r = R.route(logits, moe, "top_k")
    G, E, cap = r.token_idx.shape
    g = 16
    # Rebuild a dense (token, expert) weight table from each view.
    slot = np.zeros((G, g, E))
    tokmaj = np.zeros((G, g, E))
    for gi in range(G):
        for e in range(E):
            for c in range(cap):
                t = int(r.token_idx[gi, e, c])
                if t < g:
                    slot[gi, t, e] += float(r.combine[gi, e, c])
        for t in range(g):
            for a in range(r.token_expert.shape[-1]):
                e = int(r.token_expert[gi, t, a])
                if e < E:
                    tokmaj[gi, t, e] += float(r.token_weight[gi, t, a])
    np.testing.assert_allclose(slot, tokmaj, atol=1e-6)


# ---------------------------------------------------------------------------
# scatter-free token-choice sorted path (gathers through the sort's
# bijection); Expert Choice keeps the scatter-built tables
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("drop", [False, True])
def test_ragged_sources_invert_the_destinations(seed, drop):
    """``ragged_sources`` gives every ragged row the assignment that
    ``ragged_destinations`` packs into it and its weight (pad and tail
    rows: N and 0), every assignment its row (invalid ones: the trash
    row M), and the same counts and row count."""
    from repro.kernels.grouped_mlp import (
        ragged_destinations,
        ragged_sources,
    )

    G, N, E, block = 2, 40, 5, 4
    key = np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (G, N),
                                        0, E + int(drop)), np.int32)
    perm, _, counts, dest, M = ragged_destinations(jnp.asarray(key), E,
                                                   block)
    weight = jax.random.uniform(jax.random.PRNGKey(seed + 7), (G, N))
    got_counts, slot, wr, dest_of, got_M = ragged_sources(
        jnp.asarray(key), weight, E, block)
    assert got_M == M
    np.testing.assert_array_equal(np.asarray(got_counts), np.asarray(counts))
    want_slot = np.full((G, M + 1), N)
    want_dest = np.zeros((G, N), np.int64)
    for gi in range(G):
        for s in range(N):
            want_slot[gi, dest[gi, s]] = perm[gi, s]
            want_dest[gi, perm[gi, s]] = dest[gi, s]
    np.testing.assert_array_equal(np.asarray(slot), want_slot[:, :M])
    want_wr = np.where(want_slot[:, :M] < N, np.take_along_axis(
        np.asarray(weight), np.minimum(want_slot[:, :M], N - 1), 1), 0)
    np.testing.assert_array_equal(np.asarray(wr), want_wr)
    np.testing.assert_array_equal(np.asarray(dest_of), want_dest)
    assert int((np.asarray(dest_of) == M).sum()) == int((key == E).sum())


@pytest.mark.parametrize("router", ROUTERS)
def test_sorted_token_choice_path_has_no_scatter(setup, router):
    """Token-choice sorted ``moe_apply`` lowers, forward and ``jax.grad``,
    without a single ``scatter``: every row move and every transpose is
    a gather. Expert Choice has no fixed slots per token and keeps the
    scatter-built tables under ``moe.dispatch``."""
    cfg, vals, x = setup

    def fwd(v, xv):
        return moe_apply(v, xv, cfg, cfg.moe, router_kind=router,
                         sorted_block=8)[0]

    def loss(v, xv):
        y, m = moe_apply(v, xv, cfg, cfg.moe, router_kind=router,
                         sorted_block=8)
        return jnp.sum(y ** 2) + m["aux_loss"]

    lowered = [jax.jit(f).lower(vals, x)
               for f in (fwd, jax.grad(loss, argnums=(0, 1)))]
    compiled = [lo.compile().as_text() for lo in lowered]
    if router == "expert_choice":
        assert re.search(r'scatter\(.*op_name="[^"]*moe\.dispatch',
                         compiled[0])
    else:
        assert [lo.as_text().count("stablehlo.scatter")
                for lo in lowered] == [0, 0]
        assert ["scatter(" in text for text in compiled] == [False, False]


# ---------------------------------------------------------------------------
# ApplyCfg: one dispatch, its block resolved with the implementation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw, impl, block", [
    pytest.param({"moe_impl": "pallas", "attn_impl": "xla"}, "pallas", 128,
                 id="pallas"),
    pytest.param({}, "xla", 8, id="xla"),  # "auto" on the CPU
    pytest.param({"moe_impl": "xla", "attn_impl": "xla",
                  "sorted_block": 128}, "xla", 128, id="explicit"),
])
def test_applycfg_sorted_block_follows_implementation(kw, impl, block):
    """``resolve()`` gives an unset ``sorted_block`` the Pallas kernel's
    128-row block or the XLA path's 8, also when both implementations
    are already pinned; an explicit block wins. Resolving again changes
    nothing."""
    from repro.models.model_zoo import ApplyCfg

    ac = ApplyCfg(**kw).resolve()
    assert (ac.moe_impl, ac.sorted_block) == (impl, block)
    assert ac.resolve() == ac


def test_applycfg_accepts_only_sorted_dispatch():
    from repro.models.model_zoo import ApplyCfg

    assert ApplyCfg(dispatch="sorted").dispatch == "sorted"
    for name in ("gather", "einsum"):
        with pytest.raises(ValueError, match="'sorted'"):
            ApplyCfg(dispatch=name)
