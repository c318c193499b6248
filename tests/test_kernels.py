"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp oracle,
across shapes and dtypes — forward AND ``jax.grad`` (the custom-VJP
backward kernels)."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.expert_mlp import expert_ffn_pallas, expert_ffn_pallas_vjp
from repro.kernels.flash_attention import (
    flash_attention_pallas,
    flash_attention_pallas_vjp,
)
from repro.kernels.rwkv6_kernel import rwkv6_pallas

KEY = jax.random.PRNGKey(42)


# ---------------------------------------------------------------------------
# expert_mlp
# ---------------------------------------------------------------------------

EXPERT_CASES = [
    # E, cap, d, f, gated, act, dtype
    (4, 64, 32, 48, True, "silu", jnp.float32),
    (2, 17, 24, 40, False, "gelu", jnp.float32),
    (8, 128, 64, 96, True, "silu", jnp.bfloat16),
    (1, 8, 16, 16, False, "sqrelu", jnp.float32),
    (3, 33, 20, 28, True, "gelu", jnp.float32),
]


@pytest.mark.parametrize("case", EXPERT_CASES)
def test_expert_ffn_pallas_vs_ref(case):
    E, cap, d, f, gated, act, dtype = case
    ks = jax.random.split(KEY, 4)
    xe = jax.random.normal(ks[0], (E, cap, d)).astype(dtype)
    wi = (jax.random.normal(ks[1], (E, d, f)) * 0.1).astype(dtype)
    wg = (
        (jax.random.normal(ks[2], (E, d, f)) * 0.1).astype(dtype)
        if gated else None
    )
    wo = (jax.random.normal(ks[3], (E, f, d)) * 0.1).astype(dtype)
    got = expert_ffn_pallas(
        xe, wi, wg, wo, act=act, bc=16, bf=16, bd=16, interpret=True
    )
    want = ref.expert_ffn_ref(xe, wi, wg, wo, act=act)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )


def test_expert_ffn_ops_dispatch():
    E, cap, d, f = 2, 16, 8, 12
    ks = jax.random.split(KEY, 3)
    xe = jax.random.normal(ks[0], (4, E, cap, d))  # grouped (G, E, cap, d)
    wi = jax.random.normal(ks[1], (E, d, f)) * 0.1
    wo = jax.random.normal(ks[2], (E, f, d)) * 0.1
    for impl in ("xla", "pallas", "ref"):
        y = ops.expert_ffn(xe, wi, None, wo, act="gelu",
                           implementation=impl)
        assert y.shape == xe.shape
    y_x = ops.expert_ffn(xe, wi, None, wo, act="gelu", implementation="xla")
    y_p = ops.expert_ffn(xe, wi, None, wo, act="gelu",
                         implementation="pallas")
    np.testing.assert_allclose(np.asarray(y_x), np.asarray(y_p), atol=2e-5)


EXPERT_GRAD_CASES = [
    # E, cap, d, f, gated, act — includes padded cap/d (not tile multiples)
    (2, 16, 16, 24, True, "silu"),
    (2, 17, 12, 20, False, "gelu"),
    (3, 33, 20, 28, True, "gelu"),
    (1, 8, 16, 16, False, "sqrelu"),
]


@pytest.mark.parametrize("case", EXPERT_GRAD_CASES)
def test_expert_ffn_pallas_grad_vs_ref(case):
    """jax.grad through the custom-VJP Pallas path (fused backward
    kernels, interpret mode) matches the oracle's autodiff for every
    input: dx, dwi, dwg, dwo."""
    E, cap, d, f, gated, act = case
    ks = jax.random.split(KEY, 5)
    xe = jax.random.normal(ks[0], (E, cap, d))
    wi = jax.random.normal(ks[1], (E, d, f)) * 0.1
    wg = jax.random.normal(ks[2], (E, d, f)) * 0.1 if gated else None
    wo = jax.random.normal(ks[3], (E, f, d)) * 0.1
    cot = jax.random.normal(ks[4], (E, cap, d))  # non-trivial cotangent

    def loss_pallas(xe, wi, wg, wo):
        y = expert_ffn_pallas_vjp(
            xe, wi, wg, wo, act=act, bc=8, bf=8, bd=8, interpret=True
        )
        return jnp.sum(y * cot)

    def loss_ref(xe, wi, wg, wo):
        return jnp.sum(ref.expert_ffn_ref(xe, wi, wg, wo, act=act) * cot)

    argnums = (0, 1, 2, 3) if gated else (0, 1, 3)
    got = jax.jit(jax.grad(loss_pallas, argnums))(xe, wi, wg, wo)
    want = jax.grad(loss_ref, argnums)(xe, wi, wg, wo)
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-5
        )


def test_expert_ffn_mxu_alignment_error():
    """Compiled (non-interpret) kernels reject non-128-multiple tiles."""
    xe = jnp.zeros((1, 256, 256))
    wi = jnp.zeros((1, 256, 256))
    wo = jnp.zeros((1, 256, 256))
    with pytest.raises(ValueError, match="multiples of 128"):
        expert_ffn_pallas(xe, wi, None, wo, bc=100, interpret=False)


def test_tile_clamp_policy():
    """Compiled tiles round small dims UP to one 128-aligned tile (the
    kernels zero-pad); interpret tiles shrink to the dim exactly."""
    from repro.kernels.tiling import clamp_tile

    assert clamp_tile(128, 32, interpret=True) == 32
    assert clamp_tile(128, 32, interpret=False) == 128   # pad 32 -> 128
    assert clamp_tile(512, 200, interpret=False) == 256  # pad 200 -> 256
    assert clamp_tile(256, 4096, interpret=False) == 256


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # B, Sq, Skv, H, Kh, dh, causal, q_offset, kv_len, dtype
    (2, 64, 64, 4, 2, 16, True, 0, None, jnp.float32),
    (1, 37, 37, 8, 8, 32, True, 0, None, jnp.float32),
    (2, 1, 64, 4, 2, 16, True, 40, 41, jnp.float32),
    (2, 32, 48, 4, 4, 8, False, 0, None, jnp.float32),
    (1, 64, 64, 4, 1, 16, True, 0, None, jnp.bfloat16),
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_pallas_vs_ref(case):
    B, Sq, Skv, H, Kh, dh, causal, qo, kl, dtype = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, dh)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Skv, Kh, dh)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Skv, Kh, dh)).astype(dtype)
    got = flash_attention_pallas(
        q, k, v, causal=causal, q_offset=qo, kv_len=kl,
        bq=16, bk=16, interpret=True,
    )
    want = ref.flash_attention_ref(
        q, k, v, causal=causal, q_offset=qo,
        kv_len=None if kl is None else jnp.asarray(kl),
    )
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )


def test_flash_xla_path_matches_ref():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 40, 8, 16))
    k = jax.random.normal(ks[1], (2, 40, 2, 16))
    v = jax.random.normal(ks[2], (2, 40, 2, 16))
    got = ops.flash_attention(q, k, v, causal=True, q_chunk=8, kv_chunk=16)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


FLASH_GRAD_CASES = [
    # B, Sq, Skv, H, Kh, dh, causal, q_offset, kv_len
    (2, 16, 16, 4, 2, 8, True, 0, None),      # causal + GQA
    (1, 13, 13, 4, 4, 8, True, 0, None),      # odd seq -> tile padding
    (2, 8, 32, 4, 2, 8, True, 24, 30),        # q_offset + masked cache
    (2, 16, 24, 4, 4, 8, False, 0, None),     # non-causal
]


@pytest.mark.parametrize("case", FLASH_GRAD_CASES)
def test_flash_pallas_grad_vs_ref(case):
    """jax.grad through the custom-VJP flash kernels (dq + fused dk/dv,
    interpret mode) matches the O(S^2) oracle's autodiff."""
    B, Sq, Skv, H, Kh, dh, causal, qo, kl = case
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, Sq, H, dh))
    k = jax.random.normal(ks[1], (B, Skv, Kh, dh))
    v = jax.random.normal(ks[2], (B, Skv, Kh, dh))
    cot = jax.random.normal(ks[3], (B, Sq, H, dh))

    def loss_pallas(q, k, v):
        y = flash_attention_pallas_vjp(
            q, k, v, causal=causal, q_offset=qo, kv_len=kl,
            bq=8, bk=8, interpret=True,
        )
        return jnp.sum(y * cot)

    def loss_ref(q, k, v):
        y = ref.flash_attention_ref(
            q, k, v, causal=causal, q_offset=qo,
            kv_len=None if kl is None else jnp.asarray(kl),
        )
        return jnp.sum(y * cot)

    got = jax.jit(jax.grad(loss_pallas, (0, 1, 2)))(q, k, v)
    want = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for name, g, w in zip("qkv", got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-5,
            err_msg=f"d{name}",
        )


def test_flash_pallas_residuals_lse():
    """return_residuals exposes the row logsumexp the backward consumes."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 16, 2, 8))
    k = jax.random.normal(ks[1], (1, 16, 2, 8))
    v = jax.random.normal(ks[2], (1, 16, 2, 8))
    out, lse = flash_attention_pallas(
        q, k, v, causal=True, bq=8, bk=8, interpret=True,
        return_residuals=True,
    )
    s = jnp.einsum("bqhd,bthd->bhqt", q, k) * 8 ** -0.5
    mask = jnp.tril(jnp.ones((16, 16), bool))
    s = jnp.where(mask[None, None], s, -jnp.inf)
    want = jax.nn.logsumexp(s, axis=-1)  # (B, H, Sq)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(want), rtol=1e-5, atol=1e-5
    )


# ---------------------------------------------------------------------------
# grad through moe_apply (ops dispatch -> vmap'd custom-VJP kernels)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dispatch", ["gather", "einsum"])
def test_moe_apply_grad_pallas_matches_xla(dispatch):
    from repro.configs import get_reduced
    from repro.core.moe import moe_apply, moe_init
    from repro.models import param as pm

    cfg = get_reduced("grok-1-314b")
    p = moe_init(jax.random.PRNGKey(0), cfg, cfg.moe)
    vals, _ = pm.split(p)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))

    def loss(v, impl):
        y, m = moe_apply(v, x, cfg, cfg.moe, dispatch=dispatch,
                         implementation=impl)
        return jnp.sum(y ** 2) + m["aux_loss"]

    g_xla = jax.grad(lambda v: loss(v, "xla"))(vals)
    g_pallas = jax.grad(lambda v: loss(v, "pallas"))(vals)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5
        ),
        g_xla, g_pallas,
    )
    assert all(
        bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(g_pallas)
    )


# ---------------------------------------------------------------------------
# rwkv6
# ---------------------------------------------------------------------------

RWKV_CASES = [
    # B, T, H, K, V, chunk, with_state, dtype
    (2, 32, 2, 8, 8, 8, False, jnp.float32),
    (1, 37, 4, 16, 16, 16, True, jnp.float32),
    (2, 64, 2, 8, 12, 32, False, jnp.float32),
    (1, 16, 2, 8, 8, 4, True, jnp.bfloat16),
]


@pytest.mark.parametrize("case", RWKV_CASES)
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_rwkv6_vs_ref(case, impl):
    B, T, H, K, V, chunk, with_state, dtype = case
    ks = jax.random.split(KEY, 6)
    r = (jax.random.normal(ks[0], (B, T, H, K)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (B, T, H, K)) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (B, T, H, V)) * 0.5).astype(dtype)
    w = (jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, K))) * 0.6
         + 0.3).astype(jnp.float32)
    u = (jax.random.normal(ks[4], (H, K)) * 0.3).astype(jnp.float32)
    s0 = (
        jax.random.normal(ks[5], (B, H, K, V)) * 0.2 if with_state else None
    )
    want_o, want_s = ref.rwkv6_ref(r, k, v, w, u, initial_state=s0)
    if impl == "pallas":
        got_o, got_s = rwkv6_pallas(
            r, k, v, w, u, initial_state=s0, chunk=chunk, interpret=True
        )
    else:
        got_o, got_s = ops.rwkv6(
            r, k, v, w, u, initial_state=s0, chunk=chunk,
            implementation="xla",
        )
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(
        np.asarray(got_o, np.float32), np.asarray(want_o, np.float32),
        atol=tol, rtol=tol,
    )
    np.testing.assert_allclose(
        np.asarray(got_s), np.asarray(want_s), atol=tol, rtol=tol
    )


def test_rwkv6_state_chaining():
    """Processing [first half; second half with carried state] == full."""
    B, T, H, K, V = 1, 32, 2, 8, 8
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, T, H, K)) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, K)) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, V)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, K))) * 0.6 + 0.3
    u = jax.random.normal(ks[4], (H, K)) * 0.3
    o_full, s_full = ref.rwkv6_ref(r, k, v, w, u)
    h = T // 2
    o1, s1 = ops.rwkv6(r[:, :h], k[:, :h], v[:, :h], w[:, :h], u, chunk=8)
    o2, s2 = ops.rwkv6(
        r[:, h:], k[:, h:], v[:, h:], w[:, h:], u,
        initial_state=s1, chunk=8,
    )
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([o1, o2], axis=1)),
        np.asarray(o_full), atol=2e-4, rtol=1e-3,
    )
    np.testing.assert_allclose(
        np.asarray(s2), np.asarray(s_full), atol=2e-4, rtol=1e-3
    )


def test_rwkv6_auto_warns_once_and_pins_chunked_xla_fallback():
    """implementation="auto" has no custom-VJP rwkv6 kernel to route to
    (ROADMAP open item): it must take the chunked XLA path — identical
    outputs AND grads to implementation="xla" — and say so with a
    one-time warning instead of silently downgrading the perf path."""
    from repro.kernels import ops as ops_mod

    B, T, H, K = 1, 16, 2, 8
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, T, H, K)) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, K)) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, K)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, K))) * 0.6 + 0.3
    u = jax.random.normal(ks[4], (H, K)) * 0.3

    ops_mod._RWKV6_AUTO_WARNED = False  # re-arm the one-time warning
    with pytest.warns(UserWarning, match="chunked XLA"):
        got, _ = ops.rwkv6(r, k, v, w, u, chunk=8, implementation="auto")
    want, _ = ops.rwkv6(r, k, v, w, u, chunk=8, implementation="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))

    # one-time: a second call must not warn again
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        ops.rwkv6(r, k, v, w, u, chunk=8, implementation="auto")

    def loss(impl, *args):
        return jnp.sum(ops.rwkv6(*args, chunk=8, implementation=impl)[0])

    g_auto = jax.grad(lambda *a: loss("auto", *a), argnums=(0, 1, 2))(
        r, k, v, w, u
    )
    g_xla = jax.grad(lambda *a: loss("xla", *a), argnums=(0, 1, 2))(
        r, k, v, w, u
    )
    for a, b in zip(g_auto, g_xla):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# grouped_mlp (sorted ragged dispatch kernel)
# ---------------------------------------------------------------------------

from repro.kernels.grouped_mlp import (  # noqa: E402
    block_tables,
    grouped_mlp_pallas,
    grouped_mlp_pallas_vjp,
    ragged_buffer_rows,
    ragged_row_offsets,
)

GROUPED_CASES = [
    # G, E, d, f, bm, gated, act, per-(group, expert) valid row counts —
    # includes empty experts, whole empty groups, non-block-multiples.
    (2, 4, 16, 24, 8, True, "silu", [[9, 0, 3, 8], [0, 0, 0, 20]]),
    (1, 3, 20, 12, 4, False, "gelu", [[5, 1, 2]]),
    (2, 2, 8, 8, 8, True, "sqrelu", [[0, 0], [16, 16]]),
    (1, 5, 12, 16, 16, True, "gelu", [[1, 17, 0, 16, 2]]),
]


def _ragged_inputs(G, E, d, f, bm, gated, counts, key=KEY):
    """Random rows in the valid ragged slots, zeros in pad/tail rows."""
    counts = jnp.asarray(counts, jnp.int32)
    M = ragged_buffer_rows(int(counts.sum(-1).max()), E, bm)
    row_off, _ = ragged_row_offsets(counts, bm)
    ks = jax.random.split(key, 4)
    xs = np.zeros((G, M, d), np.float32)
    rnd = np.asarray(jax.random.normal(ks[0], (G, M, d)))
    for g in range(G):
        for e in range(E):
            s, c = int(row_off[g, e]), int(counts[g, e])
            xs[g, s:s + c] = rnd[g, s:s + c]
    wi = jax.random.normal(ks[1], (E, d, f)) * 0.1
    wg = jax.random.normal(ks[2], (E, d, f)) * 0.1 if gated else None
    wo = jax.random.normal(ks[3], (E, f, d)) * 0.1
    return jnp.asarray(xs), wi, wg, wo, counts


# Tile choices the grouped kernels are checked under: forced 8-wide f and
# d tiles (several windows per expert), and the tiles left to
# ``grouped_expert_tiles`` (whole-expert windows at these widths: one f
# and one d tile, so an expert's weights stay resident across its blocks).
GROUPED_TILINGS = pytest.mark.parametrize(
    "tiles", [(8, 8), (None, None)], ids=["tiled", "whole"]
)


@GROUPED_TILINGS
@pytest.mark.parametrize("case", GROUPED_CASES)
def test_grouped_mlp_pallas_vs_ref(case, tiles):
    G, E, d, f, bm, gated, act, counts = case
    bf, bd = tiles
    xs, wi, wg, wo, counts = _ragged_inputs(G, E, d, f, bm, gated, counts)
    got = grouped_mlp_pallas(
        xs, wi, wg, wo, counts, act=act, bm=bm, bf=bf, bd=bd, interpret=True
    )
    want = ref.grouped_mlp_ref(xs, wi, wg, wo, counts, block=bm, act=act)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5
    )


@GROUPED_TILINGS
@pytest.mark.parametrize("case", GROUPED_CASES)
def test_grouped_mlp_pallas_grad_vs_ref(case, tiles):
    """jax.grad through the grouped-GEMM custom VJP (scalar-prefetch dx +
    segment-walk dW kernels, interpret mode) matches the oracle's
    autodiff for every differentiable input."""
    G, E, d, f, bm, gated, act, counts = case
    bf, bd = tiles
    xs, wi, wg, wo, counts = _ragged_inputs(G, E, d, f, bm, gated, counts)
    # Cotangent is zero on dead-block rows: the kernel skips them (dx = 0
    # by contract), while the oracle's autodiff would produce
    # act'(0)-shaped gradients for those all-zero rows. The combine step
    # never reads them, so this is the only cotangent that can reach the
    # kernel from moe_apply.
    nb = xs.shape[1] // bm
    _, bl = block_tables(counts, bm, nb)
    live_rows = jnp.repeat(bl, bm, axis=1)[..., None]  # (G, M, 1)
    cot = jax.random.normal(jax.random.fold_in(KEY, 1), xs.shape)
    cot = cot * live_rows

    def loss_pallas(xs, wi, wg, wo):
        y = grouped_mlp_pallas_vjp(
            xs, wi, wg, wo, counts, act=act, bm=bm, bf=bf, bd=bd,
            interpret=True,
        )
        return jnp.sum(y * cot)

    def loss_ref(xs, wi, wg, wo):
        y = ref.grouped_mlp_ref(xs, wi, wg, wo, counts, block=bm, act=act)
        return jnp.sum(y * cot)

    argnums = (0, 1, 2, 3) if gated else (0, 1, 3)
    got = jax.jit(jax.grad(loss_pallas, argnums))(xs, wi, wg, wo)
    want = jax.grad(loss_ref, argnums)(xs, wi, wg, wo)
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-5
        )


def test_grouped_mlp_ops_dispatch():
    """xla (ragged_dot), pallas (interpret) and ref agree through the
    ops entry point."""
    case = GROUPED_CASES[0]
    G, E, d, f, bm, gated, act, counts = case
    xs, wi, wg, wo, counts = _ragged_inputs(G, E, d, f, bm, gated, counts)
    ys = {
        impl: ops.grouped_mlp(
            xs, wi, wg, wo, counts, act=act, block=bm, implementation=impl
        )
        for impl in ("xla", "pallas", "ref")
    }
    for impl in ("xla", "pallas"):
        np.testing.assert_allclose(
            np.asarray(ys[impl]), np.asarray(ys["ref"]),
            atol=1e-5, rtol=1e-5,
        )


def test_grouped_mlp_block_tables():
    """block_expert walks segments in order (tail clamps to E-1);
    block_live marks exactly the blocks holding valid rows; every expert
    owns >= 1 block (the min-one-block layout contract the dW kernel's
    segment flush relies on)."""
    counts = jnp.asarray([[9, 0, 3, 8]], jnp.int32)  # bm=8
    nb = ragged_buffer_rows(20, 4, 8) // 8  # ceil(20/8) + 4 = 7 blocks
    be, bl = block_tables(counts, 8, nb)
    # segments: e0 -> 2 blocks (9 rows), e1 -> 1 (empty), e2 -> 1, e3 -> 1,
    # tail 2 blocks clamp to e3.
    assert be[0].tolist() == [0, 0, 1, 2, 3, 3, 3]
    assert bl[0].tolist() == [1, 1, 0, 1, 1, 0, 0]


def test_grouped_mlp_prev_live_table():
    """prev_live pins each dead block to the most recent live block (0
    when none precedes it) — the compacted walk's no-fetch alias."""
    from repro.kernels.grouped_mlp import prev_live_table

    bl = jnp.asarray([[1, 1, 0, 1, 1, 0, 0], [0, 0, 1, 0, 1, 1, 0]],
                     jnp.int32)
    pt = prev_live_table(bl)
    assert pt[0].tolist() == [0, 1, 1, 3, 4, 4, 4]
    assert pt[1].tolist() == [0, 0, 2, 2, 4, 5, 5]


def test_grouped_walk_bytes_ragged_with_dead_blocks():
    """The compacted walk's modeled bytes track live blocks only; the
    static walk pays for dead blocks too. With zero dead blocks the two
    walks agree exactly."""
    from repro.kernels.tiling import grouped_walk_fwd_bytes

    live, total, bm, d, f = 31, 72, 128, 2048, 5632
    compact = grouped_walk_fwd_bytes(live, total, bm, d, f, 3,
                                     compacted=True)
    static = grouped_walk_fwd_bytes(live, total, bm, d, f, 3,
                                    compacted=False)
    assert compact < static
    # saved = dead blocks' weight + x streaming
    dead = total - live
    assert static - compact == dead * (3 * d * f + bm * d) * 2
    assert grouped_walk_fwd_bytes(total, total, bm, d, f, 3,
                                  compacted=True) == static


def test_grouped_mlp_rows_independent_of_capacity_factor():
    """The ragged buffer's static row count depends on the assignment
    count (g*k), NOT on capacity factor — the padded buffer's E*cap rows
    scale linearly with it."""
    g, E, k, bm = 4096, 8, 2, 128
    M = ragged_buffer_rows(g * k, E, bm)
    from repro.core.routing import capacity
    from repro.configs import MoECfg

    for cf in (1.0, 1.25, 2.0):
        moe = MoECfg(num_experts=E, capacity_factor=cf, top_k=k)
        assert ragged_buffer_rows(g * k, E, bm) == M
        assert capacity(g, moe) * E == int(cf * g)  # padded rows grow


# ---------------------------------------------------------------------------
# tile auto-tuning (VMEM budget model)
# ---------------------------------------------------------------------------


def test_tune_expert_tiles_vmem_budget():
    """Defaults hold for small d_model; the dW accumulator term drives
    bf down to 128 from d_model >= 4096 (the kernels/README case)."""
    from repro.kernels.tiling import (
        VMEM_BUDGET_BYTES,
        expert_tile_vmem_bytes,
        tune_expert_tiles,
    )

    assert tune_expert_tiles(4096, 2048, 512) == (128, 256, 512)
    assert tune_expert_tiles(4096, 5632, 2048) == (128, 256, 512)
    bc, bf, bd = tune_expert_tiles(4096, 16384, 4096)
    assert bf == 128
    assert expert_tile_vmem_bytes(bc, bf, bd, 4096) <= VMEM_BUDGET_BYTES
    # tuned tiles stay MXU-aligned
    assert bc % 128 == bf % 128 == bd % 128 == 0


@pytest.mark.parametrize(
    "f, d, itemsize, whole",
    [
        (512, 1536, 4, True),  # granite-3b experts, float32
        (512, 1024, 4, True),  # granite-1b experts, float32
        (14336, 4096, 4, False),  # too wide to hold one expert
    ],
    ids=["granite3b-f32", "granite1b-f32", "d4096-f14336-f32"],
)
def test_grouped_expert_tiles_by_shape(f, d, itemsize, whole):
    """Whole-expert windows (one f and one d tile) where the double-
    buffered forward and dx sets fit the scoped VMEM limit; otherwise
    exactly the tuned tiles of the padded kernels, in all three."""
    from repro.kernels.tiling import (
        VMEM_LIMIT_BYTES,
        grouped_expert_tiles,
        grouped_vmem_bytes,
        tune_expert_tiles,
    )

    bf, bd, bf_dw = grouped_expert_tiles(f, d, itemsize)
    if whole:
        assert (bf, bd) == (-(-f // 128) * 128, -(-d // 128) * 128)
        fwd, dx, _ = grouped_vmem_bytes(128, bf, bd, d, itemsize)
        dw = grouped_vmem_bytes(128, bf_dw, bd, d, itemsize)[2]
        assert max(fwd, dx, dw) < VMEM_LIMIT_BYTES
        assert bf % bf_dw == 0 and bf_dw % 128 == 0
    else:
        _, tbf, tbd = tune_expert_tiles(0, f, d)
        assert (bf, bd, bf_dw) == (tbf, tbd, tbf)


def test_tune_attention_tiles_vmem_budget():
    from repro.kernels.tiling import (
        VMEM_BUDGET_BYTES,
        attention_tile_vmem_bytes,
        tune_attention_tiles,
    )

    assert tune_attention_tiles(4096, 4096, 128) == (512, 512)
    bq, bk = tune_attention_tiles(4096, 4096, 2048)  # absurd dh: must fit
    assert attention_tile_vmem_bytes(bq, bk, 2048) <= VMEM_BUDGET_BYTES
    assert bq % 128 == bk % 128 == 0
