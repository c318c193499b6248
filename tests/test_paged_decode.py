"""Paged flash-decode parity: Pallas block-table-walk kernel (interpret
mode) vs the dense XLA masked-softmax oracle, across GQA ratios, ragged
kv lengths, block-boundary lengths and cache dtypes."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.models.attention import (
    _decode_attention,
    paged_decode_write,
    paged_prefill_write,
)

BS = 8  # KV block size under test


def _case(B, H, Kh, dh, nb, *, seed=0, dtype=jnp.float32):
    """Random head-major pool (P, Kh, BS, dh) + per-slot block tables
    over distinct shuffled blocks (block 0 left as trash)."""
    rng = np.random.default_rng(seed)
    P = 1 + B * nb
    q = jnp.asarray(rng.normal(size=(B, 1, H, dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, Kh, BS, dh)), dtype)
    vp = jnp.asarray(rng.normal(size=(P, Kh, BS, dh)), dtype)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, P)).reshape(B, nb), jnp.int32
    )
    return q, kp, vp, bt


def _dense(pool, bt):
    """(B, nb*BS, Kh, dh) sequence view of each slot's pool blocks."""
    B, nb = bt.shape
    blocks = pool[bt].transpose(0, 1, 3, 2, 4)  # (B, nb, BS, Kh, dh)
    return blocks.reshape(B, nb * BS, *blocks.shape[3:])


@pytest.mark.parametrize("H,Kh", [(4, 4), (4, 2), (8, 2), (8, 1)])
def test_kernel_matches_oracle_gqa(H, Kh):
    q, kp, vp, bt = _case(3, H, Kh, 16, 4, seed=H * 10 + Kh)
    ln = jnp.asarray([3, 17, 32], jnp.int32)
    y_x = ops.decode_attention(q, kp, vp, bt, ln, implementation="xla")
    y_p = ops.decode_attention(q, kp, vp, bt, ln, implementation="pallas")
    np.testing.assert_allclose(
        np.asarray(y_p), np.asarray(y_x), atol=1e-5, rtol=1e-5
    )


@pytest.mark.parametrize(
    "lengths", [[1, 1, 1], [BS - 1, BS, BS + 1], [2 * BS, 3 * BS, 1],
                [4 * BS - 1, 4 * BS, 2]],
)
def test_block_boundary_lengths(lengths):
    """Lengths straddling block boundaries: exactly-full blocks, one
    token into a fresh block, one short of the boundary."""
    q, kp, vp, bt = _case(3, 4, 2, 16, 4, seed=sum(lengths))
    ln = jnp.asarray(lengths, jnp.int32)
    y_x = ops.decode_attention(q, kp, vp, bt, ln, implementation="xla")
    y_p = ops.decode_attention(q, kp, vp, bt, ln, implementation="pallas")
    np.testing.assert_allclose(
        np.asarray(y_p), np.asarray(y_x), atol=1e-5, rtol=1e-5
    )


def test_oracle_matches_dense_decode_attention():
    """The paged XLA path on contiguously laid-out blocks equals the
    dense-cache ``_decode_attention`` directly — anchoring the paged
    oracle to the pre-paging decode math."""
    B, H, Kh, dh, nb = 2, 4, 2, 16, 3
    q, kp, vp, bt_shuffled = _case(B, H, Kh, dh, nb)
    # contiguous tables: slot b owns blocks [1+b*nb, 1+(b+1)*nb)
    bt = jnp.asarray(
        1 + np.arange(B * nb).reshape(B, nb), jnp.int32
    )
    ln = jnp.asarray([5, 2 * BS], jnp.int32)
    k_dense = _dense(kp, bt)
    v_dense = _dense(vp, bt)
    y_dense = _decode_attention(q, k_dense, v_dense, ln)
    y_paged = ops.decode_attention(q, kp, vp, bt, ln,
                                   implementation="xla")
    np.testing.assert_allclose(
        np.asarray(y_paged), np.asarray(y_dense), atol=1e-6, rtol=1e-6
    )


def test_scattered_table_equals_contiguous():
    """The block-table walk itself: the same logical sequence through a
    shuffled table must equal the contiguous layout."""
    B, H, Kh, dh, nb = 2, 4, 2, 16, 3
    rng = np.random.default_rng(3)
    P = 1 + B * nb
    q = jnp.asarray(rng.normal(size=(B, 1, H, dh)), jnp.float32)
    seq = jnp.asarray(
        rng.normal(size=(B, nb * BS, Kh, dh)), jnp.float32
    )
    ln = jnp.asarray([nb * BS - 3, BS + 2], jnp.int32)

    def build(order):
        bt = jnp.asarray(order, jnp.int32)
        kp = jnp.zeros((P, Kh, BS, dh), jnp.float32)
        kp = kp.at[bt].set(
            seq.reshape(B, nb, BS, Kh, dh).transpose(0, 1, 3, 2, 4)
        )
        return bt, kp

    bt_a, kp_a = build(1 + np.arange(B * nb).reshape(B, nb))
    bt_b, kp_b = build(
        rng.permutation(np.arange(1, P)).reshape(B, nb)
    )
    y_a = ops.decode_attention(q, kp_a, kp_a, bt_a, ln,
                               implementation="pallas")
    y_b = ops.decode_attention(q, kp_b, kp_b, bt_b, ln,
                               implementation="pallas")
    np.testing.assert_allclose(
        np.asarray(y_a), np.asarray(y_b), atol=1e-6, rtol=1e-6
    )


def test_dead_slot_exact_zero_both_paths():
    q, kp, vp, bt = _case(3, 4, 2, 16, 2)
    ln = jnp.asarray([0, 5, 0], jnp.int32)
    for impl in ("xla", "pallas"):
        y = ops.decode_attention(q, kp, vp, bt, ln, implementation=impl)
        assert bool(jnp.isfinite(y).all()), impl
        assert float(jnp.abs(y[0]).max()) == 0.0, impl
        assert float(jnp.abs(y[2]).max()) == 0.0, impl


def test_bf16_pool_parity():
    """bf16 cache reads: pallas == xla on the same bf16 pool to f32-
    accumulate tolerance, and bf16 vs f32 pools agree to cast noise."""
    q, kp, vp, bt = _case(3, 8, 2, 16, 4, seed=11)
    ln = jnp.asarray([7, 16, 25], jnp.int32)
    kb, vb = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    y_xb = ops.decode_attention(q, kb, vb, bt, ln, implementation="xla")
    y_pb = ops.decode_attention(q, kb, vb, bt, ln,
                                implementation="pallas")
    np.testing.assert_allclose(
        np.asarray(y_pb, np.float32), np.asarray(y_xb, np.float32),
        atol=1e-5, rtol=1e-5,
    )
    y_f32 = ops.decode_attention(q, kp, vp, bt, ln, implementation="xla")
    np.testing.assert_allclose(
        np.asarray(y_pb, np.float32), np.asarray(y_f32),
        atol=3e-2, rtol=3e-2,
    )


def test_compiled_alignment_guard():
    """The head-major pool makes every block a whole (bs, dh) slab, so
    Mosaic takes any head_dim and block size (tests/test_tpu_compile.py
    compiles dh=64); the one up-front shape check left is the GQA
    grouping, which raises before any lowering, compiled or not."""
    from repro.kernels.decode_attention import (
        paged_decode_attention_pallas,
    )

    q, kp, vp, bt = _case(1, 4, 3, 16, 2)
    ln = jnp.asarray([4], jnp.int32)
    with pytest.raises(ValueError, match="multiple of Kh"):
        paged_decode_attention_pallas(
            q[:, 0], kp, vp, bt, ln, interpret=False
        )


# ---------------------------------------------------------------------------
# cache write helpers
# ---------------------------------------------------------------------------


def test_prefill_write_then_decode_write_roundtrip():
    """A bucketed prompt write plus successive decode writes reproduce
    the dense sequence layout block-for-block."""
    Kh, dh, nb = 2, 4, 3
    rng = np.random.default_rng(5)
    pool = jnp.zeros((1 + nb, Kh, BS, dh), jnp.float32)
    bt = jnp.asarray([[2, 3, 1]], jnp.int32)
    plen = BS + 3
    sp = 2 * BS  # bucketed
    prompt_kv = jnp.asarray(rng.normal(size=(1, sp, Kh, dh)), jnp.float32)
    pool = paged_prefill_write(pool, prompt_kv, bt)
    # decode two more tokens at positions plen, plen+1
    toks = jnp.asarray(rng.normal(size=(2, 1, Kh, dh)), jnp.float32)
    for t in range(2):
        pool = paged_decode_write(
            pool, toks[t:t + 1], bt, jnp.asarray([plen + t], jnp.int32)
        )
    dense = _dense(pool, bt)
    np.testing.assert_allclose(
        np.asarray(dense[0, :plen]), np.asarray(prompt_kv[0, :plen])
    )
    np.testing.assert_allclose(
        np.asarray(dense[0, plen:plen + 2]), np.asarray(toks[:, 0])
    )


def test_attention_apply_free_slot_attends_nothing():
    """Through attention_apply (the engine's decode path), a free slot
    (length 0) must produce EXACT zeros — its trash-block write is never
    read back — for both decode implementations."""
    from repro.configs import get_reduced
    from repro.models.attention import attention_apply, attention_init
    from repro.models.attention import init_paged_cache

    cfg = get_reduced("granite-moe-1b-a400m")
    p = attention_init(jax.random.PRNGKey(0), cfg)
    p = jax.tree.map(lambda x: x.value, p,
                     is_leaf=lambda x: hasattr(x, "value"))
    cache = init_paged_cache(cfg, 4, BS, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 1, cfg.d_model))
    bt = jnp.asarray([[1, 2], [0, 0]], jnp.int32)  # slot 1 free
    lens = jnp.asarray([3, 0], jnp.int32)
    for impl in ("xla", "pallas"):
        y, new_cache = attention_apply(
            p, x, cfg, cache=cache, cache_index=lens,
            block_tables=bt, implementation=impl,
        )
        assert float(jnp.abs(y[1]).max()) == 0.0, impl
        assert bool(jnp.isfinite(y).all()), impl


def test_prefill_write_rejects_unbucketed_length():
    pool = jnp.zeros((3, 2, BS, 4), jnp.float32)
    kv = jnp.zeros((1, BS + 1, 2, 4), jnp.float32)
    with pytest.raises(ValueError, match="multiple of the block size"):
        paged_prefill_write(pool, kv, jnp.asarray([[1, 2]], jnp.int32))


def test_decode_write_dead_slot_hits_trash_block():
    pool = jnp.zeros((3, 2, BS, 4), jnp.float32)
    kv = jnp.ones((2, 1, 2, 4), jnp.float32)
    bt = jnp.asarray([[0, 0], [1, 2]], jnp.int32)  # slot 0 dead
    out = paged_decode_write(
        pool, kv, bt, jnp.asarray([0, 3], jnp.int32)
    )
    assert float(jnp.abs(out[0, :, 0]).max()) == 1.0  # trash written
    assert float(jnp.abs(out[1, :, 3]).min()) == 1.0  # every kv head
    assert float(jnp.abs(out[1, :, :3]).max()) == 0.0


# ---------------------------------------------------------------------------
# model-level parity (paged prefill/decode vs the training forward)
# ---------------------------------------------------------------------------


def _dropless(cfg):
    if cfg.moe is None:
        return cfg
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.num_experts)
        )
    )


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "granite-moe-1b-a400m"])
def test_paged_prefill_decode_match_train_forward(arch):
    from repro.configs import get_reduced
    from repro.models import model_zoo as zoo
    from repro.models import param as pm

    cfg = _dropless(get_reduced(arch))
    p = zoo.init_params(jax.random.PRNGKey(0), cfg)
    vals, _ = pm.split(p)
    S = 13
    toks = jax.random.randint(
        jax.random.PRNGKey(1), (1, S + 1), 0, cfg.vocab_size
    )
    logits_full, _ = zoo.forward_train(
        vals, {"tokens": toks, "targets": toks}, cfg
    )
    nb = 4
    cache = zoo.init_paged_serve_cache(cfg, 1 + nb, BS, dtype=jnp.float32)
    bt = jnp.asarray([[3, 1, 4, 2]], jnp.int32)
    sp = -(-S // BS) * BS
    tp = np.zeros((1, sp), np.int32)
    tp[0, :S] = np.asarray(toks[0, :S])
    ac = zoo.ApplyCfg()
    cache, lg = zoo.paged_prefill(
        vals, jnp.asarray(tp), cache, bt, jnp.asarray(S, jnp.int32),
        cfg, ac=ac,
    )
    np.testing.assert_allclose(
        np.asarray(lg[0, 0]), np.asarray(logits_full[0, S - 1]),
        atol=3e-3, rtol=3e-3,
    )
    cache, lg2 = zoo.paged_decode_step(
        vals, toks[:, S:S + 1], cache, bt,
        jnp.asarray([S], jnp.int32), cfg, ac=ac,
    )
    np.testing.assert_allclose(
        np.asarray(lg2[0, 0]), np.asarray(logits_full[0, S]),
        atol=3e-3, rtol=3e-3,
    )


def test_paged_decode_step_pallas_matches_xla():
    from repro.configs import get_reduced
    from repro.models import model_zoo as zoo
    from repro.models import param as pm

    cfg = _dropless(get_reduced("granite-moe-1b-a400m"))
    p = zoo.init_params(jax.random.PRNGKey(0), cfg)
    vals, _ = pm.split(p)
    S, nb = 9, 3
    cache = zoo.init_paged_serve_cache(cfg, 1 + nb, BS, dtype=jnp.float32)
    bt = jnp.asarray([[2, 3, 1]], jnp.int32)
    sp = -(-S // BS) * BS
    toks = np.zeros((1, sp), np.int32)
    toks[0, :S] = np.asarray(
        jax.random.randint(jax.random.PRNGKey(2), (S,), 0, cfg.vocab_size)
    )
    outs = {}
    for impl in ("xla", "pallas"):
        ac = zoo.ApplyCfg(attn_impl=impl, moe_impl="xla")
        c, _ = zoo.paged_prefill(
            vals, jnp.asarray(toks), cache, bt,
            jnp.asarray(S, jnp.int32), cfg, ac=ac,
        )
        _, lg = zoo.paged_decode_step(
            vals, jnp.asarray([[7]], jnp.int32), c, bt,
            jnp.asarray([S], jnp.int32), cfg, ac=ac,
        )
        outs[impl] = np.asarray(lg)
    np.testing.assert_allclose(
        outs["pallas"], outs["xla"], atol=1e-4, rtol=1e-4
    )
    assert int(outs["pallas"][0, 0].argmax()) == int(
        outs["xla"][0, 0].argmax()
    )
