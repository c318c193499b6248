"""Paged prefill-attention parity: Pallas q-tile x kv-block kernel
(interpret mode) vs the XLA gather + masked-softmax oracle, across GQA
ratios, chunks crossing block boundaries, scattered tables, bf16 pools
and dead lanes — plus the mixed-step row-write helper and model-level
mixed-step parity against the training forward."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.paged_prefill import pick_q_tile
from repro.models.attention import (
    paged_decode_write,
    paged_prefill_write,
    paged_row_write,
    reference_attention,
)

BS = 8  # KV block size under test


def _case(NC, C, H, Kh, dh, nb, *, seed=0, dtype=jnp.float32):
    """Random head-major pool (P, Kh, BS, dh) + per-chunk block tables
    over distinct shuffled blocks (block 0 left as trash)."""
    rng = np.random.default_rng(seed)
    P = 1 + NC * nb
    q = jnp.asarray(rng.normal(size=(NC, C, H, dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, Kh, BS, dh)), dtype)
    vp = jnp.asarray(rng.normal(size=(P, Kh, BS, dh)), dtype)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, P)).reshape(NC, nb), jnp.int32
    )
    return q, kp, vp, bt


def _blocks(seq, nb):
    """(nb*BS, Kh, dh) sequence -> (nb, Kh, BS, dh) head-major blocks."""
    return seq.reshape(nb, BS, *seq.shape[1:]).transpose(0, 2, 1, 3)


def _both(q, kp, vp, bt, starts, lens):
    st = jnp.asarray(starts, jnp.int32)
    ln = jnp.asarray(lens, jnp.int32)
    y_x = ops.prefill_attention(q, kp, vp, bt, st, ln,
                                implementation="xla")
    y_p = ops.prefill_attention(q, kp, vp, bt, st, ln,
                                implementation="pallas")
    return y_x, y_p


@pytest.mark.parametrize("H,Kh", [(4, 4), (4, 2), (8, 2), (8, 1)])
def test_kernel_matches_oracle_gqa(H, Kh):
    q, kp, vp, bt = _case(3, 8, H, Kh, 16, 4, seed=H * 10 + Kh)
    y_x, y_p = _both(q, kp, vp, bt, [0, 5, 17], [8, 8, 8])
    np.testing.assert_allclose(
        np.asarray(y_p), np.asarray(y_x), atol=1e-5, rtol=1e-5
    )


@pytest.mark.parametrize(
    "start,ln",
    [(0, 1), (BS - 1, 8), (BS, 8), (2 * BS - 3, 8), (2 * BS, 5), (3, 6)],
)
def test_chunk_crossing_block_boundaries(start, ln):
    """Chunks starting mid-block, at a boundary, one short of it — the
    absolute-position causal mask and the table walk must agree with the
    gather oracle in every case."""
    q, kp, vp, bt = _case(1, 8, 4, 2, 16, 4, seed=start * 10 + ln)
    y_x, y_p = _both(q, kp, vp, bt, [start], [ln])
    np.testing.assert_allclose(
        np.asarray(y_p), np.asarray(y_x), atol=1e-5, rtol=1e-5
    )


def test_scattered_table_equals_contiguous():
    """The same logical sequence through a shuffled table must equal the
    contiguous layout."""
    NC, C, H, Kh, dh, nb = 1, 8, 4, 2, 16, 3
    rng = np.random.default_rng(3)
    P = 1 + nb
    q = jnp.asarray(rng.normal(size=(NC, C, H, dh)), jnp.float32)
    seq = jnp.asarray(rng.normal(size=(nb * BS, Kh, dh)), jnp.float32)

    def build(order):
        bt = jnp.asarray([order], jnp.int32)
        kp = jnp.zeros((P, Kh, BS, dh), jnp.float32)
        kp = kp.at[bt[0]].set(_blocks(seq, nb))
        return bt, kp

    bt_a, kp_a = build([1, 2, 3])
    bt_b, kp_b = build([3, 1, 2])
    ya = ops.prefill_attention(q, kp_a, kp_a, bt_a,
                               jnp.asarray([9]), jnp.asarray([8]),
                               implementation="pallas")
    yb = ops.prefill_attention(q, kp_b, kp_b, bt_b,
                               jnp.asarray([9]), jnp.asarray([8]),
                               implementation="pallas")
    np.testing.assert_allclose(
        np.asarray(ya), np.asarray(yb), atol=1e-6, rtol=1e-6
    )


def test_oracle_matches_dense_reference():
    """The paged XLA oracle on a contiguous layout equals the dense
    causal reference with a query offset — anchoring the paged prefill
    math to the pre-paging attention."""
    Kh, dh, nb, C, start = 2, 16, 3, 8, 12
    rng = np.random.default_rng(5)
    seq_k = jnp.asarray(rng.normal(size=(1, nb * BS, Kh, dh)), jnp.float32)
    seq_v = jnp.asarray(rng.normal(size=(1, nb * BS, Kh, dh)), jnp.float32)
    bt = jnp.asarray([[1, 2, 3]], jnp.int32)
    kp = jnp.zeros((4, Kh, BS, dh)).at[bt[0]].set(_blocks(seq_k[0], nb))
    vp = jnp.zeros((4, Kh, BS, dh)).at[bt[0]].set(_blocks(seq_v[0], nb))
    q = jnp.asarray(rng.normal(size=(1, C, 4, dh)), jnp.float32)
    y = ops.prefill_attention(q, kp, vp, bt, jnp.asarray([start]),
                              jnp.asarray([C]), implementation="xla")
    y_ref = reference_attention(
        q, seq_k[:, :start + C], seq_v[:, :start + C],
        causal=True, q_offset=start,
    )
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(y_ref), atol=1e-5, rtol=1e-5
    )


def test_dead_and_padded_rows_exact_zero_both_paths():
    q, kp, vp, bt = _case(3, 8, 4, 2, 16, 2)
    starts, lens = [0, 7, 0], [0, 3, 0]
    for impl in ("xla", "pallas"):
        y = ops.prefill_attention(
            q, kp, vp, bt, jnp.asarray(starts), jnp.asarray(lens),
            implementation=impl,
        )
        assert bool(jnp.isfinite(y).all()), impl
        assert float(jnp.abs(y[0]).max()) == 0.0, impl  # dead lane
        assert float(jnp.abs(y[2]).max()) == 0.0, impl
        assert float(jnp.abs(y[1, 3:]).max()) == 0.0, impl  # padded rows
        assert float(jnp.abs(y[1, :3]).max()) > 0.0, impl


def test_bf16_pool_parity():
    q, kp, vp, bt = _case(2, 8, 8, 2, 16, 4, seed=11)
    kb, vb = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    st, ln = jnp.asarray([3, 16]), jnp.asarray([8, 6])
    y_xb = ops.prefill_attention(q, kb, vb, bt, st, ln,
                                 implementation="xla")
    y_pb = ops.prefill_attention(q, kb, vb, bt, st, ln,
                                 implementation="pallas")
    np.testing.assert_allclose(
        np.asarray(y_pb, np.float32), np.asarray(y_xb, np.float32),
        atol=1e-5, rtol=1e-5,
    )
    y_f32 = ops.prefill_attention(q, kp, vp, bt, st, ln,
                                  implementation="xla")
    np.testing.assert_allclose(
        np.asarray(y_pb, np.float32), np.asarray(y_f32),
        atol=3e-2, rtol=3e-2,
    )


def test_pick_q_tile_and_alignment_guard():
    from repro.kernels.paged_prefill import paged_prefill_attention_pallas

    assert pick_q_tile(128) == 128
    assert pick_q_tile(96) == 32  # largest pow2 divisor
    assert pick_q_tile(256) == 128  # capped
    # Fewer than 8 rows per tile (q_tile * GQA group): the whole chunk.
    assert pick_q_tile(7) == 7
    assert pick_q_tile(12) == 12
    assert pick_q_tile(12, group=2) == 4
    assert pick_q_tile(5, group=8) == 1
    with pytest.raises(ValueError, match="chunk_tokens"):
        pick_q_tile(0)
    q, kp, vp, bt = _case(1, 8, 4, 2, 16, 2)
    with pytest.raises(ValueError, match="q_tile"):
        paged_prefill_attention_pallas(  # 2 rows * G=2 < 8 sublanes
            q, kp, vp, bt, jnp.asarray([0]), jnp.asarray([8]),
            q_tile=2, interpret=False,
        )
    with pytest.raises(ValueError, match="must divide"):
        paged_prefill_attention_pallas(
            q, kp, vp, bt, jnp.asarray([0]), jnp.asarray([8]),
            q_tile=3, interpret=True,
        )


# ---------------------------------------------------------------------------
# unified row write (the mixed step's single cache-write path)
# ---------------------------------------------------------------------------


def test_row_write_matches_prefill_and_decode_writes():
    """The unified per-row scatter reproduces the dedicated prefill and
    decode write helpers position-for-position."""
    Kh, dh, nb = 2, 4, 3
    rng = np.random.default_rng(7)
    bt = jnp.asarray([[2, 3, 1]], jnp.int32)
    kv = jnp.asarray(rng.normal(size=(2 * BS, Kh, dh)), jnp.float32)

    pool_a = jnp.zeros((1 + nb, Kh, BS, dh), jnp.float32)
    pool_a = paged_prefill_write(pool_a, kv[None, :2 * BS], bt)

    pool_b = jnp.zeros((1 + nb, Kh, BS, dh), jnp.float32)
    R = 2 * BS
    rows = kv[:R][:, None]  # (R, 1, Kh, dh)
    tables = jnp.broadcast_to(bt, (R, nb))
    pos = jnp.arange(R, dtype=jnp.int32)
    pool_b = paged_row_write(pool_b, rows, tables, pos,
                             jnp.ones((R,), bool))
    np.testing.assert_allclose(np.asarray(pool_a), np.asarray(pool_b))

    tok = jnp.asarray(rng.normal(size=(1, 1, Kh, dh)), jnp.float32)
    dec = paged_decode_write(pool_a, tok, bt,
                             jnp.asarray([2 * BS], jnp.int32))
    row = paged_row_write(pool_b, tok, bt,
                          jnp.asarray([2 * BS], jnp.int32),
                          jnp.asarray([True]))
    np.testing.assert_allclose(np.asarray(dec), np.asarray(row))


def test_row_write_dead_rows_hit_trash_and_clamp():
    Kh, dh, nb = 2, 4, 2
    pool = jnp.zeros((4, Kh, BS, dh), jnp.float32)
    kv = jnp.ones((3, 1, Kh, dh), jnp.float32)
    tables = jnp.asarray([[1, 2], [1, 2], [0, 0]], jnp.int32)
    # Row 1 is dead with an out-of-table nominal position (a padded
    # chunk row past the slot's allocation): must clamp AND trash.
    pos = jnp.asarray([3, 5 * BS, 0], jnp.int32)
    live = jnp.asarray([True, False, False])
    out = paged_row_write(pool, kv, tables, pos, live)
    assert float(jnp.abs(out[1, :, 3]).min()) == 1.0  # live write landed
    assert float(jnp.abs(out[1]).sum()) == float(
        jnp.abs(out[1, :, 3]).sum()
    )
    assert float(jnp.abs(out[2]).max()) == 0.0  # nothing leaked
    assert float(jnp.abs(out[0, :, 0]).max()) == 1.0  # trash took the dead


# ---------------------------------------------------------------------------
# model-level mixed-step parity
# ---------------------------------------------------------------------------


def _dropless(cfg):
    if cfg.moe is None:
        return cfg
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.num_experts)
        )
    )


def _mixed_prefill(vals, cfg, cache, prompt, bt, *, B, NC, C, ac):
    """Drive zoo.paged_mixed_step over a chunk schedule covering the
    whole prompt; returns (cache, last-chunk logits)."""
    from repro.models import model_zoo as zoo

    nb = bt.shape[1]
    pos, lg = 0, None
    S = len(prompt)
    while pos < S:
        ctoks = np.zeros((NC, C), np.int32)
        ctab = np.zeros((NC, nb), np.int32)
        cstart = np.zeros((NC,), np.int32)
        clen = np.zeros((NC,), np.int32)
        ci = 0
        last_ci = 0
        while ci < NC and pos < S:
            n = min(C, S - pos)
            ctoks[ci, :n] = prompt[pos:pos + n]
            ctab[ci] = np.asarray(bt[0])
            cstart[ci] = pos
            clen[ci] = n
            last_ci = ci
            pos += n
            ci += 1
        cache, logits = zoo.paged_mixed_step(
            vals, jnp.zeros((B, 1), jnp.int32), jnp.asarray(ctoks),
            cache, jnp.zeros((B, nb), jnp.int32),
            jnp.zeros((B,), jnp.int32), jnp.asarray(ctab),
            jnp.asarray(cstart), jnp.asarray(clen), cfg, ac=ac,
        )
        lg = logits[B + last_ci]
    return cache, lg


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "granite-moe-1b-a400m"])
@pytest.mark.parametrize("C", [4, 8])
def test_mixed_step_matches_train_forward(arch, C):
    """Chunked prefill through the mixed step (chunks crossing block
    boundaries, multiple lanes per tick) + a mixed decode step
    reproduce the training forward's logits."""
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
    B, NC = 2, 2
    cache = zoo.init_paged_serve_cache(cfg, 1 + nb, BS, dtype=jnp.float32)
    bt = jnp.asarray([[3, 1, 4, 2]], jnp.int32)
    ac = zoo.ApplyCfg()
    prompt = list(np.asarray(toks[0, :S]))
    cache, lg = _mixed_prefill(vals, cfg, cache, prompt, bt,
                               B=B, NC=NC, C=C, ac=ac)
    np.testing.assert_allclose(
        np.asarray(lg), np.asarray(logits_full[0, S - 1]),
        atol=3e-3, rtol=3e-3,
    )
    # decode the next token through the mixed step's decode lane
    dec_tok = np.zeros((B, 1), np.int32)
    dec_tok[0, 0] = int(toks[0, S])
    dec_tab = np.zeros((B, nb), np.int32)
    dec_tab[0] = np.asarray(bt[0])
    dec_len = np.zeros((B,), np.int32)
    dec_len[0] = S
    cache, lg2 = zoo.paged_mixed_step(
        vals, jnp.asarray(dec_tok), jnp.zeros((NC, C), jnp.int32),
        cache, jnp.asarray(dec_tab), jnp.asarray(dec_len),
        jnp.zeros((NC, nb), jnp.int32), jnp.zeros((NC,), jnp.int32),
        jnp.zeros((NC,), jnp.int32), cfg, ac=ac,
    )
    np.testing.assert_allclose(
        np.asarray(lg2[0]), np.asarray(logits_full[0, S]),
        atol=3e-3, rtol=3e-3,
    )


def test_mixed_step_pallas_matches_xla():
    """The full mixed step (decode lane + chunk lane live in the SAME
    call) agrees between the Pallas paged kernels and the XLA oracles."""
    from repro.configs import get_reduced
    from repro.models import model_zoo as zoo
    from repro.models import param as pm

    cfg = _dropless(get_reduced("granite-moe-1b-a400m"))
    p = zoo.init_params(jax.random.PRNGKey(0), cfg)
    vals, _ = pm.split(p)
    nb, B, NC, C = 3, 2, 1, 8
    bt0 = np.asarray([2, 3, 1], np.int32)
    bt1 = np.asarray([4, 5, 6], np.int32)
    prompt0 = list(range(40, 49))  # 9 tokens, decoding slot
    prompt1 = list(range(60, 68))  # 8-token chunk, prefilling slot
    outs = {}
    for impl in ("xla", "pallas"):
        ac = zoo.ApplyCfg(attn_impl=impl, moe_impl="xla")
        cache = zoo.init_paged_serve_cache(cfg, 7, BS, dtype=jnp.float32)
        cache, _ = _mixed_prefill(
            vals, cfg, cache, prompt0, jnp.asarray(bt0[None]),
            B=B, NC=NC, C=C, ac=ac,
        )
        dec_tok = np.asarray([[7], [0]], np.int32)
        dec_tab = np.stack([bt0, np.zeros(nb, np.int32)])
        dec_len = np.asarray([9, 0], np.int32)
        ctoks = np.zeros((NC, C), np.int32)
        ctoks[0] = prompt1
        cache, lg = zoo.paged_mixed_step(
            vals, jnp.asarray(dec_tok), jnp.asarray(ctoks), cache,
            jnp.asarray(dec_tab), jnp.asarray(dec_len),
            jnp.asarray(bt1[None]), jnp.zeros((NC,), jnp.int32),
            jnp.asarray([C], jnp.int32), cfg, ac=ac,
        )
        outs[impl] = np.asarray(lg)
    np.testing.assert_allclose(
        outs["pallas"], outs["xla"], atol=1e-4, rtol=1e-4
    )
    assert int(outs["pallas"][0].argmax()) == int(outs["xla"][0].argmax())
    assert int(outs["pallas"][B].argmax()) == int(outs["xla"][B].argmax())
