"""Flash attention Pallas TPU kernel (GQA, causal, cache-length masked),
forward + custom-VJP backward.

Online-softmax forward over KV tiles: grid (B, H, Sq/bq, Skv/bk), kv
innermost. Running (m, l, acc) live in VMEM scratch persisting across kv
iterations; the output tile is written once at the last kv step. Score
tiles (bq x bk) never leave VMEM — this is precisely the HBM-traffic term
the XLA fallback pays (see EXPERIMENTS.md §Perf).

Tiles default to (bq, bk) = (512, 512): VMEM per step =
q(512*dh) + k/v(2*512*dh) + s/p(2*512*512*4B=2MB) + acc(512*dh*4B)
≈ 3 MB at dh=128 — MXU-aligned, triple-bufferable by the pipeline.

GQA is handled in the index map: query head h reads kv head h // group.

Layout: the callers' (B, S, H, dh) tensors are transposed to head-major
(B, H, S, dh) around the kernels, so every block is (1, 1, tile, dh) with
(tile, dh) as the trailing pair Mosaic tiles; the per-row residuals
(lse, Δ) ride as (B, H, S, 1) columns for the same reason.

Backward (``flash_attention_pallas_vjp``): the forward additionally
returns the online-softmax log-sum-exp per query row (lse = m + log l),
the only residual beyond the op's own inputs/outputs. Score tiles are
recomputed per (q, kv) tile pair from (q, k, lse) — never stored — in two
kernels, each accumulating over its opposing tile axis:

* dq kernel — grid (B, H, Sq/bq, Skv/bk), kv innermost; dq accumulates in
  a (bq, dh) f32 scratch, flushed on the last kv step.
* dk/dv kernel — grid (B, Kh, Skv/bk, G*Sq/bq): the innermost axis sweeps
  the GQA group AND the q tiles, so dk/dv accumulate contributions from
  every query head of the group in (bk, dh) f32 scratch with no extra
  HBM-sized per-head buffers; flushed on the last (g, q) step.

The per-row Δ = rowsum(dO * O) term is precomputed outside the kernels
(elementwise, O(B*S*H*dh)). See src/repro/kernels/README.md for the VMEM
budgets.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import (
    check_mxu_alignment,
    clamp_tile,
    tune_attention_tiles,
)

NEG_INF = float("-inf")


def _clamp_qk_tiles(bq, bk, Sq, Skv, dh, interpret):
    """Tile sizes default (None) to the VMEM budget model in tiling.py
    ((512, 512) for ordinary head dims). Interpret: tiles shrink to the
    seq dims. Compiled: clamp to the 128-aligned ceiling (short/odd
    sequences zero-pad up to one MXU tile); explicitly misaligned tiles
    raise a clear error instead of an opaque Mosaic lowering failure."""
    if bq is None or bk is None:
        tq, tk = tune_attention_tiles(Sq, Skv, dh)
        bq = tq if bq is None else bq
        bk = tk if bk is None else bk
    bq = clamp_tile(bq, Sq, interpret)
    bk = clamp_tile(bk, Skv, interpret)
    check_mxu_alignment("flash attention", interpret, bq=bq, bk=bk)
    return bq, bk


def _tile_mask(qoff_ref, kvlen_ref, qi, ki, *, bq, bk, causal):
    """Valid-key mask for one (bq, bk) score tile — shared fwd/bwd."""
    kv_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kv_pos < kvlen_ref[0]
    if causal:
        q_pos = (
            qoff_ref[0] + qi * bq
            + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        )
        mask = mask & (kv_pos <= q_pos)
    return mask


def _tile_live(qoff_ref, kvlen_ref, qi, ki, *, bq, bk, causal):
    """Scalar: does this (q, kv) tile pair have ANY unmasked entry? Fully
    masked tiles (entirely past kv_len, or — causal — entirely in the
    future) are skipped in the backward kernels: their p/ds are all zero,
    so the matmuls would only add zeros. For causal Sq == Skv training
    this halves the backward tile count."""
    live = ki * bk < kvlen_ref[0]
    if causal:
        last_q = qoff_ref[0] + (qi + 1) * bq - 1
        live = live & (ki * bk <= last_q)
    return live


def _kernel(q_ref, k_ref, v_ref, qoff_ref, kvlen_ref, o_ref, lse_ref,
            m_acc, l_acc, acc, *, scale: float, causal: bool,
            bq: int, bk: int, nk: int):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)
        acc[...] = jnp.zeros_like(acc)

    q = q_ref[0, 0]  # (bq, dh)
    k = k_ref[0, 0]  # (bk, dh)
    v = v_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # (bq, bk)

    mask = _tile_mask(qoff_ref, kvlen_ref, qi, ki,
                      bq=bq, bk=bk, causal=causal)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_acc[...]  # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    m_acc[...] = m_new
    l_acc[...] = l_acc[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc[...] = acc[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _():
        l = l_acc[...]
        if lse_ref is not None:
            # +inf for rows with no valid key: exp(s - lse) == 0 in the
            # backward, so those rows contribute nothing — matching the
            # forward's all-zero output for them.
            lse_ref[0, 0] = jnp.where(
                l > 0.0, m_acc[...] + jnp.log(l), jnp.inf
            )
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[...] / l).astype(o_ref.dtype)


def _heads_major(x, pad):
    """(B, S, H, dh) -> (B, H, S + pad, dh): the kernels' block layout.
    Mosaic tiles the last two block dims, so (seq tile, dh) must be the
    trailing pair — a (1, bq, 1, dh) block on (B, S, H, dh) is refused."""
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return x.transpose(0, 2, 1, 3)


def _seq_major(x, n):
    """Inverse of :func:`_heads_major`, dropping the padded rows."""
    return x.transpose(0, 2, 1, 3)[:, :n]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "bq", "bk", "interpret", "return_residuals"),
)
def flash_attention_pallas(
    q, k, v, *, causal: bool = True, q_offset=0, kv_len=None,
    bq=None, bk=None, interpret: bool = False,
    return_residuals: bool = False,
):
    """q: (B, Sq, H, dh); k, v: (B, Skv, Kh, dh). GQA: H % Kh == 0.

    With ``return_residuals`` also returns the padded per-row logsumexp
    (B, H, ceil(Sq/bq)*bq) float32 — the backward-pass residual. This
    entry point registers no VJP; use ``flash_attention_pallas_vjp``
    under ``jax.grad``.
    """
    B, Sq, H, dh = q.shape
    _, Skv, Kh, _ = k.shape
    G = H // Kh
    bq, bk = _clamp_qk_tiles(bq, bk, Sq, Skv, dh, interpret)
    pq, pk = (-Sq) % bq, (-Skv) % bk
    qh = _heads_major(q, pq)
    kh, vh = _heads_major(k, pk), _heads_major(v, pk)
    nq, nk = qh.shape[2] // bq, kh.shape[2] // bk
    if kv_len is None:
        kv_len = Skv
    kv_len = jnp.asarray(kv_len, jnp.int32).reshape(1)
    q_offset = jnp.asarray(q_offset, jnp.int32).reshape(1)

    grid = (B, H, nq, nk)
    q_spec = pl.BlockSpec((1, 1, bq, dh), lambda b, h, qi, ki: (b, h, qi, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, bk, dh), lambda b, h, qi, ki: (b, h // G, ki, 0)
    )
    out_specs = q_spec
    out_shape = jax.ShapeDtypeStruct(qh.shape, q.dtype)
    if return_residuals:
        out_specs = [
            out_specs,
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
        ]
        out_shape = [
            out_shape,
            jax.ShapeDtypeStruct(qh.shape[:3] + (1,), jnp.float32),
        ]

    def kernel(*refs):
        if return_residuals:
            (q_ref, k_ref, v_ref, qoff_ref, kvlen_ref,
             o_ref, lse_ref, m_acc, l_acc, acc) = refs
        else:
            (q_ref, k_ref, v_ref, qoff_ref, kvlen_ref,
             o_ref, m_acc, l_acc, acc) = refs
            lse_ref = None
        _kernel(q_ref, k_ref, v_ref, qoff_ref, kvlen_ref, o_ref, lse_ref,
                m_acc, l_acc, acc,
                scale=dh ** -0.5, causal=causal, bq=bq, bk=bk, nk=nk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            q_spec, kv_spec, kv_spec,
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        interpret=interpret,
    )(qh, kh, vh, q_offset, kv_len)
    if return_residuals:
        out, lse = out
        return _seq_major(out, Sq), lse[..., 0]
    return _seq_major(out, Sq)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _recompute_p_ds(q, k, v, do, lse_col, delta_col, mask, scale):
    """Recompute one (bq, bk) probability tile and its score gradient;
    ``lse_col`` / ``delta_col`` are the tile's (bq, 1) row residuals."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    p = jnp.where(mask, jnp.exp(s - lse_col), 0.0)
    dp = jax.lax.dot_general(  # do @ v^T
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta_col)
    return p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               qoff_ref, kvlen_ref, dq_ref, dq_acc, *,
               scale: float, causal: bool, bq: int, bk: int, nk: int):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(_tile_live(qoff_ref, kvlen_ref, qi, ki,
                        bq=bq, bk=bk, causal=causal))
    def _():
        mask = _tile_mask(qoff_ref, kvlen_ref, qi, ki,
                          bq=bq, bk=bk, causal=causal)
        k = k_ref[0, 0]
        _, ds = _recompute_p_ds(
            q_ref[0, 0], k, v_ref[0, 0], do_ref[0, 0],
            lse_ref[0, 0], delta_ref[0, 0], mask, scale,
        )
        dq_acc[...] += jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32
        ) * scale

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                qoff_ref, kvlen_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                scale: float, causal: bool, bq: int, bk: int,
                nq: int, ng: int):
    ki = pl.program_id(2)
    t = pl.program_id(3)  # sweeps the GQA group x q tiles
    qi = jax.lax.rem(t, nq)

    @pl.when(t == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_tile_live(qoff_ref, kvlen_ref, qi, ki,
                        bq=bq, bk=bk, causal=causal))
    def _():
        mask = _tile_mask(qoff_ref, kvlen_ref, qi, ki,
                          bq=bq, bk=bk, causal=causal)
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        p, ds = _recompute_p_ds(
            q, k_ref[0, 0], v_ref[0, 0], do,
            lse_ref[0, 0], delta_ref[0, 0], mask, scale,
        )
        pT_dot = functools.partial(  # tile^T @ rows -> (bk, dh)
            jax.lax.dot_general,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dv_acc[...] += pT_dot(p.astype(do.dtype), do)
        dk_acc[...] += pT_dot(ds.astype(q.dtype), q) * scale

    @pl.when(t == ng * nq - 1)
    def _():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "bq", "bk", "interpret"),
)
def _flash_attention_pallas_bwd(
    q, k, v, out, lse, do, q_offset, kv_len, *,
    causal: bool, bq, bk, interpret: bool,
):
    """Returns (dq, dk, dv). ``lse`` is the padded residual from the
    forward; ``do`` the output cotangent (unpadded)."""
    B, Sq, H, dh = q.shape
    _, Skv, Kh, _ = k.shape
    G = H // Kh
    scale = dh ** -0.5
    bq, bk = _clamp_qk_tiles(bq, bk, Sq, Skv, dh, interpret)
    pq, pk = (-Sq) % bq, (-Skv) % bk

    # Δ = rowsum(dO * O): elementwise, done outside the kernels. Padded q
    # rows carry dO == 0, so Δ == 0 and every tile they touch contributes
    # zero to dk/dv; their dq rows are sliced off below.
    delta = (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(
        -1, keepdims=True
    )  # (B, Sq, H, 1)
    delta = _heads_major(delta, pq)  # (B, H, Sqp, 1)
    qh, doh = _heads_major(q, pq), _heads_major(do, pq)
    kh, vh = _heads_major(k, pk), _heads_major(v, pk)
    lse = lse[..., None]  # (B, H, Sqp, 1)
    nq, nk = qh.shape[2] // bq, kh.shape[2] // bk
    kv_len = jnp.asarray(kv_len, jnp.int32).reshape(1)
    q_offset = jnp.asarray(q_offset, jnp.int32).reshape(1)
    smem = [pl.BlockSpec(memory_space=pltpu.SMEM)] * 2

    q_rows = pl.BlockSpec((1, 1, bq, dh), lambda b, h, qi, ki: (b, h, qi, 0))
    q_col = pl.BlockSpec((1, 1, bq, 1), lambda b, h, qi, ki: (b, h, qi, 0))
    kv_rows = pl.BlockSpec(
        (1, 1, bk, dh), lambda b, h, qi, ki: (b, h // G, ki, 0)
    )
    dqh = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk
        ),
        grid=(B, H, nq, nk),
        in_specs=[q_rows, kv_rows, kv_rows, q_rows, q_col, q_col] + smem,
        out_specs=q_rows,
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, dh), jnp.float32)],
        interpret=interpret,
    )(qh, kh, vh, doh, lse, delta, q_offset, kv_len)

    # dk/dv: the last grid axis sweeps (group member g, q tile qi); the
    # index maps translate t -> (query head kh*G + g, row tile qi).
    h_of = lambda kh, t, G=G, nq=nq: kh * G + t // nq
    qi_of = lambda t, nq=nq: jax.lax.rem(t, nq)
    g_rows = pl.BlockSpec(
        (1, 1, bq, dh), lambda b, kh, ki, t: (b, h_of(kh, t), qi_of(t), 0)
    )
    g_col = pl.BlockSpec(
        (1, 1, bq, 1), lambda b, kh, ki, t: (b, h_of(kh, t), qi_of(t), 0)
    )
    own_rows = pl.BlockSpec(
        (1, 1, bk, dh), lambda b, kh, ki, t: (b, kh, ki, 0)
    )
    dkh, dvh = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
            nq=nq, ng=G,
        ),
        grid=(B, Kh, nk, G * nq),
        in_specs=[g_rows, own_rows, own_rows, g_rows, g_col, g_col] + smem,
        out_specs=[own_rows, own_rows],
        out_shape=[
            jax.ShapeDtypeStruct(kh.shape, k.dtype),
            jax.ShapeDtypeStruct(vh.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, dh), jnp.float32),
            pltpu.VMEM((bk, dh), jnp.float32),
        ],
        interpret=interpret,
    )(qh, kh, vh, doh, lse, delta, q_offset, kv_len)
    return _seq_major(dqh, Sq), _seq_major(dkh, Skv), _seq_major(dvh, Skv)


@functools.lru_cache(maxsize=None)
def _make_flash_vjp(causal: bool, bq, bk, interpret: bool):
    kw = dict(causal=causal, bq=bq, bk=bk, interpret=interpret)

    @jax.custom_vjp
    def fn(q, k, v, q_offset, kv_len):
        return flash_attention_pallas(
            q, k, v, q_offset=q_offset, kv_len=kv_len, **kw
        )

    def fwd(q, k, v, q_offset, kv_len):
        out, lse = flash_attention_pallas(
            q, k, v, q_offset=q_offset, kv_len=kv_len,
            return_residuals=True, **kw
        )
        return out, (q, k, v, out, lse, q_offset, kv_len)

    def bwd(res, do):
        q, k, v, out, lse, q_offset, kv_len = res
        dq, dk, dv = _flash_attention_pallas_bwd(
            q, k, v, out, lse, do, q_offset, kv_len, **kw
        )
        zero_int = lambda x: np.zeros(x.shape, jax.dtypes.float0)
        return dq, dk, dv, zero_int(q_offset), zero_int(kv_len)

    fn.defvjp(fwd, bwd)
    return fn


def flash_attention_pallas_vjp(
    q, k, v, *, causal: bool = True, q_offset=0, kv_len=None,
    bq=None, bk=None, interpret: bool = False,
):
    """Differentiable flash attention: forward Pallas kernel + fused
    backward kernels via ``jax.custom_vjp``. Drop-in for
    ``flash_attention_pallas`` anywhere gradients may flow."""
    if kv_len is None:
        kv_len = k.shape[1]
    fn = _make_flash_vjp(bool(causal), bq, bk, bool(interpret))
    return fn(
        q, k, v,
        jnp.asarray(q_offset, jnp.int32),
        jnp.asarray(kv_len, jnp.int32),
    )
