"""Paged prefill-attention Pallas TPU kernel (GQA, multi-token chunks).

The chunked-prefill half of the mixed serve step (repro/serve): a
*chunk* is a run of ``C`` consecutive prompt tokens of one request,
admitted alongside the live decode batch. Its k/v are scattered into the
request's paged KV blocks **before** attention (one cache-write path for
both lanes, ``models/attention.paged_row_write``), so the kernel only
ever reads the pool: queries at absolute positions ``start + i`` attend
over every pool position ``<= start + i`` — earlier chunks, the shared
prompt prefix (prefix cache) and the chunk itself are all just block
reads, no separate "local fresh kv" path.

Compared to ``decode_attention.py`` (one query per slot, grid
``(B, Kh, nb)``) this kernel amortizes the block-table walk over a
**q-tile x kv-block grid** ``(NC, Kh, nq, nb)``: each step streams one
KV block against a ``(bq*G, dh)`` query tile (the chunk's rows with the
GQA group folded in, row ``i`` = chunk row ``i // G``, group member
``i % G``) — an ``(bq*G, bs)`` MXU matmul instead of ``bq`` separate
``(G, bs)`` decode steps re-walking the same table. Queries, the pool
``(P, Kh, bs, dh)`` and the output are all head-major, so every block's
trailing pair is ``(rows, dh)`` as Mosaic requires.

* scalar prefetch: ``block_tables (NC, nb)``, ``starts (NC,)`` and
  ``lens (NC,)`` ride ``PrefetchScalarGridSpec`` and drive the k/v
  BlockSpec index maps — grid step ``(c, kh, qi, j)`` DMAs exactly pool
  block ``block_tables[c, j]``.
* online softmax: running ``(m, l, acc)`` VMEM scratch across the block
  walk per q tile; output written once at the last block step; rows with
  no valid key (padded chunk rows, dead chunks) emit exact zeros.
* causal masking against ABSOLUTE positions: row ``i`` of chunk ``c``
  masks ``kv_pos <= starts[c] + i``; rows ``i >= lens[c]`` are fully
  masked (``lens[c] == 0`` marks a dead chunk lane).
* dead-step fetch elision: block steps past the q tile's causal limit
  ``ceil((starts[c] + min((qi+1)*bq, lens[c])) / bs)`` clamp their k/v
  windows to the tile's last needed block (dead tiles pin to block 0),
  so the pipeline's same-window revisit check elides the fetch — reads
  scale with the blocks each q tile actually attends, not ``nb``
  (byte model: ``tiling.paged_prefill_fwd_bytes``). The elision itself
  is a TPU-validation item: interpret mode cannot observe DMA traffic.
* bf16 pools cast to f32 at the MXU boundary (oracle-identical
  promotion), halving KV bytes at the same accumulate precision.

Serving-only: no VJP (chunked prefill under grad is the same ROADMAP
item as training-through-decode). Oracle/fallback:
``ops.prefill_attention(..., implementation="xla")`` — pool gather +
masked softmax over absolute positions (tests/test_paged_prefill.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def pick_q_tile(chunk_tokens: int, group: int = 1, cap: int = 128) -> int:
    """Largest power-of-two divisor of the chunk length, capped at
    ``cap`` — q tiles must tile the chunk exactly. A tile whose
    ``bq * group`` rows fall short of Mosaic's 8-row sublane tile widens
    to the whole chunk (a block equal to the array dim is always
    accepted)."""
    if chunk_tokens <= 0:
        raise ValueError(f"chunk_tokens must be >= 1, got {chunk_tokens}")
    bq = min(chunk_tokens & -chunk_tokens, cap)  # pow2 dividing C
    return bq if (bq * group) % 8 == 0 else chunk_tokens


def _prefill_kernel(bt_ref, st_ref, ln_ref, q_ref, k_ref, v_ref, o_ref,
                    m_acc, l_acc, acc, *, scale: float, bs: int, bq: int,
                    group: int, nb: int):
    c = pl.program_id(0)
    qi = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)
        acc[...] = jnp.zeros_like(acc)

    ln = ln_ref[c]
    st = st_ref[c]
    # Causal limit of this q tile: its top row (the last valid one)
    # attends kv positions < st + min((qi+1)*bq, ln). Tiles fully past
    # the chunk's valid rows, and block steps past the limit, are dead:
    # compute skipped here, fetch elided by the pinned index maps.
    hi = jnp.minimum((qi + 1) * bq, ln)
    live = (qi * bq < ln) & (j * bs < st + hi)

    @pl.when(live)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq*G, dh)
        k = k_ref[0, 0].astype(jnp.float32)  # (bs, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (bq*G, bs)
        shape = s.shape
        row_i = jax.lax.broadcasted_iota(jnp.int32, shape, 0) // group
        kv_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = (
            (qi * bq + row_i < ln)                 # valid chunk row
            & (kv_pos <= st + qi * bq + row_i)     # absolute causality
        )
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_acc[...]  # (bq*G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
        alpha = jnp.where(
            jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0
        )
        m_acc[...] = m_new
        l_acc[...] = l_acc[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nb - 1)
    def _():
        l = l_acc[...]
        # Rows with no valid key (padded rows of a partial chunk, dead
        # chunk lanes) keep l == 0: emit exact zeros.
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("q_tile", "interpret"))
def paged_prefill_attention_pallas(
    q, k_pool, v_pool, block_tables, starts, lens, *,
    q_tile: int = 0, interpret: bool = False,
):
    """q: (NC, C, H, dh) chunk queries; k_pool/v_pool: (P, Kh, bs, dh)
    global block pools (chunk k/v already written); block_tables:
    (NC, nb) int32 pool block ids per chunk's slot; starts: (NC,) int32
    absolute position of q[c, 0]; lens: (NC,) int32 valid rows per chunk
    (0 = dead chunk lane -> exact-zero output). Returns (NC, C, H, dh).

    ``q_tile`` (0 = auto via :func:`pick_q_tile`) must divide C and,
    compiled, give ``q_tile * G`` rows divisible by 8 unless it is C;
    GQA exactly as the decode kernel (head h reads kv head h // (H //
    Kh)).
    """
    NC, C, H, dh = q.shape
    P, Kh, bs, _ = k_pool.shape
    if H % Kh:
        raise ValueError(f"H ({H}) must be a multiple of Kh ({Kh})")
    G = H // Kh
    nb = block_tables.shape[1]
    bq = q_tile or pick_q_tile(C, G)
    if C % bq:
        raise ValueError(
            f"q_tile ({bq}) must divide the chunk length ({C})"
        )
    nq = C // bq
    if not interpret and (bq * G) % 8 and bq != C:
        # The q block is (q_tile * G, dh) rows of a (C * G, dh) array:
        # Mosaic takes a row count that is a multiple of 8 or the whole
        # array. Any head_dim and block_size compile (the pool block is
        # always a whole (block_size, dh) slab).
        raise ValueError(
            "compiled paged prefill needs (q_tile * GQA group) % 8 == 0 "
            f"or q_tile == chunk length; got q_tile={bq}, G={G}, C={C}. "
            "Run interpret=True for CPU validation."
        )
    # (NC, Kh, C*G, dh) grouped-query layout, q tiles on the row axis.
    qg = q.reshape(NC, C, Kh, G, dh).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(NC, Kh, C * G, dh)
    block_tables = block_tables.astype(jnp.int32)
    starts = starts.astype(jnp.int32)
    lens = lens.astype(jnp.int32)

    def kv_map(c, kh, qi, j, bt, st, ln):
        # Blocks past the q tile's causal limit clamp to its last needed
        # block (dead tiles pin to the table head): same window as the
        # previous step -> the pipeline elides the fetch.
        hi = jnp.minimum((qi + 1) * bq, ln[c])
        limit = jnp.where(qi * bq < ln[c], st[c] + hi, 0)
        nlive = (limit + bs - 1) // bs
        jj = jnp.minimum(j, jnp.maximum(nlive - 1, 0))
        return (bt[c, jj], kh, 0, 0)

    q_spec = pl.BlockSpec(
        (1, 1, bq * G, dh),
        lambda c, kh, qi, j, bt, st, ln: (c, kh, qi, 0),
    )
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(NC, Kh, nq, nb),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, 1, bs, dh), kv_map),
            pl.BlockSpec((1, 1, bs, dh), kv_map),
        ],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((bq * G, 1), jnp.float32),
            pltpu.VMEM((bq * G, 1), jnp.float32),
            pltpu.VMEM((bq * G, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _prefill_kernel, scale=dh ** -0.5, bs=bs, bq=bq, group=G,
            nb=nb,
        ),
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        interpret=interpret,
    )(block_tables, starts, lens, qg, k_pool, v_pool)
    out = out.reshape(NC, Kh, C, G, dh).transpose(0, 2, 1, 3, 4)
    return out.reshape(NC, C, H, dh)
