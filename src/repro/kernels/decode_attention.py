"""Paged flash-decode attention Pallas TPU kernel (GQA, single query).

The serving hot path: one query token per sequence slot attending over
that slot's KV cache, which lives as fixed-size blocks scattered through
a global pool ``(num_blocks, Kh, block_size, dh)`` (repro/serve paged KV
cache; head-major so a block's ``(block_size, dh)`` slab is the trailing
pair Mosaic tiles). Each slot's blocks are named by a **block table**
``(B, nb)`` of pool block ids; sequences are ragged (per-slot
``lengths``), so dense
``(B, max_len)`` cache reads would stream ``max_len`` bytes per slot no
matter how short the sequence is.

The kernel walks each slot's block table with **scalar prefetch** (the
same ``PrefetchScalarGridSpec`` discipline as the grouped-GEMM kernel in
``grouped_mlp.py``): the block table and the per-slot lengths are
prefetched into SMEM and drive the k/v BlockSpec *index maps*, so grid
step ``(b, kh, j)`` DMAs exactly pool block ``block_tables[b, j]`` —
no gather materialization, reads scale with ``ceil(length/bs)`` blocks.

* grid ``(B, Kh, nb)``, block index innermost; the GQA query group
  ``(G, dh)`` with ``G = H // Kh`` rides along as the kernel tile.
* online softmax over the block walk: running ``(m, l, acc)`` in VMEM
  scratch (``(G, 1)``, ``(G, 1)``, ``(G, dh)`` f32), exactly the flash
  forward residual structure; the output tile is written once at the
  last block step.
* ragged lengths: blocks past ``ceil(length/bs)`` are **dead** — their
  grid steps skip all compute via a scalar ``pl.when`` and their k/v
  index maps clamp to the slot's last live block, so the pipeline's
  same-window revisit check elides the fetch (the compacted-walk trick
  from ``grouped_mlp.py``): dead steps stream no bytes. ``length == 0``
  (a free slot in the continuous-batching engine) produces exact zeros.
* bf16 cache reads: k/v tiles are cast to f32 at the MXU boundary
  (``preferred_element_type`` discipline), matching the XLA oracle's
  promotion, so bf16 pools cost half the HBM bytes of f32 with the same
  accumulate precision.

VMEM per step: ``G*dh`` (q) + ``2*bs*dh`` (k, v) + ``G*bs`` (scores) +
``G*(dh + 2)`` f32 scratch — a few KB at (G, bs, dh) = (8, 16, 128);
decode is HBM-bound, the tiny tiles exist to keep reads ragged (see
``tiling.paged_decode_fwd_bytes`` and ``benchmarks/roofline.py
kernel.decode_attention.*``).

Serving-only: no VJP is registered (training-through-decode is a ROADMAP
open item). The XLA oracle/fallback is ``ops.decode_attention(...,
implementation="xla")`` — a pool gather + the dense masked-softmax
``models/attention._decode_attention``.

This kernel serves the DECODE lane of the mixed serve step; the prefill
chunk lanes run its multi-token sibling ``paged_prefill.py`` (same
block-table walk, but a q-tile x kv-block grid that amortizes the walk
over ``bq`` chunk rows — see ``benchmarks/kernels_micro.py
paged_prefill_chunk_vs_decode_walk`` for why prefilling through the
single-query walk would re-stream the whole live prefix per token).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _decode_kernel(bt_ref, ln_ref, q_ref, k_ref, v_ref, o_ref,
                   m_acc, l_acc, acc, *, scale: float, bs: int, nb: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)
        acc[...] = jnp.zeros_like(acc)

    # Any valid key in this block? Dead blocks (past the slot's length,
    # or the whole walk for a free slot with length 0) skip all compute;
    # their k/v windows are pinned to the last live block by the index
    # maps, so they stream nothing either.
    live = j * bs < ln_ref[b]

    @pl.when(live)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, dh)
        k = k_ref[0, 0].astype(jnp.float32)  # (bs, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (G, bs)
        G = s.shape[0]
        kv_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (G, bs), 1)
        mask = kv_pos < ln_ref[b]
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_acc[...]  # (G, 1)
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
        # Rows with no valid key (length 0) keep l == 0: emit zeros, the
        # continuous-batching engine never reads free slots' outputs.
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(
    q, k_pool, v_pool, block_tables, lengths, *, interpret: bool = False,
):
    """q: (B, H, dh); k_pool/v_pool: (P, Kh, bs, dh) global block pools;
    block_tables: (B, nb) int32 pool block ids; lengths: (B,) int32 valid
    kv tokens per slot. Returns (B, H, dh) in q's dtype.

    GQA: H % Kh == 0; query head h reads kv head h // (H // Kh), encoded
    by the (B, Kh, G, dh) reshape — identical head order to the dense
    decode oracle.
    """
    B, H, dh = q.shape
    P, Kh, bs, _ = k_pool.shape
    if H % Kh:
        raise ValueError(f"H ({H}) must be a multiple of Kh ({Kh})")
    G = H // Kh
    nb = block_tables.shape[1]
    qg = q.reshape(B, Kh, G, dh)
    block_tables = block_tables.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)

    def kv_map(b, kh, j, bt, ln):
        # Dead steps clamp to the slot's last live block: same window as
        # the previous step -> the pipeline skips the fetch (length 0
        # pins to bt[b, 0], one fetch, compute skipped anyway).
        nlive = (ln[b] + bs - 1) // bs
        jj = jnp.minimum(j, jnp.maximum(nlive - 1, 0))
        return (bt[b, jj], kh, 0, 0)

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Kh, nb),
        in_specs=[
            pl.BlockSpec(
                (1, 1, G, dh), lambda b, kh, j, bt, ln: (b, kh, 0, 0)
            ),
            pl.BlockSpec((1, 1, bs, dh), kv_map),
            pl.BlockSpec((1, 1, bs, dh), kv_map),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, G, dh), lambda b, kh, j, bt, ln: (b, kh, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, scale=dh ** -0.5, bs=bs, nb=nb
        ),
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((B, Kh, G, dh), q.dtype),
        interpret=interpret,
    )(block_tables, lengths, qg, k_pool, v_pool)
    return out.reshape(B, H, dh)
