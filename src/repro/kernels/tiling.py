"""Shared tile-size policy for the Pallas TPU kernels.

Two regimes:

* interpret mode (CPU validation) — clamp blocks exactly to the dim so
  tiny test shapes use tiny tiles.
* compiled TPU — clamp blocks to the 128-aligned ceiling of the dim:
  a dim smaller than the requested block is zero-padded up to ONE
  MXU-aligned tile (the kernels' padding already guarantees zero rows
  contribute zero, forward and backward), while an explicitly requested
  misaligned block raises a clear error instead of an opaque Mosaic
  lowering failure.

Tile sizes themselves come from a VMEM budget model (``tune_expert_tiles``
/ ``tune_attention_tiles``) rather than fixed defaults: each kernel
family's worst-case resident f32 working set (scratch accumulators plus
resident output windows — the terms the Mosaic pipeline cannot stream)
is evaluated against the per-core VMEM budget and the tile sizes are
halved, largest contributor first, until the model fits. The dW kernel's
``6 * d * bf`` accumulator+output term is what drives ``bf`` down to 128
at d_model >= 4096 (see kernels/README.md).

The grouped-GEMM kernels first try whole-expert windows
(``grouped_expert_tiles``), judged by a model that counts the
pipeline's double buffers against the scoped limit they request, and
fall back to ``tune_expert_tiles``.
"""
from __future__ import annotations

# Per-core VMEM on the reference part (TPU v5e). The tuners keep the
# modeled resident set under this; streamed input tiles are double-
# buffered by the pipeline and counted once.
VMEM_BUDGET_BYTES = 16 * 1024 * 1024
# Scoped VMEM the expert-FFN kernels request from Mosaic (its default
# scoped limit is the 16 MiB above). The pipeline double-buffers every
# streamed window on top of the modeled resident set: with whole-expert
# windows the grouped dW kernel at f32 and granite-3b widths (d 1536,
# f 512) models 51 MiB (``grouped_vmem_bytes``). A v5e core has 128 MiB.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
MXU = 128


def clamp_tile(block: int, dim: int, interpret: bool) -> int:
    if interpret:
        return min(block, dim)
    return min(block, -(-dim // 128) * 128)


def check_mxu_alignment(kernel: str, interpret: bool, **tiles: int) -> None:
    """Compiled TPU kernels need MXU-aligned tiles; interpret mode (the
    CPU validation path) accepts anything."""
    if interpret:
        return
    bad = {n: v for n, v in tiles.items() if v % 128}
    if bad:
        raise ValueError(
            f"{kernel} Pallas tile sizes must be multiples of 128 (MXU "
            f"lane width) when compiled for TPU; got {bad}. Pick aligned "
            "block sizes (dims smaller than one block are padded "
            "automatically), or run interpret=True."
        )


def _align128(dim: int) -> int:
    return -(-dim // 128) * 128


def expert_tile_vmem_bytes(bc: int, bf: int, bd: int, d: int) -> int:
    """Worst-case resident f32 bytes across the expert-FFN kernel family
    (fwd / dx / dW of kernels/grouped_mlp.py — ``bc`` is the row-block
    dim, ``bm`` there).

    Terms follow kernels/README.md: per-kernel scratch accumulators plus
    the full-d resident output window, plus the (non-full-d) input tiles
    the step actually touches. The dW kernel is modeled as its f32
    accumulators + resident output blocks (``6 * dp * bf``) — the term
    that forces bf=128 at d >= 4096.
    """
    dp = _align128(d)
    fwd = bc * bd + 2 * bd * bf + bf * dp + 2 * bc * bf + bc * dp
    dx = 3 * bc * bf + bc * dp + 2 * bc * bd + 3 * bd * bf
    dw = 6 * dp * bf
    return 4 * max(fwd, dx, dw)


def tune_expert_tiles(
    cap: int, f: int, d: int, *,
    budget_bytes: int = VMEM_BUDGET_BYTES,
    bc: int = 128, bf: int = 256, bd: int = 512,
) -> tuple[int, int, int]:
    """Pick (bc, bf, bd) for the expert-FFN kernels from the VMEM model.

    Starts from the historical defaults (128, 256, 512) and halves the
    dominant contributors (bf, then bd, then bc) down to the 128-tile
    floor until the modeled resident set fits ``budget_bytes``. Covers
    the README case: d_model >= 4096 -> bf = 128.
    """
    while expert_tile_vmem_bytes(bc, bf, bd, d) > budget_bytes:
        if bf > MXU:
            bf //= 2
        elif bd > MXU:
            bd //= 2
        elif bc > MXU:
            bc //= 2
        else:
            break  # floor reached: d too large for this kernel family
    return bc, bf, bd


def grouped_vmem_bytes(
    bm: int, bf: int, bd: int, d: int, itemsize: int, *, gated: bool = True,
) -> tuple[int, int, int]:
    """Modeled VMEM bytes of the grouped-GEMM kernels (forward, dx, dW)
    at tiles ``(bm, bf, bd)`` (kernels/grouped_mlp.py).

    Unlike ``expert_tile_vmem_bytes``, every window the pipeline streams
    is counted twice (it double-buffers inputs and outputs) at the
    operands' ``itemsize``, on top of the f32 scratch accumulators and
    the largest f32 value a step builds in VMEM (forward: the hidden tile
    and the full-d output product; dx: one d tile of the expansion; dW:
    one ``(dp, bf)`` weight-gradient product).
    """
    dp = _align128(d)
    nw = 2 if gated else 1  # wi (+ wg)
    fwd = (2 * itemsize * (bm * bd + nw * bd * bf + bf * dp + bm * dp)
           + 4 * (nw * bm * bf + bm * bf + bm * dp))
    dx = (2 * itemsize * (2 * bm * bd + nw * bd * bf + bf * bd + bm * dp)
          + 4 * ((nw + 1) * bm * bf + bm * dp + bm * bd))
    dw = (2 * itemsize * (2 * bm * dp + 2 * (nw + 1) * dp * bf)
          + 4 * (nw + 2) * dp * bf)
    return fwd, dx, dw


def grouped_expert_tiles(
    f: int, d: int, itemsize: int, *, bm: int = 128, gated: bool = True,
    limit_bytes: int = VMEM_LIMIT_BYTES,
) -> tuple[int, int, int]:
    """Pick ``(bf, bd, bf_dw)`` for the grouped-GEMM kernels by shape.

    Whole-expert windows (``bf = fp``, ``bd = dp``: one f and one d tile)
    when the forward and dx kernels' modeled sets (``grouped_vmem_bytes``)
    fit ``limit_bytes`` less an eighth kept for Mosaic's own scratch. An
    expert's weight windows are then the same for every row-block of its
    segment, so the pipeline fetches them once per segment instead of
    once per ``bm`` rows. The dW kernel (``bf_dw``) takes the widest
    128-multiple divisor of ``fp`` its own model fits. A shape whose
    whole windows do not fit keeps ``tune_expert_tiles``' tiles in all
    three kernels.
    """
    fp, dp = _align128(f), _align128(d)
    budget = limit_bytes - limit_bytes // 8
    fwd, dx, _ = grouped_vmem_bytes(bm, fp, dp, d, itemsize, gated=gated)
    if max(fwd, dx) > budget:
        _, bf, bd = tune_expert_tiles(0, f, d)
        return bf, bd, bf
    bf_dw = fp
    while bf_dw > MXU and (
        fp % bf_dw
        or grouped_vmem_bytes(bm, bf_dw, dp, d, itemsize, gated=gated)[2]
        > budget
    ):
        bf_dw -= MXU
    return fp, dp, bf_dw


def grouped_walk_fwd_bytes(
    live_blocks: int, total_blocks: int, bm: int, d: int, f: int,
    n_weights: int = 3, *, compacted: bool = True, itemsize: int = 2,
) -> int:
    """Modeled forward HBM bytes of the grouped-GEMM block walk
    (kernels/grouped_mlp.py), shared by benchmarks/roofline.py and
    benchmarks/kernels_micro.py.

    This is the tiled walk (several f or d tiles): per visited row-block
    it streams its owner's full weight set (``n_weights * d * f``: wi +
    wo, + wg when gated; with the whole-expert windows of
    ``grouped_expert_tiles`` they stream once per segment instead) and
    the block's ``bm * d`` input rows; every block's output rows are written
    (dead blocks write zeros — part of the layout contract). The
    *static* walk streams x/weight tiles for dead blocks too; the
    *compacted* walk pins dead steps to the previous live block's
    resident tiles, so only live blocks pay input bytes — bytes become
    ragged like FLOPs.
    """
    read_blocks = live_blocks if compacted else total_blocks
    w_bytes = read_blocks * n_weights * d * f * itemsize
    x_bytes = read_blocks * bm * d * itemsize
    y_bytes = total_blocks * bm * d * itemsize
    return w_bytes + x_bytes + y_bytes


def paged_decode_fwd_bytes(
    lengths, block_size: int, kv_heads: int, head_dim: int, *,
    n_heads: int, itemsize: int = 2, q_itemsize: int = 4,
) -> int:
    """Modeled HBM bytes of ONE paged flash-decode step over a slot
    batch (kernels/decode_attention.py), shared by benchmarks/roofline.

    Per slot the block-table walk streams k + v for the slot's LIVE
    blocks only (``ceil(len/bs) * bs`` rows — dead steps pin to the last
    live block and fetch nothing), plus the (H, dh) query read and
    output write. A dense ``(B, max_len)`` cache read pays ``max_len``
    rows per slot regardless of length — pass ``lengths = [max_len]*B``
    to model it (the ``paged_vs_dense`` roofline ratio).
    """
    kv_rows = sum(
        -(-int(n) // block_size) * block_size for n in lengths
    )
    kv_bytes = 2 * kv_rows * kv_heads * head_dim * itemsize
    qo_bytes = 2 * len(lengths) * n_heads * head_dim * q_itemsize
    return kv_bytes + qo_bytes


def decode_attention_flops(lengths, n_heads: int, head_dim: int) -> int:
    """Single-query GQA decode FLOPs: qk^T + pv = 4*H*len*dh per slot."""
    return sum(4 * n_heads * int(n) * head_dim for n in lengths)


def paged_prefill_fwd_bytes(
    start: int, chunk_len: int, q_tile: int, block_size: int,
    kv_heads: int, head_dim: int, *, n_heads: int, itemsize: int = 2,
    q_itemsize: int = 4,
) -> int:
    """Modeled HBM bytes of ONE chunk through the paged prefill-attention
    kernel (kernels/paged_prefill.py), shared by benchmarks/roofline.

    Grid (Kh, nq, nb), block walk innermost: each q tile re-streams the
    KV blocks it attends — blocks past the tile's causal limit
    ``ceil((start + min((qi+1)*bq, len)) / bs)`` pin their windows to
    the last needed block, so dead steps fetch nothing (the DMA-elision
    claim stays a TPU-validation item; interpret mode cannot measure
    it). Plus the chunk's q read and o write. Compare with
    ``paged_decode_fwd_bytes``: decoding the same ``chunk_len`` tokens
    one step at a time walks the table ``chunk_len`` times.
    """
    kv_rows = 0
    for q0 in range(0, chunk_len, q_tile):
        hi = min(q0 + q_tile, chunk_len)
        kv_rows += -(-(start + hi) // block_size) * block_size
    kv_bytes = 2 * kv_rows * kv_heads * head_dim * itemsize
    qo_bytes = 2 * chunk_len * n_heads * head_dim * q_itemsize
    return kv_bytes + qo_bytes


def paged_prefill_flops(start: int, chunk_len: int, n_heads: int,
                        head_dim: int) -> int:
    """Chunk GQA attention FLOPs: row i attends start + i + 1 positions,
    qk^T + pv = 4*H*dh per (query, key) pair."""
    total_kv = sum(start + i + 1 for i in range(chunk_len))
    return 4 * n_heads * head_dim * total_kv


def attention_tile_vmem_bytes(bq: int, bk: int, dh: int) -> int:
    """Worst-case resident f32 bytes across the flash-attention kernels
    (fwd / dq / dkv). The dkv kernel dominates: q+do tiles, k/v tiles,
    dk/dv f32 accumulators, and the (bq, bk) p/ds score tiles."""
    dhp = _align128(dh)
    fwd = 2 * bq * dhp + 2 * bk * dhp + bq * bk + 2 * bq
    dq = fwd + bq * bk + bq * dhp
    dkv = 2 * bq * dhp + 4 * bk * dhp + 2 * bq * bk
    return 4 * max(fwd, dq, dkv)


def tune_attention_tiles(
    sq: int, skv: int, dh: int, *,
    budget_bytes: int = VMEM_BUDGET_BYTES,
    bq: int = 512, bk: int = 512,
) -> tuple[int, int]:
    """Pick (bq, bk) for the flash-attention kernels from the VMEM model
    (alternate halving, 128-tile floor)."""
    while attention_tile_vmem_bytes(bq, bk, dh) > budget_bytes:
        if bq >= bk and bq > MXU:
            bq //= 2
        elif bk > MXU:
            bk //= 2
        else:
            break
    return bq, bk
