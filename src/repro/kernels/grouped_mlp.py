"""Grouped-GEMM expert FFN over a sorted ragged token buffer (Pallas TPU),
forward + custom-VJP backward.

This is the MoE layer's expert FFN (core/moe.py): tokens arrive as a
flat expert-sorted stream ``xs: (G, M, d)`` in which expert ``e``'s rows occupy one
contiguous *block-aligned* segment. Per-expert segment geometry is given
by ``group_sizes: (G, E)`` — the number of VALID rows per expert — and
the layout contract (shared with core/moe.py via ``ragged_row_offsets``):

* each expert's segment is padded up to a multiple of the row-block size
  ``bm`` and holds at least one block (so every expert owns >= 1 block,
  which keeps the dW grid total and lets empty experts emit zero grads);
* padded rows (and the tail past the last segment) are all-zero, so they
  contribute zero forward and backward;
* static buffer size ``M = (ceil(N/bm) + E) * bm`` where ``N`` is the
  assignment count (g * k for token-choice routing) — *independent of
  capacity factor*, unlike ``E * cap``.

The kernels walk expert boundaries with **scalar prefetch**: three small
int32 tables, ``block_expert (G, nb)`` (which expert owns row-block m;
tail blocks clamp to E-1), ``block_live (G, nb)`` (does the block hold
any valid row) and ``prev_live (G, nb)`` (the most recent live block at
or before m; 0 when none), are prefetched into SMEM and drive the
x/weight BlockSpec index maps — so row-block m fetches exactly its
owner's weight tiles. Dead blocks skip all matmuls via scalar ``pl.when``
(their output/grad rows are written as zeros), making compute
proportional to the *filled* rows.

**Whole-expert windows:** consecutive blocks of one expert reuse the
resident weight tiles only when the block's last window equals the next
block's first, i.e. with one f and one d tile (``nf = nd = 1``). With
several, every block walks all its ``(fi, di)`` windows again and the
expert's whole weight set streams once per ``bm`` rows. Tiles left to
the caller (``bf = bd = None``) therefore come from
``tiling.grouped_expert_tiles``: ``bf = fp, bd = dp`` wherever the
double-buffered forward and dx windows fit the scoped VMEM limit, so an
expert's weights are fetched once per segment; the dW kernel takes the
widest f window its own set fits. Shapes too wide for that keep the
tuned tiles of ``tune_expert_tiles``.

**Compacted block walk (bytes ragged like FLOPs):** a dead block's grid
steps pin every *input* index map to the previous live block's final
resident window (via the ``prev_live`` table), so the pipeline's
same-window revisit check suppresses the fetch entirely — dead blocks
stream no x or weight tiles, only their zero output write. A leading
dead run (block 0 dead) falls back to block 0's own tiles, one fetch.
The static grid shape is unchanged; only the data walk is compacted, so
HBM read bytes now track the *live* blocks exactly like the FLOPs do
(see ``kernels.tiling.grouped_walk_fwd_bytes`` for the byte model and
``benchmarks/roofline.py kernel.grouped_mlp.cf*`` for the ratios vs a
padded capacity buffer).

Contract note: dead-block rows get ``dx = 0`` — valid because the combine
step never reads their outputs, so their cotangent is identically zero
(the ref oracle's autodiff, fed a nonzero cotangent there, would instead
produce ``act'(0)``-shaped gradients for ungated activations).

Forward: grid (G, nb, nf, nd), d innermost — accumulate the hidden over
d tiles, activate, accumulate the output — with ``block_expert[g, m]``
choosing each row-block's expert weights.

Backward (``grouped_mlp_pallas_vjp``): residuals are the inputs only
(xs, wi, wg, wo + the int32 block tables); the (bm, f) hidden tensors are
recomputed in-kernel:

* dx kernel — grid (G, nb, nf, 2*nd), a two-phase d-sweep (phase 1
  re-accumulates a/g/dh, activation VJP at the phase boundary, phase 2
  expands da/dg into a persistent (bm, d) f32 dx accumulator).
* dW kernel — grid (G, f/bf_dw, nb), row-blocks innermost. f32 VMEM
  accumulators are zeroed at each expert-segment START (detected from
  the prefetched ``block_expert`` table: block m starts a segment iff
  ``be[m] != be[m-1]``), accumulated across the segment's blocks, and
  flushed at the segment END into *per-group* dW outputs (G, E, d, f),
  summed over G outside the kernel.

See src/repro/kernels/README.md for VMEM budgets.
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
    grouped_expert_tiles,
    VMEM_LIMIT_BYTES,
)

_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _act_fn(name: str):
    from repro.models.layers import activation

    return activation(name)


# ---------------------------------------------------------------------------
# ragged layout helpers (the contract between core/moe.py and the kernels)
# ---------------------------------------------------------------------------


def ragged_buffer_rows(n_assignments: int, num_experts: int, bm: int) -> int:
    """Static row count M of the block-aligned ragged buffer: worst case
    over all ways to split ``n_assignments`` rows into ``num_experts``
    bm-aligned min-one-block segments. Independent of capacity factor."""
    return (-(-n_assignments // bm) + num_experts) * bm


def ragged_row_offsets(group_sizes: jax.Array, bm: int):
    """group_sizes (..., E) valid rows per expert ->
    (row_off (..., E+1), valid_off (..., E+1)): aligned segment starts and
    cumulative valid counts. Expert e's valid rows live at
    [row_off[e], row_off[e] + group_sizes[e])."""
    blocks = jnp.maximum(1, -(-group_sizes // bm))
    aligned = blocks * bm
    zero = jnp.zeros_like(group_sizes[..., :1])
    row_off = jnp.concatenate([zero, jnp.cumsum(aligned, -1)], -1)
    valid_off = jnp.concatenate([zero, jnp.cumsum(group_sizes, -1)], -1)
    return row_off, valid_off


def ragged_destinations(key: jax.Array, num_experts: int, block: int):
    """Shared sort-and-pack step of the sorted dispatches (single-device
    core/moe.py and the per-device leg of core/ep.py): stable-sort each
    row of ``key (G, N)`` — expert id per assignment, ``num_experts``
    marking invalid — and compute every assignment's destination row in
    the block-aligned ragged buffer.

    Returns ``(perm, key_s, counts, dest, M)``: the sort permutation,
    sorted keys, per-expert valid counts ``(G, E)``, destination rows in
    sorted order (``M`` = trash row for invalid assignments), and the
    static buffer row count. Keeping this next to ``ragged_buffer_rows``
    / ``ragged_row_offsets`` keeps the layout contract in one place.
    """
    G, N = key.shape
    iota = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32)[None], (G, N))
    key_s, perm = jax.lax.sort((key, iota), dimension=1, num_keys=1)
    counts = (
        (key_s[..., None] == jnp.arange(num_experts)).sum(1)
        .astype(jnp.int32)
    )
    M = ragged_buffer_rows(N, num_experts, block)
    row_off, valid_off = ragged_row_offsets(counts, block)  # (G, E+1)
    rank = iota - jnp.take_along_axis(valid_off, key_s, axis=1)
    dest = jnp.where(
        key_s < num_experts,
        jnp.take_along_axis(row_off, key_s, axis=1) + rank,
        M,
    )
    return perm, key_s, counts, dest, M


def ragged_sources(key: jax.Array, weight: jax.Array, num_experts: int,
                   block: int):
    """Both directions of ``ragged_destinations``' block-aligned packing
    (the same stable layout by expert), with no scatter and no gather:
    the tables of the token-choice sorted dispatch, which moves rows
    through them by gathers alone (``core/moe.py``).

    Rows to assignments: one stable sort of the stream together with
    ``M - N`` filler entries, expert ``e`` getting the
    ``row_off[e+1] - row_off[e] - counts[e]`` fillers that pad its
    segment to whole blocks (the rest, and the invalid assignments, sort
    past the last segment). Sorted, row ``m`` holds its assignment's
    stream index and ``weight`` (``N`` and 0 on a filler's row).
    Assignments to rows: ``row_off[e] +`` the assignment's rank among
    the assignments of ``e`` before it in stream order, a prefix sum (the
    rank the stable sort gives it).

    Returns ``(counts, slot, weight_rows, dest_of, M)``: per-expert valid
    counts ``(G, E)``; ``slot (G, M)`` — the stream index (position in
    ``key``) of row ``m``'s assignment, ``N`` on pad and tail rows;
    ``weight_rows (G, M)`` — its ``weight``, 0 there; ``dest_of
    (G, N)`` — the row of every assignment in stream order, ``M`` for
    invalid ones; the static buffer row count.
    """
    G, N = key.shape
    E = num_experts
    M = ragged_buffer_rows(N, E, block)
    valid = key < E
    oh = (key[..., None] == jnp.arange(E)).astype(jnp.int32)  # (G, N, E)
    counts = oh.sum(1)
    row_off, _ = ragged_row_offsets(counts, block)  # (G, E+1)
    before = jnp.cumsum(oh, axis=1) - oh  # same-expert assignments before
    dest_of = jnp.where(
        valid, (oh * (row_off[:, None, :E] + before)).sum(-1), M
    )

    # Filler f pads expert e's segment iff ends[e-1] <= f < ends[e].
    ends = jnp.cumsum(row_off[:, 1:] - row_off[:, :-1] - counts, axis=-1)
    fill = jnp.arange(M - N, dtype=jnp.int32)
    fill_key = (fill[None, :, None] >= ends[:, None, :]).sum(
        -1, dtype=key.dtype)  # (G, M - N)
    stream = jnp.where(valid, jnp.arange(N, dtype=jnp.int32), N)
    _, slot, weight_rows = jax.lax.sort(
        (jnp.concatenate([key, fill_key], axis=1),
         jnp.concatenate([stream, jnp.full((G, M - N), N, jnp.int32)], 1),
         jnp.concatenate([jnp.where(valid, weight, 0),
                          jnp.zeros((G, M - N), weight.dtype)], 1)),
        dimension=1, num_keys=1,
    )
    return counts, slot, weight_rows, dest_of, M


def block_tables(group_sizes: jax.Array, bm: int, nb: int):
    """Scalar-prefetch tables for the kernels' expert-boundary walk.

    Returns (block_expert (G, nb) int32 — owner of row-block m, tail
    blocks clamped to E-1; block_live (G, nb) int32 — 1 iff the block
    holds at least one valid row)."""
    G, E = group_sizes.shape
    blocks = jnp.maximum(1, -(-group_sizes // bm))
    live_blocks = -(-group_sizes // bm)  # blocks with >= 1 valid row
    bend = jnp.cumsum(blocks, axis=-1)  # (G, E) segment block ends
    b = jnp.arange(nb, dtype=jnp.int32)
    be = (b[None, :, None] >= bend[:, None, :]).sum(-1).astype(jnp.int32)
    be = jnp.minimum(be, E - 1)
    bstart = jnp.concatenate(
        [jnp.zeros((G, 1), bend.dtype), bend[:, :-1]], axis=-1
    )
    rel = b[None, :] - jnp.take_along_axis(bstart, be, axis=1)
    bl = rel < jnp.take_along_axis(live_blocks, be, axis=1)
    return be, bl.astype(jnp.int32)


def prev_live_table(block_live: jax.Array) -> jax.Array:
    """(G, nb) int32: index of the most recent LIVE row-block at or
    before m (0 when no live block precedes m). Dead grid steps pin
    their input index maps to this block's resident tiles, which the
    pipeline's same-window revisit check turns into a no-fetch — the
    compacted block walk."""
    nb = block_live.shape[-1]
    idx = jnp.arange(nb, dtype=jnp.int32)[None]
    marked = jnp.where(block_live > 0, idx, -1)
    return jnp.maximum(jax.lax.cummax(marked, axis=1), 0).astype(jnp.int32)


def _tiles(bm, bf, bd, xs, wi, wg, interpret):
    """(bf, bd, bf_dw): explicit tiles where given (the dW kernel then
    takes ``bf`` too), else ``grouped_expert_tiles``' choice by shape;
    clamped to the dims and checked for the MXU."""
    _, M, d = xs.shape
    f = wi.shape[-1]
    # bm is a LAYOUT parameter (the caller aligned segments to it): it is
    # never clamped, only validated.
    if M % bm:
        raise ValueError(
            f"ragged buffer rows ({M}) must be a multiple of the row "
            f"block bm={bm} (use ragged_buffer_rows to size the buffer)"
        )
    item = max(xs.dtype.itemsize, wi.dtype.itemsize)
    rbf, rbd, bf_dw = grouped_expert_tiles(f, d, item, bm=bm,
                                           gated=wg is not None)
    bf_dw = bf_dw if bf is None else bf
    bf = clamp_tile(rbf if bf is None else bf, f, interpret)
    bd = clamp_tile(rbd if bd is None else bd, d, interpret)
    bf_dw = clamp_tile(bf_dw, f, interpret)
    if (f + (-f) % bf) % bf_dw:  # interpret mode clamps tiles to f exactly
        bf_dw = bf
    check_mxu_alignment("grouped MLP", interpret, bm=bm, bf=bf, bd=bd,
                        bf_dw=bf_dw)
    return bf, bd, bf_dw


def _pad_fd(xs, wi, wg, wo, bf, bd):
    G, M, d = xs.shape
    f = wi.shape[-1]
    pf, pd = (-f) % bf, (-d) % bd
    if pd:
        xs = jnp.pad(xs, ((0, 0), (0, 0), (0, pd)))
    if pd or pf:
        wi = jnp.pad(wi, ((0, 0), (0, pd), (0, pf)))
        if wg is not None:
            wg = jnp.pad(wg, ((0, 0), (0, pd), (0, pf)))
        wo = jnp.pad(wo, ((0, 0), (0, pf), (0, pd)))
    return xs, wi, wg, wo, pf, pd


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(be_ref, bl_ref, x_ref, wi_ref, wg_ref, wo_ref, o_ref,
                h_acc, g_acc, *, act: str, nd: int):
    g = pl.program_id(0)
    m = pl.program_id(1)
    fi = pl.program_id(2)
    di = pl.program_id(3)
    live = bl_ref[g, m] > 0

    # The (g, m) output block spans full d and is revisited across all
    # (fi, di) steps: zero it once, then accumulate per f tile. Dead
    # blocks only get the zero write.
    @pl.when((fi == 0) & (di == 0))
    def _():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(live & (di == 0))
    def _():
        h_acc[...] = jnp.zeros_like(h_acc)
        if g_acc is not None:
            g_acc[...] = jnp.zeros_like(g_acc)

    @pl.when(live)
    def _():
        x = x_ref[0]  # (bm, bd)
        h_acc[...] += jnp.dot(
            x, wi_ref[0], preferred_element_type=jnp.float32
        )
        if g_acc is not None:
            g_acc[...] += jnp.dot(
                x, wg_ref[0], preferred_element_type=jnp.float32
            )

    @pl.when(live & (di == nd - 1))
    def _():
        h = _act_fn(act)(h_acc[...])
        if g_acc is not None:
            h = h * g_acc[...]
        y = jnp.dot(
            h.astype(wo_ref.dtype), wo_ref[0],
            preferred_element_type=jnp.float32,
        )
        o_ref[0] = (o_ref[0].astype(jnp.float32) + y).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("act", "bm", "bf", "bd", "interpret"),
)
def grouped_mlp_pallas(
    xs, wi, wg, wo, group_sizes, *, act: str = "silu",
    bm: int = 128, bf=None, bd=None, interpret: bool = False,
):
    """xs: (G, M, d) expert-sorted block-aligned rows -> (G, M, d).
    Forward only (no VJP registered — use ``grouped_mlp_pallas_vjp``
    under ``jax.grad``)."""
    be, bl = block_tables(group_sizes, bm, xs.shape[1] // bm)
    return _grouped_mlp_pallas_tables(
        xs, wi, wg, wo, be, bl,
        act=act, bm=bm, bf=bf, bd=bd, interpret=interpret,
    )


def _compact_walk_maps(nf: int, nd: int):
    """Input index-map factories for the compacted block walk: a live
    block m walks its tiles normally; a dead block pins every input
    window to the previous live block's FINAL window (x tile at
    di=nd-1, wi/wg at (nd-1, nf-1), wo at (nf-1, 0)) so the pipeline's
    same-window revisit check skips the fetch for the whole dead run."""

    def pick(live, m, pf_m):
        return jnp.where(live, m, pf_m)

    def x_map(g, m, di, be, bl, pf):
        live = bl[g, m] > 0
        return (g, pick(live, m, pf[g, m]), jnp.where(live, di, nd - 1))

    def wi_map(g, m, di, fi, be, bl, pf):
        live = bl[g, m] > 0
        mm = pick(live, m, pf[g, m])
        return (be[g, mm], jnp.where(live, di, nd - 1),
                jnp.where(live, fi, nf - 1))

    def wo_map(g, m, fi, be, bl, pf):
        live = bl[g, m] > 0
        mm = pick(live, m, pf[g, m])
        return (be[g, mm], jnp.where(live, fi, nf - 1), 0)

    return x_map, wi_map, wo_map


@functools.partial(
    jax.jit,
    static_argnames=("act", "bm", "bf", "bd", "interpret"),
)
def _grouped_mlp_pallas_tables(
    xs, wi, wg, wo, be, bl, *, act: str,
    bm: int, bf, bd, interpret: bool,
):
    G, M, d = xs.shape
    E, _, f = wi.shape
    bf, bd, _ = _tiles(bm, bf, bd, xs, wi, wg, interpret)
    xs, wi, wg, wo, pf, pd = _pad_fd(xs, wi, wg, wo, bf, bd)
    fp, dp = f + pf, d + pd
    nb, nf, nd = M // bm, fp // bf, dp // bd
    gated = wg is not None
    pl_tbl = prev_live_table(bl)
    x_map, wi_map, wo_map = _compact_walk_maps(nf, nd)

    in_specs = [
        pl.BlockSpec(
            (1, bm, bd),
            lambda g, m, fi, di, be, bl, pt: x_map(g, m, di, be, bl, pt),
        ),
        pl.BlockSpec(
            (1, bd, bf),
            lambda g, m, fi, di, be, bl, pt: wi_map(
                g, m, di, fi, be, bl, pt
            ),
        ),
    ]
    args = [xs, wi]
    if gated:
        in_specs.append(
            pl.BlockSpec(
                (1, bd, bf),
                lambda g, m, fi, di, be, bl, pt: wi_map(
                    g, m, di, fi, be, bl, pt
                ),
            )
        )
        args.append(wg)
    # wo tile and the output block span the FULL d dim (same discipline as
    # the padded kernel): the second matmul produces all d columns per
    # (bm, bf) tile, accumulated over f.
    in_specs.append(
        pl.BlockSpec(
            (1, bf, dp),
            lambda g, m, fi, di, be, bl, pt: wo_map(g, m, fi, be, bl, pt),
        )
    )
    args.append(wo)

    scratch = [pltpu.VMEM((bm, bf), jnp.float32)]
    if gated:
        scratch.append(pltpu.VMEM((bm, bf), jnp.float32))

    def kernel(be_ref, bl_ref, pt_ref, *refs):
        if gated:
            x_ref, wi_ref, wg_ref, wo_ref, o_ref, h_acc, g_acc = refs
        else:
            x_ref, wi_ref, wo_ref, o_ref, h_acc = refs
            wg_ref = g_acc = None
        _fwd_kernel(be_ref, bl_ref, x_ref, wi_ref, wg_ref, wo_ref, o_ref,
                    h_acc, g_acc, act=act, nd=nd)

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(G, nb, nf, nd),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, bm, dp), lambda g, m, fi, di, be, bl, pt: (g, m, 0)
        ),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((G, M, dp), xs.dtype),
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(be, bl, pl_tbl, *args)
    if pd:
        out = out[:, :, :d]
    return out


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _recompute_grads_f_tile(x, dy, wi_t, wg_t, wo_t, act):
    """One (bm, bf) tile of the hidden-space gradients, from full-d rows.

    Returns (h, da, dg): the post-activation hidden (for dwo) and the
    pre-activation gradients (for dwi/dwg/dx). dg is None when ungated.
    """
    a = jnp.dot(x, wi_t, preferred_element_type=jnp.float32)
    dh = jax.lax.dot_general(  # dy @ wo_t^T
        dy, wo_t, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    act_out, act_vjp = jax.vjp(_act_fn(act), a)
    if wg_t is not None:
        g = jnp.dot(x, wg_t, preferred_element_type=jnp.float32)
        h = act_out * g
        da = act_vjp(dh * g)[0]
        dg = dh * act_out
    else:
        h = act_out
        da = act_vjp(dh)[0]
        dg = None
    return h, da, dg


def _dx_kernel(be_ref, bl_ref, x_ref, wi_ref, wg_ref, wo_ref, dy_ref,
               dx_ref, a_acc, g_acc, dh_acc, dx_acc, *,
               act: str, nd: int, nf: int, bd: int):
    """The two-phase d-sweep over ragged row-blocks. Phase 1 (t < nd): accumulate a, g, dh over d tiles.
    Phase boundary (t == nd): activation VJP in place. Phase 2: expand
    da/dg back to d tiles into the persistent (bm, dp) dx scratch."""
    g = pl.program_id(0)
    m = pl.program_id(1)
    fi = pl.program_id(2)
    t = pl.program_id(3)
    live = bl_ref[g, m] > 0

    @pl.when((fi == 0) & (t == 0))
    def _():
        dx_acc[...] = jnp.zeros_like(dx_acc)

    @pl.when(live & (t == 0))
    def _():
        a_acc[...] = jnp.zeros_like(a_acc)
        dh_acc[...] = jnp.zeros_like(dh_acc)
        if g_acc is not None:
            g_acc[...] = jnp.zeros_like(g_acc)

    @pl.when(live & (t < nd))
    def _():
        x = x_ref[0]  # (bm, bd)
        a_acc[...] += jnp.dot(
            x, wi_ref[0], preferred_element_type=jnp.float32
        )
        if g_acc is not None:
            g_acc[...] += jnp.dot(
                x, wg_ref[0], preferred_element_type=jnp.float32
            )
        dh_acc[...] += jax.lax.dot_general(  # dy @ wo_tile^T -> (bm, bf)
            dy_ref[0], wo_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(live & (t == nd))
    def _():
        a, dh = a_acc[...], dh_acc[...]
        act_out, act_vjp = jax.vjp(_act_fn(act), a)
        if g_acc is not None:
            gv = g_acc[...]
            a_acc[...] = act_vjp(dh * gv)[0]
            g_acc[...] = dh * act_out
        else:
            a_acc[...] = act_vjp(dh)[0]

    @pl.when(live & (t >= nd))
    def _():
        di = jax.lax.rem(t, nd)
        da = a_acc[...]
        contrib = jax.lax.dot_general(  # da @ wi_tile^T -> (bm, bd)
            da, wi_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if g_acc is not None:
            contrib += jax.lax.dot_general(
                g_acc[...], wg_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        dx_acc[:, pl.ds(di * bd, bd)] += contrib

    @pl.when((fi == nf - 1) & (t == 2 * nd - 1))
    def _():
        dx_ref[0] = dx_acc[...].astype(dx_ref.dtype)


def _dw_kernel(be_ref, bl_ref, x_ref, wi_ref, wg_ref, wo_ref, dy_ref,
               dwi_ref, dwg_ref, dwo_ref, dwi_acc, dwg_acc, dwo_acc, *,
               act: str, nb: int):
    """Expert-segment walk: zero the f32 accumulators at each segment
    start, fold in one (bm, bf) recomputed hidden tile per live block,
    flush into the per-group dW outputs at the segment end."""
    g = pl.program_id(0)
    m = pl.program_id(2)
    e = be_ref[g, m]
    live = bl_ref[g, m] > 0
    prev = be_ref[g, jnp.maximum(m - 1, 0)]
    nxt = be_ref[g, jnp.minimum(m + 1, nb - 1)]
    seg_start = (m == 0) | (prev != e)
    seg_end = (m == nb - 1) | (nxt != e)

    @pl.when(seg_start)
    def _():
        dwi_acc[...] = jnp.zeros_like(dwi_acc)
        dwo_acc[...] = jnp.zeros_like(dwo_acc)
        if dwg_acc is not None:
            dwg_acc[...] = jnp.zeros_like(dwg_acc)

    @pl.when(live)
    def _():
        x = x_ref[0]  # (bm, dp)
        dy = dy_ref[0]
        h, da, dg = _recompute_grads_f_tile(
            x, dy, wi_ref[0], wg_ref[0] if wg_ref is not None else None,
            wo_ref[0], act,
        )
        xt_dot = functools.partial(
            jax.lax.dot_general,  # x^T @ grad -> (dp, bf)
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dwi_acc[...] += xt_dot(x, da)
        if dwg_acc is not None:
            dwg_acc[...] += xt_dot(x, dg)
        dwo_acc[...] += xt_dot(h, dy.astype(jnp.float32))

    @pl.when(seg_end)
    def _():
        dwi_ref[0, 0] = dwi_acc[...].astype(dwi_ref.dtype)
        dwo_ref[0, 0] = dwo_acc[...].astype(dwo_ref.dtype)
        if dwg_acc is not None:
            dwg_ref[0, 0] = dwg_acc[...].astype(dwg_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("act", "bm", "bf", "bd", "interpret"),
)
def _grouped_mlp_pallas_bwd(xs, wi, wg, wo, dy, be, bl, *, act: str,
                            bm: int, bf, bd, interpret: bool):
    """Returns (dx, dwi, dwg, dwo); dwg is None when wg is None."""
    G, M, d = xs.shape
    E, _, f = wi.shape
    bf, bd, bf_dw = _tiles(bm, bf, bd, xs, wi, wg, interpret)
    xs, wi, wg, wo, pf, pd = _pad_fd(xs, wi, wg, wo, bf, bd)
    if pd:
        dy = jnp.pad(dy, ((0, 0), (0, 0), (0, pd)))
    fp, dp = f + pf, d + pd
    nb, nf, nd = M // bm, fp // bf, dp // bd
    nf_dw = fp // bf_dw
    gated = wg is not None
    pl_tbl = prev_live_table(bl)
    x_map, wi_map, _ = _compact_walk_maps(nf, nd)

    # ---- dx: grid (G, nb, nf, 2*nd), two-phase over the last axis ------
    # Same compacted walk as the forward: dead blocks pin every input
    # window to the previous live block's final window (no fetch).
    di_of = lambda t, nd=nd: jax.lax.rem(t, nd)

    def dx_wo_map(g, m, fi, t, be, bl, pt):
        live = bl[g, m] > 0
        mm = jnp.where(live, m, pt[g, m])
        return (be[g, mm], jnp.where(live, fi, nf - 1),
                jnp.where(live, di_of(t), nd - 1))

    in_specs = [
        pl.BlockSpec(
            (1, bm, bd),
            lambda g, m, fi, t, be, bl, pt: x_map(
                g, m, di_of(t), be, bl, pt
            ),
        ),
        pl.BlockSpec(
            (1, bd, bf),
            lambda g, m, fi, t, be, bl, pt: wi_map(
                g, m, di_of(t), fi, be, bl, pt
            ),
        ),
    ]
    args = [xs, wi]
    if gated:
        in_specs.append(
            pl.BlockSpec(
                (1, bd, bf),
                lambda g, m, fi, t, be, bl, pt: wi_map(
                    g, m, di_of(t), fi, be, bl, pt
                ),
            )
        )
        args.append(wg)
    in_specs.append(pl.BlockSpec((1, bf, bd), dx_wo_map))
    args.append(wo)
    in_specs.append(
        pl.BlockSpec(
            (1, bm, bd),
            lambda g, m, fi, t, be, bl, pt: x_map(
                g, m, di_of(t), be, bl, pt
            ),
        )
    )
    args.append(dy)

    scratch = [
        pltpu.VMEM((bm, bf), jnp.float32),  # a (phase 1) / da (phase 2)
        pltpu.VMEM((bm, bf), jnp.float32),  # dh
        pltpu.VMEM((bm, dp), jnp.float32),  # dx accumulator (across f)
    ]
    if gated:
        scratch.insert(1, pltpu.VMEM((bm, bf), jnp.float32))  # g / dg

    def dx_kernel(be_ref, bl_ref, pt_ref, *refs):
        if gated:
            (x_ref, wi_ref, wg_ref, wo_ref, dy_ref, dx_ref,
             a_acc, g_acc, dh_acc, dx_acc) = refs
        else:
            (x_ref, wi_ref, wo_ref, dy_ref, dx_ref,
             a_acc, dh_acc, dx_acc) = refs
            wg_ref = g_acc = None
        _dx_kernel(be_ref, bl_ref, x_ref, wi_ref, wg_ref, wo_ref, dy_ref,
                   dx_ref, a_acc, g_acc, dh_acc, dx_acc,
                   act=act, nd=nd, nf=nf, bd=bd)

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(G, nb, nf, 2 * nd),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, bm, dp), lambda g, m, fi, t, be, bl, pt: (g, m, 0)
        ),
        scratch_shapes=scratch,
    )
    dx = pl.pallas_call(
        dx_kernel,
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((G, M, dp), xs.dtype),
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(be, bl, pl_tbl, *args)

    # ---- dW: grid (G, nf_dw, nb), row-blocks innermost -----------------
    # Outputs are PER GROUP (G, E, ...) — summed over G below; this is the
    # same contract the padded path gets from vmap'ing the dW kernel over
    # groups. Every expert owns >= 1 block per group (layout contract), so
    # every (g, e, fi) output block is flushed exactly once. Dead blocks
    # still take part in the segment walk (an empty expert's single dead
    # block flushes its zeroed accumulators — that is how it emits zero
    # dW), but their INPUT windows pin to the previous live block (m
    # innermost here, so the pin targets the previous step's resident
    # tiles at the same fi) and stream nothing.
    def dw_x_map(g, fi, m, be, bl, pt):
        return (g, jnp.where(bl[g, m] > 0, m, pt[g, m]), 0)

    def dw_wi_map(g, fi, m, be, bl, pt):
        mm = jnp.where(bl[g, m] > 0, m, pt[g, m])
        return (be[g, mm], 0, fi)

    def dw_wo_map(g, fi, m, be, bl, pt):
        mm = jnp.where(bl[g, m] > 0, m, pt[g, m])
        return (be[g, mm], fi, 0)

    in_specs = [
        pl.BlockSpec((1, bm, dp), dw_x_map),
        pl.BlockSpec((1, dp, bf_dw), dw_wi_map),
    ]
    args = [xs, wi]
    if gated:
        in_specs.append(pl.BlockSpec((1, dp, bf_dw), dw_wi_map))
        args.append(wg)
    in_specs.append(pl.BlockSpec((1, bf_dw, dp), dw_wo_map))
    args.append(wo)
    in_specs.append(pl.BlockSpec((1, bm, dp), dw_x_map))
    args.append(dy)

    out_specs = [
        pl.BlockSpec(
            (1, 1, dp, bf_dw),
            lambda g, fi, m, be, bl, pt: (g, be[g, m], 0, fi),
        ),
        pl.BlockSpec(
            (1, 1, bf_dw, dp),
            lambda g, fi, m, be, bl, pt: (g, be[g, m], fi, 0),
        ),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((G, E, dp, fp), wi.dtype),
        jax.ShapeDtypeStruct((G, E, fp, dp), wo.dtype),
    ]
    scratch = [
        pltpu.VMEM((dp, bf_dw), jnp.float32),  # dwi
        pltpu.VMEM((bf_dw, dp), jnp.float32),  # dwo
    ]
    if gated:
        out_specs.insert(
            1,
            pl.BlockSpec(
                (1, 1, dp, bf_dw),
                lambda g, fi, m, be, bl, pt: (g, be[g, m], 0, fi),
            ),
        )
        out_shape.insert(1, jax.ShapeDtypeStruct((G, E, dp, fp), wg.dtype))
        scratch.insert(1, pltpu.VMEM((dp, bf_dw), jnp.float32))

    def dw_kernel(be_ref, bl_ref, pt_ref, *refs):
        if gated:
            (x_ref, wi_ref, wg_ref, wo_ref, dy_ref,
             dwi_ref, dwg_ref, dwo_ref,
             dwi_acc, dwg_acc, dwo_acc) = refs
        else:
            (x_ref, wi_ref, wo_ref, dy_ref,
             dwi_ref, dwo_ref, dwi_acc, dwo_acc) = refs
            wg_ref = dwg_ref = dwg_acc = None
        _dw_kernel(be_ref, bl_ref, x_ref, wi_ref, wg_ref, wo_ref, dy_ref,
                   dwi_ref, dwg_ref, dwo_ref, dwi_acc, dwg_acc, dwo_acc,
                   act=act, nb=nb)

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(G, nf_dw, nb),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    dws = pl.pallas_call(
        dw_kernel,
        grid_spec=gs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(be, bl, pl_tbl, *args)
    if gated:
        dwi_pg, dwg_pg, dwo_pg = dws
    else:
        dwi_pg, dwo_pg = dws
        dwg_pg = None

    # Cross-group reduction in f32, cast back to the weight dtype.
    reduce = lambda t, dt: t.astype(jnp.float32).sum(0).astype(dt)
    dwi = reduce(dwi_pg, wi.dtype)
    dwo = reduce(dwo_pg, wo.dtype)
    dwg = reduce(dwg_pg, wg.dtype) if gated else None

    if pd:
        dx = dx[:, :, :d]
    if pd or pf:
        dwi = dwi[:, :d, :f]
        dwo = dwo[:, :f, :d]
        if gated:
            dwg = dwg[:, :d, :f]
    return dx, dwi, dwg, dwo


@functools.lru_cache(maxsize=None)
def _make_grouped_mlp_vjp(act: str, bm: int, bf, bd, interpret: bool,
                          gated: bool):
    kw = dict(act=act, bm=bm, bf=bf, bd=bd, interpret=interpret)
    zero_int = lambda x: np.zeros(x.shape, jax.dtypes.float0)

    if gated:
        @jax.custom_vjp
        def fn(xs, wi, wg, wo, be, bl):
            return _grouped_mlp_pallas_tables(xs, wi, wg, wo, be, bl, **kw)

        def fwd(xs, wi, wg, wo, be, bl):
            return fn(xs, wi, wg, wo, be, bl), (xs, wi, wg, wo, be, bl)

        def bwd(res, dy):
            xs, wi, wg, wo, be, bl = res
            dx, dwi, dwg, dwo = _grouped_mlp_pallas_bwd(
                xs, wi, wg, wo, dy, be, bl, **kw
            )
            return dx, dwi, dwg, dwo, zero_int(be), zero_int(bl)
    else:
        @jax.custom_vjp
        def fn(xs, wi, wo, be, bl):
            return _grouped_mlp_pallas_tables(
                xs, wi, None, wo, be, bl, **kw
            )

        def fwd(xs, wi, wo, be, bl):
            return fn(xs, wi, wo, be, bl), (xs, wi, wo, be, bl)

        def bwd(res, dy):
            xs, wi, wo, be, bl = res
            dx, dwi, _, dwo = _grouped_mlp_pallas_bwd(
                xs, wi, None, wo, dy, be, bl, **kw
            )
            return dx, dwi, dwo, zero_int(be), zero_int(bl)

    fn.defvjp(fwd, bwd)
    return fn


def grouped_mlp_pallas_vjp(
    xs, wi, wg, wo, group_sizes, *, act: str = "silu",
    bm: int = 128, bf=None, bd=None, interpret: bool = False,
):
    """Differentiable grouped-GEMM expert FFN over the sorted ragged
    buffer: Pallas forward + custom-VJP fused backward kernels. Drop-in
    for ``grouped_mlp_pallas`` anywhere gradients may flow."""
    be, bl = block_tables(group_sizes, bm, xs.shape[1] // bm)
    fn = _make_grouped_mlp_vjp(act, bm, bf, bd, bool(interpret),
                               wg is not None)
    if wg is None:
        return fn(xs, wi, wo, be, bl)
    return fn(xs, wi, wg, wo, be, bl)
