"""Kernel micro-benchmarks (CPU wall-time for the XLA paths; the Pallas
kernels are TPU-targeted and validated for correctness in interpret mode —
their perf effect is modeled in the roofline, benchmarks/roofline.py).

Forward AND fwd+bwd (``jax.value_and_grad``) timings for the two training
hot spots, so backward-path regressions show up next to the forward ones.
Set ``REPRO_BENCH_SMOKE=1`` (scripts/verify.sh) for a seconds-scale run at
reduced shapes that still exercises the Pallas custom-VJP kernels in
interpret mode.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timed
from repro.kernels import ops

SMOKE = bool(int(os.environ.get("REPRO_BENCH_SMOKE", "0")))


def _ragged_rows(key, M, d, counts, bm):
    """Random rows in the valid ragged segments, zeros on pad/tail rows —
    the layout contract ops.grouped_mlp documents (zero rows are what
    keep the Pallas dead-block skip and the XLA ragged_dot tail
    numerically identical)."""
    from repro.kernels.grouped_mlp import ragged_row_offsets

    row_off, _ = ragged_row_offsets(counts, bm)  # (G, E+1)
    rows_i = jnp.arange(M)[None, :, None]
    in_seg = (
        (rows_i >= row_off[:, None, :-1])
        & (rows_i < row_off[:, None, :-1] + counts[:, None, :])
    ).any(-1)
    xs = jax.random.normal(key, (counts.shape[0], M, d), jnp.float32)
    return xs * in_seg[..., None]


def run() -> list[tuple[str, float, str]]:
    rows = []
    key = jax.random.PRNGKey(0)

    # Sorted ragged dispatch (grouped GEMM): the buffer's static row
    # count M = (ceil(g*k/bm) + E) * bm does not depend on the capacity
    # factor, so neither do its FLOPs.
    from repro.kernels.grouped_mlp import ragged_buffer_rows

    d2, f2 = (64, 128) if SMOKE else (256, 512)
    reps = 3 if SMOKE else 10
    g_tok, E2, k2 = (128, 4, 1) if SMOKE else (512, 8, 1)  # switch-style
    bm = 8 if SMOKE else 32  # CPU-bench block; the TPU kernel uses 128
    ks = jax.random.split(key, 4)
    wi2 = jax.random.normal(ks[0], (E2, d2, f2)) * 0.05
    wg2 = jax.random.normal(ks[1], (E2, d2, f2)) * 0.05
    wo2 = jax.random.normal(ks[2], (E2, f2, d2)) * 0.05
    n_assign = g_tok * k2
    M = ragged_buffer_rows(n_assign, E2, bm)
    counts = jnp.full((1, E2), n_assign // E2, jnp.int32)
    xs_r = _ragged_rows(ks[3], M, d2, counts, bm)

    f_sort = jax.jit(lambda x, c: ops.grouped_mlp(
        x, wi2, wg2, wo2, c, act="silu", block=bm))
    us_sort = timed(f_sort, xs_r, counts, n=reps)
    ca = f_sort.lower(xs_r, counts).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    # XLA's CPU lowering of ragged_dot expands to a dense per-expert
    # loop, so the measured FLOPs run ~E above the model.
    rows.append((
        "kernels/grouped_mlp_xla", us_sort,
        f"rows={M} model_mflops={M * 6 * d2 * f2 / 1e6:.1f} "
        f"measured_mflops={float((ca or {}).get('flops', 0.0)) / 1e6:.1f}",
    ))

    # Compacted block walk (dead blocks stream no x/weight tiles): model
    # the HBM byte savings at this bench's shapes under a skewed load —
    # half the assignments on expert 0, the rest spread — where the
    # ragged buffer carries real dead blocks. The walk is always-on in
    # the kernel; this row keeps its modeled savings visible next to
    # the measured timings (REPRO_BENCH_SMOKE switches the shapes, not
    # the code path).
    from repro.kernels.grouped_mlp import block_tables
    from repro.kernels.tiling import grouped_walk_fwd_bytes

    skew = [n_assign // 2] + [n_assign // (2 * (E2 - 1))] * (E2 - 1)
    counts_sk = jnp.asarray([skew], jnp.int32)
    nb_total = M // bm
    _, bl = block_tables(counts_sk, bm, nb_total)
    nb_live = int(bl.sum())
    b_compact = grouped_walk_fwd_bytes(
        nb_live, nb_total, bm, d2, f2, 3, compacted=True
    )
    b_static = grouped_walk_fwd_bytes(
        nb_live, nb_total, bm, d2, f2, 3, compacted=False
    )
    rows.append((
        "kernels/grouped_mlp_compact_walk", 0.0,
        f"live_blocks={nb_live} total_blocks={nb_total} "
        f"dead_blocks={nb_total - nb_live} "
        f"compact_walk_bytes={b_compact} static_walk_bytes={b_static} "
        f"bytes_saved_frac={1 - b_compact / b_static:.2f}",
    ))

    # grouped-GEMM fwd+bwd: XLA ragged_dot path and the Pallas custom-VJP
    # kernels in interpret mode (correctness-path timing only).
    def gm_loss(x, wi, wg, wo):
        return jnp.sum(ops.grouped_mlp(
            x, wi, wg, wo, counts, act="silu", block=bm) ** 2)

    gm_g = jax.jit(jax.value_and_grad(gm_loss, argnums=(0, 1, 2, 3)))
    us_gm = timed(gm_g, xs_r, wi2, wg2, wo2, n=reps)
    rows.append((
        "kernels/grouped_mlp_xla_fwd_bwd", us_gm,
        f"vs_fwd={us_gm / us_sort:.2f}x rows={M}",
    ))

    Ms = ragged_buffer_rows(32, 2, 8)
    cs_s = jnp.full((1, 2), 16, jnp.int32)
    xs_s = _ragged_rows(ks[3], Ms, 32, cs_s, 8)
    wis = jax.random.normal(ks[0], (2, 32, 64)) * 0.05
    wgs = jax.random.normal(ks[1], (2, 32, 64)) * 0.05
    wos = jax.random.normal(ks[2], (2, 64, 32)) * 0.05

    def gm_loss_p(x, wi, wg, wo):
        return jnp.sum(ops.grouped_mlp(
            x, wi, wg, wo, cs_s, act="silu", block=8,
            implementation="pallas") ** 2)

    gm_gp = jax.jit(jax.value_and_grad(gm_loss_p, argnums=(0, 1, 2, 3)))
    us_gmp = timed(gm_gp, xs_s, wis, wgs, wos, n=2)
    rows.append((
        "kernels/grouped_mlp_pallas_interpret_fwd_bwd", us_gmp,
        "custom_vjp_kernels=dx+dw scalar_prefetch=block_tables",
    ))

    # flash attention XLA chunked vs full-materialization reference
    B, S, H, Kh, dh = (1, 256, 4, 2, 32) if SMOKE else (2, 1024, 8, 2, 64)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Kh, dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Kh, dh), jnp.float32)
    chunk = 128 if SMOKE else 256
    ff = jax.jit(lambda q, k, v: ops.flash_attention(
        q, k, v, causal=True, q_chunk=chunk, kv_chunk=chunk))
    fr = jax.jit(lambda q, k, v: ops.flash_attention(
        q, k, v, causal=True, implementation="ref"))
    us_f = timed(ff, q, k, v, n=reps)
    us_r = timed(fr, q, k, v, n=reps)
    rows.append((
        "kernels/flash_attention_xla", us_f,
        f"vs_full_materialization={us_r / us_f:.2f}x",
    ))

    def attn_loss(q, k, v):
        return jnp.sum(ops.flash_attention(
            q, k, v, causal=True, q_chunk=chunk, kv_chunk=chunk) ** 2)

    fag = jax.jit(jax.value_and_grad(attn_loss, argnums=(0, 1, 2)))
    us_ag = timed(fag, q, k, v, n=reps)
    rows.append((
        "kernels/flash_attention_xla_fwd_bwd", us_ag,
        f"vs_fwd={us_ag / us_f:.2f}x",
    ))

    qs, ks_, vs = q[:, :64], k[:, :64], v[:, :64]

    def attn_loss_p(q, k, v):
        return jnp.sum(ops.flash_attention(
            q, k, v, causal=True, implementation="pallas") ** 2)

    fagp = jax.jit(jax.value_and_grad(attn_loss_p, argnums=(0, 1, 2)))
    us_agp = timed(fagp, qs, ks_, vs, n=2)
    rows.append((
        "kernels/flash_attention_pallas_interpret_fwd_bwd", us_agp,
        "custom_vjp_kernels=dq+dkv",
    ))

    # Chunked paged prefill vs the per-token decode walk: one C-token
    # chunk through ops.prefill_attention against C sequential
    # ops.decode_attention steps over the same block-table KV (the
    # serving mixed step's prefill lane vs prefilling through the
    # decode kernel). XLA-path wall time; the Pallas kernels run in
    # interpret mode for a correctness-path timing only — DMA-elision
    # and MXU-utilization numbers remain TPU-validation items.
    Bc, Cc, Hc, Khc, dhc = (1, 8, 4, 2, 16) if SMOKE else (1, 16, 8, 2, 32)
    bsp, nbp = 8, 8
    Pp = 1 + nbp
    ks = jax.random.split(key, 3)
    qc = jax.random.normal(ks[0], (Bc, Cc, Hc, dhc), jnp.float32)
    kpool = jax.random.normal(ks[1], (Pp, Khc, bsp, dhc), jnp.float32)
    vpool = jax.random.normal(ks[2], (Pp, Khc, bsp, dhc), jnp.float32)
    btp = jnp.arange(1, Pp, dtype=jnp.int32)[None, :]
    start0 = 3 * bsp  # chunk attends prior blocks + itself

    fp = jax.jit(lambda q: ops.prefill_attention(
        q, kpool, vpool, btp, jnp.asarray([start0]), jnp.asarray([Cc])))
    us_pf = timed(fp, qc, n=reps)

    fd = jax.jit(lambda q, ln: ops.decode_attention(
        q, kpool, vpool, btp, ln))

    def decode_walk(q):
        for i in range(Cc):
            fd(q[:, i:i + 1],
               jnp.asarray([start0 + i + 1])).block_until_ready()

    # Same warmup + median discipline as timed() so the two columns of
    # this row are comparable.
    import time as _time

    for _ in range(3):
        decode_walk(qc)
    ts = []
    for _ in range(reps):
        t0 = _time.perf_counter()
        decode_walk(qc)
        ts.append(_time.perf_counter() - t0)
    us_dw = float(np.median(ts)) * 1e6
    rows.append((
        "kernels/paged_prefill_chunk_vs_decode_walk", us_pf,
        f"chunk_us={us_pf:.0f} decode_walk_us={us_dw:.0f} "
        f"speedup={us_dw / us_pf:.2f}x chunk_len={Cc} "
        f"context_blocks={start0 // bsp}",
    ))

    fpp = jax.jit(lambda q: ops.prefill_attention(
        q, kpool, vpool, btp, jnp.asarray([start0]), jnp.asarray([Cc]),
        implementation="pallas"))
    us_ppf = timed(fpp, qc, n=2)
    rows.append((
        "kernels/paged_prefill_pallas_interpret", us_ppf,
        "q_tile_x_kv_block_walk=scalar_prefetch online_softmax=causal_abs",
    ))

    # rwkv6: chunked-parallel vs sequential scan
    B, T, Hh, K = (1, 128, 4, 32) if SMOKE else (1, 512, 8, 64)
    ks = jax.random.split(key, 5)
    r = jax.random.normal(ks[0], (B, T, Hh, K)) * 0.5
    kk = jax.random.normal(ks[1], (B, T, Hh, K)) * 0.5
    vv = jax.random.normal(ks[2], (B, T, Hh, K)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, Hh, K))) * 0.5 + 0.4
    u = jax.random.normal(ks[4], (Hh, K)) * 0.3
    fc = jax.jit(lambda *a: ops.rwkv6(*a, chunk=64)[0])
    fs = jax.jit(lambda *a: ops.rwkv6(*a, implementation="ref")[0])
    us_c = timed(fc, r, kk, vv, w, u, n=2 if SMOKE else 5)
    us_s = timed(fs, r, kk, vv, w, u, n=2 if SMOKE else 5)
    rows.append((
        "kernels/rwkv6_chunked_xla", us_c,
        f"vs_sequential_scan={us_s / us_c:.2f}x",
    ))
    return rows
