"""Roofline report: aggregates the dry-run artifacts
(artifacts/dryrun/*.json, produced by ``python -m repro.launch.dryrun``)
into the per-(arch x shape x mesh) three-term table of EXPERIMENTS.md
§Roofline.

Terms are seconds per chip on TPU v5e (197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s ICI link); dominant term = the bottleneck the perf loop attacks.

Also emits a static per-kernel fwd/bwd roofline for the two Pallas
training kernels (grouped expert FFN, flash attention): now that the backward
pass is kernel-fused (custom VJP), the training step pays the backward
FLOP terms through the same VMEM-resident kernels, so both directions
are modeled.
"""
from __future__ import annotations

import glob
import json
import os

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")

PEAK_FLOPS_BF16 = 197e12  # TPU v5e
HBM_BW = 819e9


def _roofline_row(name, flops, bytes_):
    t_c = flops / PEAK_FLOPS_BF16
    t_m = bytes_ / HBM_BW
    t = max(t_c, t_m)
    return (
        name,
        t * 1e6,
        f"flops={flops:.3e} bytes={bytes_:.3e} "
        f"ai={flops / bytes_:.0f} "
        f"bound={'compute' if t_c >= t_m else 'memory'}",
    )


def kernel_rooflines() -> list[tuple[str, float, str]]:
    """Fwd/bwd FLOP + HBM-byte model at a reference training shape.

    Grouped expert FFN (gated), per row: fwd = 3 matmuls (wi, wg, wo) =
    6*d*f FLOPs. Bwd = dx kernel (recompute a/g/dh: 3 matmuls, expand
    da/dg -> dx: 2) + dW kernel (recompute a/g/dh: 3, dwi/dwg/dwo: 3) =
    16*d*f — ~2.7x fwd (the flash-style recompute tax for keeping the
    (bm, f) tensors in VMEM; residuals are the kernel inputs only).

    Flash attention, per (b, h): fwd = qk^T + pv = 4*Sq*Skv*dh. Bwd =
    dq kernel (s, dp, dq: 6*Sq*Skv*dh) + dkv kernel (s, dp, dk, dv:
    8*Sq*Skv*dh) = 3.5x fwd.
    """
    rows = []
    # Reference shapes: an 8-expert 1B-class MoE layer and a 4k-context
    # attention layer, bf16 tensors (2 bytes). A padded capacity buffer
    # for comparison is tiled by bc = 128 rows; the grouped kernel's
    # backward re-streams full-d x/dy rows once per bf = 256 f tile.
    E, d, f = 8, 2048, 5632
    bc, bf = 128, 256
    # Grouped-GEMM (sorted ragged dispatch) vs the padded capacity
    # buffer, same E/d/f, one routing group of g = 4096 tokens, top-2,
    # under a skewed expert load (top expert draws ~30% of assignments —
    # the upcycled-MoE imbalance regime the capacity factor exists to
    # absorb). Padded FLOPs/bytes follow E*cap = cf*g rows; ragged live
    # FLOPs follow the FILLED (block-aligned) rows only — independent of
    # cf once every expert saturates. With the COMPACTED block walk
    # (kernels/grouped_mlp.py prev_live pinning) dead blocks stream no
    # x/weight tiles either, so bytes are ragged like FLOPs:
    # bytes_ratio = ragged_compacted / padded, < 1.0 means the ragged
    # path reads strictly fewer HBM bytes than the capacity buffer.
    from repro.kernels.tiling import grouped_walk_fwd_bytes

    g_tok, k, bm = 4096, 2, 128
    fracs = [0.30, 0.20, 0.15, 0.10, 0.08, 0.07, 0.06, 0.04]  # E = 8
    M = (-(-g_tok * k // bm) + E) * bm
    nb_total = M // bm

    def live_blocks_of(cf):
        cap_cf = -(-int(g_tok * cf) // E)
        counts = [min(int(fr * k * g_tok), cap_cf) for fr in fracs]
        return cap_cf, counts, sum(-(-c // bm) for c in counts)

    for cf in (1.0, 1.25, 2.0):
        cap_cf, counts, nb_live = live_blocks_of(cf)
        live = nb_live * bm
        pad_rows = E * cap_cf
        pad_flops = 6 * pad_rows * d * f
        rag_flops = 6 * live * d * f
        pad_bytes = -(-cap_cf // bc) * E * 3 * d * f * 2 \
            + 2 * pad_rows * d * 2
        rag_bytes = grouped_walk_fwd_bytes(
            nb_live, nb_total, bm, d, f, 3, compacted=True
        )
        rag_bytes_static = grouped_walk_fwd_bytes(
            nb_live, nb_total, bm, d, f, 3, compacted=False
        )
        rows.append((
            f"roofline/kernel.grouped_mlp.cf{cf}",
            0.0,
            f"padded_rows={pad_rows} ragged_live_rows={live} "
            f"flops_ratio_padded_over_ragged={pad_flops / rag_flops:.2f} "
            f"bytes_ratio={rag_bytes / pad_bytes:.2f} "
            f"bytes_ratio_static_walk={rag_bytes_static / pad_bytes:.2f} "
            f"ragged_static_rows={M} (cf-independent)",
        ))
    # fwd/bwd rooflines for the grouped kernel at the cf=2.0 point
    # (6x fwd, 16x bwd recompute tax per row),
    # bytes follow the compacted walk (live blocks only stream tiles).
    _, _, nb_live2 = live_blocks_of(2.0)
    live2 = nb_live2 * bm
    rag_w_bytes = nb_live2 * 3 * d * f * 2
    rag_x_bytes = nb_live2 * bm * d * 2
    rows.append(_roofline_row(
        "roofline/kernel.grouped_mlp.fwd", 6 * live2 * d * f,
        grouped_walk_fwd_bytes(nb_live2, nb_total, bm, d, f, 3,
                               compacted=True),
    ))
    nf = f // bf
    rows.append(_roofline_row(
        # The dx kernel re-streams full-d x/dy rows once per f tile in each of its two
        # phases, the dW kernel once more (3*nf*2 x-passes total); weight
        # tiles stream per LIVE row-block twice in dx, once in dW
        # (3*rag_w_bytes); writes = dx (buffer-sized) + dW (weight-sized).
        "roofline/kernel.grouped_mlp.bwd", 16 * live2 * d * f,
        3 * rag_w_bytes + 3 * nf * 2 * rag_x_bytes
        + M * d * 2 + E * 3 * d * f * 2,
    ))
    # Paged flash-decode (kernels/decode_attention.py) at a serving
    # shape: 8 slots, GQA 16 query / 2 kv heads, dh=128, 16-token KV
    # blocks, ragged lengths in a max_len=4096 engine. Decode is
    # HBM-bound with arithmetic intensity == the GQA ratio G (every kv
    # byte feeds G query heads); what the paged walk buys is the BYTES
    # term scaling with each slot's live blocks instead of max_len —
    # the bytes_ratio row is the whole point.
    from repro.kernels.tiling import (
        decode_attention_flops,
        paged_decode_fwd_bytes,
    )

    Bd, Hd, Khd, dhd, bsd, mxd = 8, 16, 2, 128, 16, 4096
    lens = [256, 512, 1024, 1536, 2048, 2560, 3072, 3840]
    dec_fl = decode_attention_flops(lens, Hd, dhd)
    dec_by = paged_decode_fwd_bytes(lens, bsd, Khd, dhd, n_heads=Hd)
    rows.append(_roofline_row(
        "roofline/kernel.decode_attention.fwd", dec_fl, dec_by
    ))
    dense_by = paged_decode_fwd_bytes(
        [mxd] * Bd, bsd, Khd, dhd, n_heads=Hd
    )
    rows.append((
        "roofline/kernel.decode_attention.paged_vs_dense",
        0.0,
        f"paged_bytes={dec_by:.3e} dense_maxlen_bytes={dense_by:.3e} "
        f"bytes_ratio={dec_by / dense_by:.2f} "
        f"mean_len={sum(lens) // len(lens)} max_len={mxd} "
        "(paged reads track live blocks; dense pays max_len per slot)",
    ))
    # bf16 pools halve the kv byte term (the tentpole's bf16 cache
    # reads); f32 shown for the parity-test configuration.
    dec_by_f32 = paged_decode_fwd_bytes(
        lens, bsd, Khd, dhd, n_heads=Hd, itemsize=4
    )
    rows.append((
        "roofline/kernel.decode_attention.cache_dtype",
        0.0,
        f"bf16_bytes={dec_by:.3e} f32_bytes={dec_by_f32:.3e} "
        f"ratio={dec_by / dec_by_f32:.2f}",
    ))
    # Paged prefill-attention (kernels/paged_prefill.py) at a serving
    # shape: one 256-token chunk of a request with 1024 tokens already
    # cached (prefix blocks + earlier chunks), GQA 16/2, dh=128, bs=16.
    # Two claims: (1) the q-tile x kv-block walk amortizes the table
    # walk — decoding the same 256 tokens one step at a time would
    # re-stream each token's whole live prefix (the chunked_vs_decode
    # bytes ratio); (2) arithmetic intensity scales with the q tile
    # (bq*G rows per kv byte), so chunks run MXU-bound where decode is
    # HBM-bound. Dead-step fetch elision (blocks past a q tile's causal
    # limit) is modeled here and in tiling.paged_prefill_fwd_bytes;
    # measuring the elided DMAs needs real hardware — a TPU-validation
    # item, like the decode kernel's.
    from repro.kernels.tiling import (
        paged_prefill_flops,
        paged_prefill_fwd_bytes,
    )

    cstart, clen, cbq = 1024, 256, 128
    pf_fl = paged_prefill_flops(cstart, clen, Hd, dhd)
    pf_by = paged_prefill_fwd_bytes(
        cstart, clen, cbq, bsd, Khd, dhd, n_heads=Hd
    )
    rows.append(_roofline_row(
        "roofline/kernel.paged_prefill.fwd", pf_fl, pf_by
    ))
    dec_walk_by = sum(
        paged_decode_fwd_bytes([cstart + i + 1], bsd, Khd, dhd,
                               n_heads=Hd)
        for i in range(clen)
    )
    rows.append((
        "roofline/kernel.paged_prefill.chunked_vs_decode",
        0.0,
        f"chunk_bytes={pf_by:.3e} per_token_decode_bytes="
        f"{dec_walk_by:.3e} bytes_ratio={pf_by / dec_walk_by:.2f} "
        f"chunk_len={clen} context={cstart} q_tile={cbq} "
        "(prefilling via the decode walk re-streams the whole live "
        "prefix per token; the chunk kernel pays it once per q tile)",
    ))
    B, H, Sq, dh = 8, 16, 4096, 128
    bq = 512  # flash_attention.py default
    nq = Sq // bq
    att_fwd = B * H * 4 * Sq * Sq * dh
    att_bwd = B * H * 14 * Sq * Sq * dh
    row_bytes = B * H * Sq * dh * 2  # one of q/k/v/o/do per head
    rows.append(_roofline_row(
        # fwd: k+v streamed per q tile, q read + o written once.
        "roofline/kernel.flash_attention.fwd", att_fwd,
        nq * 2 * row_bytes + 2 * row_bytes,
    ))
    rows.append(_roofline_row(
        # dq kernel: k+v per q tile, q/do/dq once; dkv kernel: q+do per
        # kv tile, k/v/dk/dv once; lse + delta are O(S) and ignored.
        "roofline/kernel.flash_attention.bwd", att_bwd,
        2 * nq * 2 * row_bytes + 7 * row_bytes,
    ))
    return rows


def moe_comm_rows() -> list[tuple[str, float, str]]:
    """Comm-volume model for the MoE layer's two layouts (core/moe.py
    layout table), per device per MoE layer, bf16:

    * expert-parallel a2a (``moe.ep="a2a"``): tokens move — 2 exchanges
      (dispatch + return) of ``tokens_dev * k`` rows of d features, of
      which fraction (ep-1)/ep crosses links;
    * FSDP weight-gather (``ep="none"``): weights move — each device
      gathers the (1 - 1/ep) of the 3*E*d*f expert weights it does not
      hold on the same axis.

    The (ep-1)/ep crossing fractions cancel, so the crossover is
    ``tokens_dev* = 3 * E * f / (2 * k)`` — independent of d and ep:
    below it tokens are cheaper to move (a2a wins), above it weights
    are. Reported per (E, ep, tokens_dev) config with the a2a's ICI
    time as the value column.
    """
    from repro.launch.mesh import ICI_BW

    d, f, k = 2048, 5632, 2  # reference 1B-class MoE layer, top-2
    rows = []
    for E, ep, tokens_dev in [
        (8, 8, 4096),
        (8, 8, 65536),
        (64, 16, 8192),
        (64, 16, 1 << 19),
    ]:
        frac = (ep - 1) / ep
        a2a = 2 * tokens_dev * k * d * 2 * frac
        gather = 3 * E * d * f * 2 * frac
        crossover = 3 * E * f // (2 * k)
        winner = "a2a" if a2a < gather else "weight_gather"
        rows.append((
            f"roofline/comm.moe.E{E}.ep{ep}.tok{tokens_dev}",
            a2a / ICI_BW * 1e6,
            f"a2a_bytes={a2a:.3e} weight_gather_bytes={gather:.3e} "
            f"crossover_tokens_dev={crossover} winner={winner}",
        ))
    return rows


def speculative_rows() -> list[tuple[str, float, str]]:
    """Draft-FLOPs vs verify-bytes model of the speculative serving
    tick (serve/speculative.py), per decode tick, bf16.

    Decode is memory-bound: a target step streams the weight set W_t
    once however many rows ride it, so the (k+1)-position verify pass
    costs ~one decode step of HBM time — its FLOPs grow with k+1 but
    stay far under the ridge (the kernel.speculative.verify rows make
    that explicit). A speculative tick is (k+1) draft steps + 1 verify
    = ``1 + (k+1) * r`` step units, ``r = W_draft / W_target``, and
    emits ``E[a] = (1 - a^(k+1)) / (1 - a)`` tokens at per-token
    acceptance a; speedup = E[a] / cost. Break-even is the a* with
    E[a*] = cost — below it, drafting LOSES time. The dense parent of
    the comm.moe.* reference layer has r ~= 1/E on the FFN (it reads
    one expert's weights where the MoE streams all E under batching);
    a top1 draft still streams ~every expert (r ~= 1), which is why it
    treads water in serve_bench's speculative scenario unless routing
    locality is measured to be high.
    """
    d, f, E = 2048, 5632, 8  # the comm.moe.* reference MoE layer
    attn = 4 * d * d  # q/k/v/o projections
    w_target = 3 * E * d * f + attn + E * d  # experts + attn + router
    w_draft = 3 * d * f + attn  # dense parent: one expert's MLP
    r = w_draft / w_target
    rows = []
    for k in (2, 4, 8):
        # the verify pass itself: batch of one slot, k+1 positions
        rows.append(_roofline_row(
            f"roofline/kernel.speculative.verify.k{k}",
            2 * w_target * (k + 1),
            w_target * 2,
        ))
        cost = 1 + (k + 1) * r  # tick cost in target-step units

        def exp_tokens(a, k=k):
            return (k + 1) if a >= 1.0 else (1 - a ** (k + 1)) / (1 - a)

        lo, hi = 0.0, 1.0  # E[a] is monotone: bisect E[a*] = cost
        for _ in range(60):
            mid = (lo + hi) / 2
            lo, hi = (mid, hi) if exp_tokens(mid) < cost else (lo, mid)
        a_star = (lo + hi) / 2
        grid = " ".join(
            f"a={a}:{exp_tokens(a) / cost:.2f}x"
            for a in (0.5, 0.7, 0.9)
        )
        rows.append((
            f"roofline/comm.speculative.k{k}",
            cost * w_target * 2 / HBM_BW * 1e6,  # tick HBM time
            f"draft_ratio={r:.3f} tick_cost={cost:.2f}steps "
            f"speedup[{grid}] breakeven_acceptance={a_star:.2f}",
        ))
    return rows


def load(pattern: str = "*") -> list[dict]:
    out = []
    for f in sorted(glob.glob(os.path.join(ART, f"{pattern}.json"))):
        with open(f) as fh:
            out.append(json.load(fh))
    return out


def run() -> list[tuple[str, float, str]]:
    rows = []
    for d in load():
        name = (
            f"roofline/{d['arch']}.{d['shape']}.{d['mesh']}.{d['profile']}"
        )
        if d.get("tag"):
            name += f".{d['tag']}"
        if d.get("status") == "skipped":
            rows.append((name, 0.0, f"SKIPPED: {d['reason']}"))
            continue
        r = d["roofline"]
        bound = r["step_time_lower_bound_s"]
        frac = r["compute_s"] / bound if bound else 0.0
        rows.append((
            name,
            bound * 1e6,  # us per step lower bound
            f"dom={r['dominant']} compute={r['compute_s']:.4f}s "
            f"mem={r['memory_s']:.4f}s coll={r['collective_s']:.4f}s "
            f"roofline_frac={frac:.3f} "
            f"mf_ratio={d['useful_flops_ratio']:.2f} "
            f"fits16G={d['memory']['fits_16g']}",
        ))
    if not rows:
        rows.append((
            "roofline/none", 0.0,
            "no artifacts — run: PYTHONPATH=src python -m "
            "repro.launch.dryrun --all",
        ))
    rows.extend(kernel_rooflines())
    rows.extend(moe_comm_rows())
    rows.extend(speculative_rows())
    return rows
