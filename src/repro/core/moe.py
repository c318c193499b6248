"""The MoE layer: router + dispatch + expert FFN + combine.

Three dispatch implementations (N = tokens/group g, A = assignments per
token — top-k's k; rows below are per group, f = d_ff):

  ========  ==================  =======================  =================
  dispatch  FFN rows processed  extra FLOPs vs dense     when to use
  ========  ==================  =======================  =================
  einsum    E*cap = C*g         one-hot dispatch AND     paper-faithful
            (scales with C)     combine matmuls:         baseline, tiny
                                O(g*E*cap*d) each        shapes, audits
  gather    E*cap = C*g         none (gather/scatter     padded default:
            (scales with C)     indexing only), but      expert-parallel
                                zero-pad FFN FLOPs on    a2a sharding via
                                unfilled slots           (G,E,cap,d) buf
  sorted    g*A + O(E*bm)       none; FFN FLOPs track    perf path: C > 1
            (independent of     *filled* rows only       or imbalanced
            capacity factor C)  (ragged grouped GEMM)    Top-K; finetune/
                                                         inference economy
  ========  ==================  =======================  =================

``einsum``/``gather`` build the padded ``(G, E, cap, d)`` capacity buffer
and go through ``kernels.ops.expert_ffn``; ``sorted`` sorts the flat
assignment stream by expert into a block-aligned ragged buffer
``(G, M, d)`` (M independent of capacity factor) and goes through
``kernels.ops.grouped_mlp`` — the scalar-prefetch Pallas grouped-GEMM
kernel on TPU, per-group ``lax.ragged_dot`` on XLA. All three consume the
same ``Routing`` decisions, so outputs/gradients agree to float tolerance
(tests/test_moe.py parity sweeps).

Sharding: the padded paths constrain dispatched buffers (G, E, cap, d) to
``_ expert cap embed`` — with experts on the ``model`` mesh axis this makes
GSPMD insert the all-to-alls of the paper's "expert partitioning"
(§A.4). When E doesn't divide the axis (grok), the constraint degrades to
replicated-expert + tensor-parallel d_ff via the rules engine. The sorted
path has two layouts, selected by ``moe.ep``:

  ==========  ===========  ================  ==========================
  layout      who moves    drops happen      sharding constraints
  ==========  ===========  ================  ==========================
  ep="none"   weights      router capacity   ragged buffer batch-
  (FSDP       (E weight    only (keep        sharded (``batch seq
  weight-     gathers to   masks, shared     embed``; dynamic expert
  gather)     the data     by all paths)     boundaries forbid an
              shards)                        expert axis); weights
                                             expert-resident when E
                                             divides ``model``, else
                                             d_ff tensor-parallel
  ep="a2a"    tokens (2    capacity PLUS     shard_map: token groups
  (expert-    ragged a2a   send-buffer       over every mesh axis,
  parallel,   exchanges    overflow past     weights over ``model``
  core/ep.py) over the     the static per-   (E/ep local experts per
              ``model``    peer row budget,  device); send/recv a2a
              axis)        ``ep_overflow_    buffers block-aligned,
                           frac`` metric     static (ep, budget, d)
  ==========  ===========  ================  ==========================

``ep="none"`` is the "Llama 3 Meets MoE" upcycling layout — weight
traffic scales with E; ``ep="a2a"`` trades it for token traffic that
scales with tokens/device (the GShard regime, where the capacity buffer
used to live) — see benchmarks/roofline.py ``comm.moe`` for the
crossover. Falls back to ``ep="none"`` when the mesh cannot host EP
(no ``model`` axis, size 1, or E % ep != 0 — the rules-engine
fallback discipline).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig, MoECfg
from repro.core import routing as R
from repro.models import param as pm
from repro.models.layers import activation
from repro.sharding import ShardCtx, act


def moe_init(rng, cfg: ArchConfig, moe: MoECfg, *, dtype=jnp.float32):
    d, f, E = cfg.d_model, cfg.d_ff, moe.num_experts
    ks = jax.random.split(rng, 4)
    experts = {
        "wi": pm.dense(ks[0], (E, d, f), "expert embed mlp", dtype=dtype),
        "wo": pm.dense(
            ks[2], (E, f, d), "expert mlp embed", dtype=dtype, fan_in=f
        ),
    }
    if cfg.gated_mlp:
        experts["wg"] = pm.dense(
            ks[1], (E, d, f), "expert embed mlp", dtype=dtype
        )
    return {
        "router": R.router_init(ks[3], d, moe),
        "experts": experts,
    }


def _compute_layout_weights(experts, ctx: Optional[ShardCtx]):
    """Constrain expert weights to their COMPUTE layout: expert-resident
    ("expert _ _", one FSDP-style gather per layer) when E divides the
    `model` axis, else d_ff tensor-parallel. Without this GSPMD sometimes
    prefers replicating the token buffers over gathering the weights —
    ~4x more bytes at Jamba scale (EXPERIMENTS.md SPerf, jamba iteration 3).
    Shared by the padded (expert_ffn) and sorted (grouped_mlp) paths."""
    wi, wg, wo = experts["wi"], experts.get("wg"), experts["wo"]
    if ctx is not None:
        E = wi.shape[0]
        model = dict(ctx.mesh.shape).get("model", 1)
        if E % model == 0:
            wi = act(ctx, wi, "expert _ _")
            wo = act(ctx, wo, "expert _ _")
            wg = act(ctx, wg, "expert _ _") if wg is not None else None
        else:
            wi = act(ctx, wi, "_ _ mlp")
            wo = act(ctx, wo, "_ mlp _")
            wg = act(ctx, wg, "_ _ mlp") if wg is not None else None
    return wi, wg, wo


def expert_ffn(experts, xe, cfg: ArchConfig, *, implementation="xla",
               ctx: Optional[ShardCtx] = None):
    """xe: (G, E, cap, d) -> (G, E, cap, d). Dispatches to kernels.ops."""
    from repro.kernels import ops

    with jax.named_scope("moe.experts"):
        wi, wg, wo = _compute_layout_weights(experts, ctx)
        return ops.expert_ffn(
            xe, wi, wg, wo,
            act=cfg.act,
            implementation=implementation,
        )


def _sorted_dispatch(params, xg, r, cfg: ArchConfig, moe: MoECfg, *,
                     ctx: Optional[ShardCtx], implementation: str,
                     block: int):
    """Sorted ragged dispatch: argsort the flat assignment stream by
    expert, run the contiguous ragged buffer through the grouped-GEMM
    kernel, unsort via scatter-add combine. Returns y (G, g, d).

    The ragged buffer has ``M = (ceil(N/block) + E) * block`` rows — N is
    the assignment count (g*k for token-choice), so FFN work is
    independent of capacity factor; capacity only decides WHICH
    assignments survive (the routers' keep masks, identical across
    dispatch paths).
    """
    from repro.kernels import ops
    from repro.kernels.grouped_mlp import ragged_destinations

    G, g, d = xg.shape
    E = moe.num_experts

    with jax.named_scope("moe.dispatch"):
        # Flat per-group assignment stream (token id, expert id, weight)
        # — shared with the expert-parallel path (core/ep.py).
        tok, eid, w = R.assignment_stream(r, E, g)
        N = tok.shape[1]
        valid = (eid < E) & (tok < g)
        key = jnp.where(valid, eid, E).astype(jnp.int32)

        # Stable sort by expert (dropped assignments -> key E, past the
        # last segment) and block-aligned ragged destinations — the
        # layout math shared with core/ep.py via kernels/grouped_mlp.py.
        # Only the integer permutation goes through lax.sort; the
        # differentiable weights follow via take_along_axis, so no
        # gradient flows through the sort itself.
        perm, key_s, counts, dest, M = ragged_destinations(key, E, block)
        tok_s = jnp.take_along_axis(tok, perm, axis=1)
        w_s = jnp.take_along_axis(w, perm, axis=1)
        valid_s = key_s < E

        # Ragged buffers: src maps ragged row -> group-local token (g =
        # pad row), wr carries the combine weight (0 on pad rows). Row M
        # is the trash row for dropped assignments.
        gi = jnp.broadcast_to(jnp.arange(G)[:, None], (G, N))
        src = (jnp.full((G, M + 1), g, jnp.int32)
               .at[gi, dest].set(tok_s)[:, :M])
        wr = (
            jnp.zeros((G, M + 1), w_s.dtype)
            .at[gi, dest].set(jnp.where(valid_s, w_s, 0.0))[:, :M]
        )

        gm = jnp.broadcast_to(jnp.arange(G)[:, None], (G, M))
        pad_row = src >= g
        xs = xg[gm, jnp.minimum(src, g - 1)]
        xs = xs * (1.0 - pad_row[..., None].astype(xg.dtype))
        # Ragged rows stay batch-sharded: expert boundaries are dynamic,
        # so the expert dim cannot be a sharding axis here (see module
        # docstring).
        xs = act(ctx, xs, "batch seq embed")
    with jax.named_scope("moe.experts"):
        wi, wg, wo = _compute_layout_weights(params["experts"], ctx)
        ys = ops.grouped_mlp(
            xs, wi, wg, wo, counts,
            act=cfg.act, block=block, implementation=implementation,
        )
    with jax.named_scope("moe.combine"):
        # Weight, unsort, scatter-add (duplicate token rows — one per
        # surviving assignment — accumulate, exactly like the gather
        # path).
        ys = act(ctx, ys, "batch seq mlp")
        yw = (ys * wr[..., None]).astype(xg.dtype)
        y = jnp.zeros((G, g + 1, d), xg.dtype)
        y = act(ctx, y, "batch seq mlp")
        y = y.at[gm, src].add(yw)
        y = act(ctx, y, "batch seq mlp")
        return y[:, :g]


def _group(x2d: jax.Array, group_size: int):
    n, d = x2d.shape
    g = min(group_size, n)
    pad = (-n) % g
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    return x2d.reshape(-1, g, d), n, pad


def moe_apply(
    params,
    x: jax.Array,
    cfg: ArchConfig,
    moe: MoECfg,
    *,
    router_kind: Optional[str] = None,
    dispatch: str = "gather",
    ctx: Optional[ShardCtx] = None,
    implementation: str = "xla",
    sorted_block: int = 128,
    token_mask=None,
):
    """x: (B, S, d) or (N, d). Returns (y, metrics dict).

    ``dispatch``: "einsum" | "gather" (padded capacity buffer) | "sorted"
    (ragged grouped GEMM; ``sorted_block`` is the row-block alignment of
    the ragged buffer — 128 matches the TPU kernel's MXU tiles, tests use
    smaller blocks to keep interpret-mode buffers tiny).

    ``token_mask``: None, or a bool array broadcastable to x's token dims
    (B, S) — False marks dead tokens (the continuous-batching engine's
    free decode slots): they claim no experts, no capacity, and no ragged
    grouped-GEMM rows, so expert compute scales with LIVE tokens rather
    than the static decode batch. Dead tokens' outputs are zero
    (residual passthrough); live tokens are bit-identical to an unmasked
    call with the same group composition.
    """
    router_kind = router_kind or moe.router
    ep_overflow = jnp.zeros((), jnp.float32)
    orig_shape = x.shape
    x2d = x.reshape(-1, x.shape[-1])
    xg, n, pad = _group(x2d, moe.group_size)
    G, g, d = xg.shape

    with jax.named_scope("moe.route"):
        mg = None
        if token_mask is not None:
            m1 = jnp.broadcast_to(
                token_mask, orig_shape[:-1]
            ).reshape(-1).astype(bool)
            if pad:
                m1 = jnp.pad(m1, (0, pad))
            mg = m1.reshape(G, g)

        logits = jnp.einsum(
            "Ggd,de->Gge", xg, params["router"]["w"],
            preferred_element_type=jnp.float32,
        )
        r = R.route(logits, moe, router_kind, token_mask=mg)

    if dispatch == "einsum":
        # One-hot dispatch/combine (GShard-era faithful path).
        with jax.named_scope("moe.dispatch"):
            oh = jax.nn.one_hot(r.token_idx, g + 1, dtype=xg.dtype)[..., :g]
            # (G, E, cap, g) x (G, g, d) -> (G, E, cap, d)
            xe = jnp.einsum("Gect,Gtd->Gecd", oh, xg)
            xe = act(ctx, xe, "batch expert cap embed")
        ye = expert_ffn(params["experts"], xe, cfg,
                        implementation=implementation, ctx=ctx)
        with jax.named_scope("moe.combine"):
            ye = act(ctx, ye, "batch expert cap embed")
            comb = oh * r.combine[..., None].astype(xg.dtype)
            y = jnp.einsum("Gect,Gecd->Gtd", comb, ye)
    elif dispatch == "gather":
        with jax.named_scope("moe.dispatch"):
            safe_idx = jnp.minimum(r.token_idx, g - 1)
            gi = jnp.broadcast_to(
                jnp.arange(G)[:, None, None], r.token_idx.shape
            )
            xe = xg[gi, safe_idx]  # (G, E, cap, d)
            valid = (r.token_idx < g)[..., None].astype(xg.dtype)
            xe = xe * valid
            xe = act(ctx, xe, "batch expert cap embed")
        ye = expert_ffn(params["experts"], xe, cfg,
                        implementation=implementation, ctx=ctx)
        with jax.named_scope("moe.combine"):
            # Resharding ye from expert-sharded to hidden-sharded BEFORE
            # the scatter makes GSPMD emit a (tokens*k*d/E)-sized
            # all-to-all and a shard-local scatter, instead of
            # partial-summing the full (G, g, d) token buffer with an
            # all-reduce per layer (~E/k * 2 more bytes; see
            # EXPERIMENTS.md SPerf jamba iteration).
            ye = act(ctx, ye, "batch _ cap mlp")
            w = (r.combine[..., None] * valid).astype(ye.dtype)
            yw = (ye * w).astype(xg.dtype)
            y = jnp.zeros((G, g + 1, d), xg.dtype)
            y = act(ctx, y, "batch seq mlp")
            y = y.at[gi, r.token_idx].add(yw)
            y = act(ctx, y, "batch seq mlp")
            y = y[:, :g]
    elif dispatch == "sorted":
        from repro.sharding.logical import expert_parallel_layout

        ep_layout = (
            expert_parallel_layout(ctx.mesh, moe.num_experts)
            if (moe.ep == "a2a" and ctx is not None) else None
        )
        if ep_layout is not None:
            from repro.core.ep import sorted_dispatch_ep

            # The expert FFN and the combine name their own scopes inside.
            with jax.named_scope("moe.dispatch"):
                y, ep_overflow = sorted_dispatch_ep(
                    params, xg, r, cfg, moe,
                    ctx=ctx, implementation=implementation,
                    block=sorted_block,
                )
        else:
            # ep="a2a" on an EP-incapable mesh (or no ctx) falls back to
            # the batch-sharded weight-gather layout — same results.
            y = _sorted_dispatch(
                params, xg, r, cfg, moe,
                ctx=ctx, implementation=implementation,
                block=sorted_block,
            )
    else:
        raise ValueError(f"unknown dispatch {dispatch!r}")

    with jax.named_scope("moe.combine"):
        y = y.reshape(-1, d)
        if pad:
            y = y[:n]
        y = y.reshape(orig_shape).astype(x.dtype)
    # Remat boundary tag: with stack_apply(remat="moe") only this combined
    # output is saved for the backward; the dispatched (G, E, cap, d)
    # buffers and router tensors above are recomputed.
    from jax.ad_checkpoint import checkpoint_name

    y = checkpoint_name(y, "moe_block")

    with jax.named_scope("moe.route"):
        metrics = {
            "aux_loss": r.aux_loss * moe.aux_loss_weight,
            "z_loss": r.z_loss * moe.z_loss_weight,
            "dropped_frac": r.dropped_frac,
            "router_prob_mean_max": r.probs.max(-1).mean(),
            # Assignments dropped by the expert-parallel a2a send-buffer
            # budget (0 outside the EP path and whenever the budget
            # holds).
            "ep_overflow_frac": ep_overflow,
        }
    return y, metrics
