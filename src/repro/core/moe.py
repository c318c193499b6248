"""The MoE layer: router + sorted ragged dispatch + expert FFN + combine.

One dispatch: the flat assignment stream (``routing.assignment_stream``)
is sorted by expert into a block-aligned ragged buffer ``(G, M, d)`` —
``M = (ceil(N/block) + E) * block`` rows for N assignments a group, so
FFN work tracks the *filled* rows and is independent of capacity factor,
which only decides which assignments survive (the routers' keep masks)
— and goes through ``kernels.ops.grouped_mlp``: the scalar-prefetch
Pallas grouped-GEMM kernel on TPU, per-group ``lax.ragged_dot`` on XLA.

How rows move: the sort is a bijection between the surviving
assignments and the live ragged rows, so for the token-choice routers
(k slots per token) each side looks the other up by gather —
``kernels.grouped_mlp.ragged_sources`` gives each row its assignment and
combine weight, and each assignment its row. Dispatch gathers token rows
into the buffer, and its transpose gathers each token's k row cotangents
and sums them; the combine gathers each token's k weighted expert rows
and sums them, and its transposes gather the output cotangent back onto
the rows and read the weight gradient per row. No scatter is left, in
either direction (on TPU XLA runs a scatter as a serial loop over its
updates). Expert Choice, whose tokens appear a variable number of times,
keeps scatter-built tables and a scatter-add combine.

Sharding: two layouts, selected by ``moe.ep``:

  ==========  ===========  ================  ==========================
  layout      who moves    drops happen      sharding constraints
  ==========  ===========  ================  ==========================
  ep="none"   weights      router capacity   ragged buffer batch-
  (FSDP       (E weight    only (keep        sharded (``batch seq
  weight-     gathers to   masks)            embed``; dynamic expert
  gather)     the data                       boundaries forbid an
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
scales with tokens/device (the GShard regime) — see
benchmarks/roofline.py ``comm.moe`` for the crossover. Falls back to
``ep="none"`` when the mesh cannot host EP (no ``model`` axis, size 1,
or E % ep != 0 — the rules-engine fallback discipline).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig, MoECfg
from repro.core import routing as R
from repro.models import param as pm
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
    Called by the ``ep="none"`` layout before ``grouped_mlp``."""
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


def _rows(x, idx):
    """Per-group row gather: x (G, R, d), idx (G, ...) -> (G, ..., d)."""
    gi = jnp.arange(x.shape[0]).reshape((-1,) + (1,) * (idx.ndim - 1))
    return x[gi, idx]


def _zero_row(x):
    """x (G, R, ...) with a zero row R appended: (G, R + 1, ...). Index
    R then gathers zeros, with no select pass over the gathered rows."""
    return jnp.concatenate([x, jnp.zeros_like(x[:, :1])], axis=1)


def _slot_rows(x, row_of):
    """Each token's k ragged rows: x (G, M, d), row_of (G, g, k) ->
    ((G, g, k, d) with dropped slots' rows clamped, (G, g, k) bool live)."""
    M = x.shape[1]
    return _rows(x, jnp.minimum(row_of, M - 1)), row_of < M


@jax.custom_vjp
def _dispatch_rows(xg, slot, row_of):
    """xs[m] = xg[slot[m] // k] — ragged row m's token — and 0 on pad
    rows (slot == N = g*k, i.e. token g: the appended zero row). The
    transpose walks the sort's bijection the other way: dxg[t] =
    sum_j dxs[row_of[t, j]], a gather and a sum over the token's k
    slots instead of a scatter-add."""
    return _rows(_zero_row(xg), slot // row_of.shape[-1])


def _dispatch_rows_fwd(xg, slot, row_of):
    return _dispatch_rows(xg, slot, row_of), (slot, row_of)


def _dispatch_rows_bwd(res, dxs):
    slot, row_of = res
    rows, live = _slot_rows(dxs, row_of)
    dxg = jnp.where(live[..., None], rows, jnp.zeros((), dxs.dtype)).sum(2)
    return dxg, None, None


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@jax.custom_vjp
def _combine_rows(ys, w, slot, row_of, wr):
    """y[t] = sum_j w[t, j] * ys[row_of[t, j]] — each token gathers its
    k weighted expert rows (dropped slots add 0). Both cotangents are
    gathers too, of one row gather dyr[m] = dy[slot[m] // k] (0 on pad
    rows): dys[m] = wr[m] * dyr[m], with ``wr`` the rows' weights
    (w[slot[m]], 0 on pad rows), and the weight gradient to the router,
    dw[t, j] = <ys, dyr>[row_of[t, j]] (0 where dropped)."""
    rows, live = _slot_rows(ys, row_of)
    wv = jnp.where(live, w, 0.0)
    return (rows * wv[..., None]).sum(2)


def _combine_rows_fwd(ys, w, slot, row_of, wr):
    return _combine_rows(ys, w, slot, row_of, wr), (ys, slot, row_of, wr)


def _combine_rows_bwd(res, dy):
    ys, slot, row_of, wr = res
    G, g, k = row_of.shape
    dyr = _rows(_zero_row(dy), slot // k)  # dy[token of m], 0 on pad rows
    dys = (dyr * wr[..., None]).astype(ys.dtype)
    dwr = (ys * dyr).sum(-1)  # (G, M): <ys[m], dy[token of m]>
    # dropped slots read the appended zero at row M
    dw = jnp.take_along_axis(_zero_row(dwr), row_of.reshape(G, g * k),
                             axis=1)
    return dys, dw.reshape(G, g, k).astype(wr.dtype), None, None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def _sorted_dispatch(params, xg, r, cfg: ArchConfig, moe: MoECfg, *,
                     ctx: Optional[ShardCtx], implementation: str,
                     block: int):
    """Sorted ragged dispatch: sort the flat assignment stream by expert,
    run the contiguous ragged buffer through the grouped-GEMM kernel, and
    bring each token its rows back. Returns y (G, g, d).

    The ragged buffer has ``M = (ceil(N/block) + E) * block`` rows — N is
    the assignment count (g*k for token-choice), so FFN work is
    independent of capacity factor; capacity only decides WHICH
    assignments survive (the routers' keep masks).

    Token-choice routers (``r.token_expert`` set: k slots per token) move
    rows by gathers alone, in both directions and both transposes: the
    sort is a bijection between surviving assignments and live ragged
    rows, so ``ragged_sources`` gives every row its assignment (``slot``)
    and combine weight (``wr``) and every assignment its row
    (``row_of``), and each scatter the autodiff of a gather would emit
    has a gather through the other table instead (``_dispatch_rows``,
    ``_combine_rows``). Expert Choice has no fixed slots per token (its
    stream is expert-major, a token appears a variable number of times),
    so it keeps the scatter-built tables and the scatter-add combine.
    """
    from repro.kernels import ops
    from repro.kernels.grouped_mlp import ragged_destinations, ragged_sources

    G, g, d = xg.shape
    E = moe.num_experts
    token_choice = r.token_expert is not None

    with jax.named_scope("moe.dispatch"):
        # Flat per-group assignment stream (token id, expert id, weight)
        # — shared with the expert-parallel path (core/ep.py).
        tok, eid, w = R.assignment_stream(r, E, g)
        N = tok.shape[1]
        valid = (eid < E) & (tok < g)
        key = jnp.where(valid, eid, E).astype(jnp.int32)

        # Stable sort by expert (dropped assignments -> key E, past the
        # last segment) into block-aligned ragged rows — the layout math
        # shared with core/ep.py via kernels/grouped_mlp.py. No gradient
        # flows through the sort itself: the weights it carries are
        # constants, and the combine's VJP routes their gradient.
        if token_choice:
            counts, slot, wr, dest_of, M = ragged_sources(
                key, jax.lax.stop_gradient(w), E, block)
            row_of = dest_of.reshape(r.token_expert.shape)
            xs = _dispatch_rows(xg, slot, row_of)
        else:
            perm, key_s, counts, dest, M = ragged_destinations(key, E,
                                                               block)
            tok_s = jnp.take_along_axis(tok, perm, axis=1)
            w_s = jnp.take_along_axis(w, perm, axis=1)
            valid_s = key_s < E

            # Ragged buffers: src maps ragged row -> group-local token (g
            # = pad row), wr carries the combine weight (0 on pad rows).
            # Row M is the trash row for dropped assignments.
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
        ys = act(ctx, ys, "batch seq mlp")
        if token_choice:
            # Each token gathers and sums its k weighted rows.
            y = _combine_rows(ys, r.token_weight, slot, row_of, wr)
            return act(ctx, y.astype(xg.dtype), "batch seq mlp")
        # Weight, unsort, scatter-add (duplicate token rows — one per
        # surviving assignment — accumulate).
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
    ctx: Optional[ShardCtx] = None,
    implementation: str = "xla",
    sorted_block: int = 128,
    token_mask=None,
):
    """x: (B, S, d) or (N, d). Returns (y, metrics dict).

    ``sorted_block`` is the row-block alignment of the ragged buffer —
    128 matches the TPU kernel's MXU tiles; ``ApplyCfg.resolve`` picks 8
    for the XLA and ref implementations, whose small decode batches
    would otherwise pad to E*128 rows.

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

    with jax.named_scope("moe.combine"):
        y = y.reshape(-1, d)
        if pad:
            y = y[:n]
        y = y.reshape(orig_shape).astype(x.dtype)
    # Remat boundary tag: with stack_apply(remat="moe") only this combined
    # output is saved for the backward; the ragged buffers and router
    # tensors above are recomputed.
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
