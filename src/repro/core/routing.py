"""Routers: Expert Choice, Top-K (with BPR), Switch (Top-1).

Routing operates on token *groups* (paper §A.1.1: group size <= 4096): the
top-k / capacity bookkeeping is local to each group, which bounds the
routing working set and — on hardware — the all-to-all payloads.

All routers return a ``Routing`` carrying integer dispatch indices, combine
weights, and metrics. core/moe.py has one dispatch: it reads them through
``assignment_stream`` and sorts the stream into a ragged buffer for the
grouped-GEMM kernel. Token-choice routers additionally expose the
token-major assignment view (``token_expert``/``token_weight``) that the
stream and the gather-only combine read.

Shapes: x grouped as (G, g, d); router logits (G, g, E); slot tables
(G, E, cap).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs import MoECfg
from repro.models import param as pm


class Routing(NamedTuple):
    # For every expert slot (G, E, cap): which group-local token fills it.
    # Token id == g (out of range) marks an unfilled slot.
    token_idx: jax.Array  # int32 (G, E, cap)
    # Combine weight for each expert slot (0 where unfilled) (G, E, cap).
    combine: jax.Array
    # Router probabilities (G, g, E) — kept for metrics.
    probs: jax.Array
    aux_loss: jax.Array  # scalar
    z_loss: jax.Array  # scalar
    # Fraction of tokens processed by no expert (dropped) — scalar metric.
    dropped_frac: jax.Array
    # Token-major assignments for the sorted ragged dispatch (token-choice
    # routers only; None for Expert Choice, whose slot table is already
    # expert-major and fully dense). (G, g, k) int32 expert id per
    # assignment — id == E marks a capacity-dropped assignment — and the
    # matching combine weight (0 where dropped). Mirrors the slot table
    # exactly: same capacity claims, same drops, same weights.
    token_expert: Optional[jax.Array] = None  # int32 (G, g, k)
    token_weight: Optional[jax.Array] = None  # f32 (G, g, k)


def router_init(rng, d_model: int, moe: MoECfg):
    return {
        "w": pm.normal(
            rng, (d_model, moe.num_experts), "embed expert",
            std=moe.router_init_std,
        )
    }


def _z_loss(logits) -> jax.Array:
    return jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))


def capacity(group: int, moe: MoECfg) -> int:
    """Tokens per expert per group (paper §2.1: cap = C * n / E)."""
    cap = max(1, -(-int(group * moe.capacity_factor) // moe.num_experts))
    return min(cap, group)


def route_expert_choice(logits: jax.Array, moe: MoECfg) -> Routing:
    """Expert Choice (Zhou et al. 2022): every expert picks its top-cap
    tokens (top-k per column). Always perfectly load balanced."""
    G, g, E = logits.shape
    cap = capacity(g, moe)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    # (G, E, g): experts choose tokens.
    weights, token_idx = jax.lax.top_k(probs.transpose(0, 2, 1), cap)
    combine = weights  # (G, E, cap)

    if moe.normalize_combine_weights:
        combine = _normalize_per_token(token_idx, combine, g)

    # Dropped tokens: selected by no expert.
    sel = jnp.zeros((G, g + 1), jnp.float32)
    sel = _scatter_add_groups(sel, token_idx, jnp.ones_like(combine))
    dropped = jnp.mean((sel[:, :g] == 0).astype(jnp.float32))

    # EC is perfectly load balanced by construction: no aux loss (the
    # weighted zero keeps the metrics tree shape identical to Top-K).
    aux = jnp.zeros((), jnp.float32)
    return Routing(
        token_idx=token_idx,
        combine=combine,
        probs=probs,
        aux_loss=aux,
        z_loss=_z_loss(logits) if moe.z_loss_weight else jnp.zeros(()),
        dropped_frac=dropped,
    )


def _top_k_by_mask(probs: jax.Array, k: int):
    """``lax.top_k`` over the last axis whose weights read ``probs``
    through a one-hot mask instead of a gather, so the gradient is a
    masked sum rather than ``top_k``'s scatter-add transpose (on TPU a
    serial loop over its g*k updates). Same values: each weight is one
    probability plus exact zeros, and top-k picks distinct experts."""
    top_e = jax.lax.top_k(jax.lax.stop_gradient(probs), k)[1]
    hot = jax.nn.one_hot(top_e, probs.shape[-1], dtype=probs.dtype)
    return (probs[..., None, :] * hot).sum(-1), top_e


def route_top_k(
    logits: jax.Array,
    moe: MoECfg,
    *,
    k: Optional[int] = None,
    bpr: Optional[bool] = None,
    token_mask: Optional[jax.Array] = None,
) -> Routing:
    """Top-K token-choice routing (Shazeer et al. 2017 / GShard) with
    capacity buffers, optional Batch Prioritized Routing (paper §B.1).

    ``token_mask`` (G, g) bool: False marks dead tokens (continuous-
    batching decode slots that hold no request) — their assignments are
    forced to the trash expert id E *before* capacity accounting, so
    they claim no capacity, appear in no dispatch table, and carry zero
    combine weight. Live tokens' routing is unchanged."""
    G, g, E = logits.shape
    k = moe.top_k if k is None else k
    bpr = moe.bpr if bpr is None else bpr
    cap = capacity(g, moe)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_w, top_e = _top_k_by_mask(probs, k)  # (G, g, K)
    if token_mask is not None:
        top_e = jnp.where(token_mask[..., None], top_e, E)

    def positions_of(top_e_local):
        """Capacity claims in token-major, k-minor order."""
        oh = jax.nn.one_hot(top_e_local, E, dtype=jnp.int32)  # (G,g,K,E)
        flat = oh.reshape(G, g * k, E)
        pos_flat = jnp.cumsum(flat, axis=1) - flat  # claims before this
        return (pos_flat * flat).sum(-1).reshape(G, g, k)

    # Priority order for capacity claims: BPR gives capacity to the most
    # confident tokens first; default is natural (causal-safe) order.
    # Implemented with lax.sort round trips (NOT batched gathers — those
    # hit an XLA-client version skew in this environment under scan).
    if bpr:
        # Integer bookkeeping only — no gradients flow through priority
        # order, and lax.sort's JVP would itself emit batched gathers.
        neg_conf = jax.lax.stop_gradient(-top_w[..., 0])  # (G, g)
        token_ids = jnp.broadcast_to(
            jnp.arange(g, dtype=jnp.int32), (G, g)
        )
        sorted_ops = jax.lax.sort(
            (neg_conf, token_ids)
            + tuple(top_e[..., i] for i in range(k)),
            dimension=1, num_keys=1,
        )
        orig_idx = sorted_ops[1]
        top_e_sorted = jnp.stack(sorted_ops[2:], axis=-1)
        pos_s = positions_of(top_e_sorted)
        keep_s = (pos_s < cap).astype(jnp.int32)
        # un-sort back to natural token order
        unsorted = jax.lax.sort(
            (orig_idx,)
            + tuple(pos_s[..., i] for i in range(k))
            + tuple(keep_s[..., i] for i in range(k)),
            dimension=1, num_keys=1,
        )
        pos = jnp.stack(unsorted[1:1 + k], axis=-1)
        keep = jnp.stack(unsorted[1 + k:], axis=-1).astype(bool)
    else:
        pos = positions_of(top_e)
        keep = pos < cap

    if token_mask is not None:
        keep = keep & token_mask[..., None]
    w = top_w * keep
    if moe.normalize_combine_weights:
        denom = jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
        w = w / denom

    # Scatter (token, k) claims into expert slot table (G, E, cap).
    token_ids = jnp.broadcast_to(jnp.arange(g)[None, :, None], (G, g, k))
    slot_e = jnp.where(keep, top_e, E)  # overflow -> expert E (trash row)
    slot_p = jnp.where(keep, pos, cap)
    token_idx = jnp.full((G, E + 1, cap + 1), g, jnp.int32)
    combine = jnp.zeros((G, E + 1, cap + 1), jnp.float32)
    gi = jnp.broadcast_to(jnp.arange(G)[:, None, None], (G, g, k))
    token_idx = token_idx.at[gi, slot_e, slot_p].set(token_ids)
    combine = combine.at[gi, slot_e, slot_p].set(w)
    token_idx = token_idx[:, :E, :cap]
    combine = combine[:, :E, :cap]

    # Metrics normalize over LIVE tokens when a mask is present, so a
    # mostly-free decode batch doesn't read as "75% dropped" and dead
    # tokens' router probs don't dilute the load-balance terms.
    no_keep = 1.0 - jnp.any(keep, axis=-1).astype(jnp.float32)
    # Load-balance aux loss (Switch/GShard form on top-1 assignments);
    # dead tokens' top_e is E, so their one-hot rows are already zero.
    top1 = jax.nn.one_hot(top_e[..., 0], E, dtype=jnp.float32)
    if token_mask is None:
        dropped = jnp.mean(no_keep)
        density = top1.mean(axis=1)  # (G, E) fraction of tokens -> e
        p_mean = probs.mean(axis=1)  # (G, E)
    else:
        live = token_mask.astype(jnp.float32)  # (G, g)
        n_live = jnp.maximum(live.sum(-1, keepdims=True), 1.0)  # (G, 1)
        dropped = jnp.mean((no_keep * live).sum(-1) / n_live[:, 0])
        density = top1.sum(axis=1) / n_live
        p_mean = (probs * live[..., None]).sum(axis=1) / n_live
    aux = E * jnp.mean(jnp.sum(density * p_mean, axis=-1))

    return Routing(
        token_idx=token_idx,
        combine=combine,
        probs=probs,
        aux_loss=aux,
        z_loss=_z_loss(logits) if moe.z_loss_weight else jnp.zeros(()),
        dropped_frac=dropped,
        token_expert=jnp.where(keep, top_e, E).astype(jnp.int32),
        token_weight=w,
    )


def route(logits: jax.Array, moe: MoECfg, router_kind: str, *,
          token_mask: Optional[jax.Array] = None) -> Routing:
    if router_kind == "expert_choice":
        if token_mask is not None:
            # EC's per-expert top-cap would need column-wise masking;
            # decoders (the only place dead decode slots exist) always
            # route token-choice (stack_router_kind, paper §3.1).
            raise ValueError(
                "token_mask is only supported by token-choice routers"
            )
        return route_expert_choice(logits, moe)
    if router_kind == "top_k":
        return route_top_k(logits, moe, token_mask=token_mask)
    if router_kind == "switch":
        return route_top_k(logits, moe, k=1, token_mask=token_mask)
    raise ValueError(f"unknown router {router_kind!r}")


def assignment_stream(r: Routing, num_experts: int, group: int):
    """Flat per-group assignment stream ``(tok, eid, w)``, each ``(G, N)``:
    group-local token id, expert id and combine weight for every routing
    assignment. This is the common input of the sorted dispatches
    (single-device ragged sort in core/moe.py and the expert-parallel
    all-to-all in core/ep.py).

    Token-choice routers expose it token-major (their ``token_expert`` /
    ``token_weight`` views, N = g*k); Expert Choice slots are already
    expert-major and fully dense, so its slot table flattens directly
    (N = E*cap). Dropped/invalid assignments carry ``eid == E`` or
    ``tok == group``.
    """
    G = r.probs.shape[0]
    E = num_experts
    if r.token_expert is not None:
        A = r.token_expert.shape[-1]
        tok = jnp.broadcast_to(
            jnp.arange(group, dtype=jnp.int32)[None, :, None],
            (G, group, A),
        ).reshape(G, group * A)
        eid = r.token_expert.reshape(G, group * A)
        w = r.token_weight.reshape(G, group * A)
    else:
        cap = r.token_idx.shape[-1]
        eid = jnp.broadcast_to(
            jnp.arange(E, dtype=jnp.int32)[:, None], (E, cap)
        ).reshape(1, E * cap)
        eid = jnp.broadcast_to(eid, (G, E * cap))
        tok = r.token_idx.reshape(G, E * cap)
        w = r.combine.reshape(G, E * cap)
    return tok, eid, w


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _scatter_add_groups(tbl, idx, val):
    """tbl (G, g+1); idx (G, E, cap) group-local token ids; val same shape."""
    G = tbl.shape[0]
    gi = jnp.broadcast_to(
        jnp.arange(G)[:, None, None], idx.shape
    )
    return tbl.at[gi, idx].add(val)


def _normalize_per_token(token_idx, combine, g):
    """Paper §B.7: renormalize each token's combine weights to sum to 1.

    Tokens selected by no expert keep weight 0 (their output is 0 — i.e.
    residual passthrough in the transformer block).
    """
    G = combine.shape[0]
    denom = jnp.zeros((G, g + 1), jnp.float32)
    denom = _scatter_add_groups(denom, token_idx, combine)
    denom = jnp.maximum(denom, 1e-9)
    gi = jnp.broadcast_to(jnp.arange(G)[:, None, None], token_idx.shape)
    return combine / denom[gi, token_idx]
