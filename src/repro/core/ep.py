"""Expert-parallel sorted dispatch: shard_map ragged all-to-all.

The ``moe.ep="none"`` layout of core/moe.py keeps the ragged token
buffer batch-sharded and lets GSPMD gather every expert's weights to the
data shards (FSDP / "Llama 3 Meets MoE" layout) — weight traffic scales
with E. This module is the complementary regime (``moe.ep="a2a"``):
**tokens move, weights stay**. Expert weights are sharded over the
``model`` mesh axis (their natural PARAM_RULES placement) and each
device runs the grouped-GEMM kernel over only its E/ep local experts;
token rows cross the axis through two all-to-alls (dispatch + return).

Under ``shard_map`` each device:

1. flattens its local routing groups into one assignment stream and
   stable-partitions it by DESTINATION PEER (``expert // E_loc``);
2. packs rows into a block-aligned send buffer with a *static* per
   (src, dst) row budget — assignments past the budget are dropped
   exactly like capacity overflow (``ep_overflow_frac`` metric);
3. ``lax.all_to_all`` (tiled) exchanges token rows + local-expert ids;
4. locally sorts the received rows by local expert into the same
   block-aligned ragged layout as the single-device sorted path and
   runs ``ops.grouped_mlp`` (Pallas grouped-GEMM kernel / XLA
   ragged_dot — the same custom-VJP kernels);
5. returns results through the mirror all-to-all and combines on the
   SOURCE device (weight multiply + unsort scatter-add), so combine
   weights never travel.

Everything inside the shard_map is plain jnp + ``lax.all_to_all`` +
the custom-VJP grouped kernel, so ``jax.grad`` works end-to-end: the
all-to-alls transpose to all-to-alls, scatters to gathers, and the
replicated-in weight specs transpose to psums over the non-EP axes —
the train loop needs no special casing.

Who moves / where drops happen (vs ``ep="none"``): see the layout
table in core/moe.py and kernels/README.md.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import ArchConfig, MoECfg
from repro.core import routing as R
from repro.sharding import ShardCtx
from repro.sharding.logical import expert_parallel_layout


def ep_row_budget(n_local: int, ep: int, factor: float, block: int) -> int:
    """Static per-(src, dst) peer row budget: ``factor`` times the
    balanced share of the local assignments, block-aligned, capped at
    ``n_local`` (a source can never send more than everything to one
    peer — ``factor >= ep`` therefore guarantees zero EP drops)."""
    b = -(-int(n_local * factor) // ep)
    b = max(block, -(-b // block) * block)
    return min(b, -(-n_local // block) * block)


def sorted_dispatch_ep(
    params, xg, r, cfg: ArchConfig, moe: MoECfg, *,
    ctx: ShardCtx, implementation: str, block: int,
):
    """Expert-parallel sorted dispatch. xg: (G, g, d) -> (y (G, g, d),
    ep_overflow_frac scalar). Caller guarantees
    ``expert_parallel_layout(ctx.mesh, E)`` is not None."""
    from repro.kernels import ops
    from repro.kernels.grouped_mlp import ragged_destinations

    mesh = ctx.mesh
    E = moe.num_experts
    ep_axis, ep, token_axes = expert_parallel_layout(mesh, E)
    E_loc = E // ep
    G, g, d = xg.shape
    ndev = mesh.devices.size
    if G % ndev:
        raise ValueError(
            f"moe.ep='a2a' shards routing groups over all {ndev} mesh "
            f"devices, but G={G} groups (tokens/group_size) is not "
            f"divisible — pick batch*seq and group_size so that "
            f"G % {ndev} == 0"
        )
    tok, eid, w = R.assignment_stream(r, E, g)  # (G, N) each
    N = tok.shape[1]
    G_loc = G // ndev
    n_local = G_loc * N
    budget = ep_row_budget(n_local, ep, moe.ep_budget_factor, block)

    wi = params["experts"]["wi"]
    wg = params["experts"].get("wg")
    wo = params["experts"]["wo"]
    gated = wg is not None

    def local_fn(xg_l, tok_l, eid_l, w_l, *weights):
        if gated:
            wi_l, wg_l, wo_l = weights
        else:
            wi_l, wo_l = weights
            wg_l = None
        Gl = xg_l.shape[0]
        Nl = Gl * N
        f32 = jnp.float32

        # ---- pack by destination peer -------------------------------
        tokf = (
            tok_l + (jnp.arange(Gl, dtype=jnp.int32) * g)[:, None]
        ).reshape(Nl)
        eidf = eid_l.reshape(Nl)
        wf = w_l.reshape(Nl)
        valid = (eidf < E) & (tok_l.reshape(Nl) < g)
        peer = jnp.where(valid, eidf // E_loc, ep).astype(jnp.int32)
        onehot = (
            peer[:, None] == jnp.arange(ep, dtype=jnp.int32)[None, :]
        ).astype(jnp.int32)
        rank = ((jnp.cumsum(onehot, 0) - onehot) * onehot).sum(1)
        keep = valid & (rank < budget)  # overflow dropped like capacity
        slot = jnp.where(keep, peer * budget + rank, ep * budget)

        x_rows = xg_l.reshape(Gl * g, d)[jnp.minimum(tokf, Gl * g - 1)]
        x_rows = x_rows * keep[:, None].astype(x_rows.dtype)
        send_x = (
            jnp.zeros((ep * budget + 1, d), xg_l.dtype)
            .at[slot].set(x_rows)[: ep * budget]
        )
        send_e = (
            jnp.full((ep * budget + 1,), E_loc, jnp.int32)
            .at[slot].set(jnp.where(keep, eidf % E_loc, E_loc))
            [: ep * budget]
        )

        # ---- dispatch all-to-all (tokens + local-expert ids) --------
        recv_x = jax.lax.all_to_all(send_x, ep_axis, 0, 0, tiled=True)
        recv_e = jax.lax.all_to_all(send_e, ep_axis, 0, 0, tiled=True)

        # ---- local ragged sort by expert + grouped GEMM -------------
        # Same sort-and-pack layout math as the single-device path,
        # shared via kernels/grouped_mlp.py (recv_e == E_loc marks
        # invalid rows; counts (1, E_loc) feeds the kernel directly).
        Rr = ep * budget
        perm, _, counts, dest, M = ragged_destinations(
            recv_e[None], E_loc, block
        )
        perm, dest = perm[0], dest[0]
        xs = (
            jnp.zeros((M + 1, d), xg_l.dtype)
            .at[dest].set(jnp.take(recv_x, perm, axis=0))[:M]
        )
        with jax.named_scope("moe.experts"):
            ys = ops.grouped_mlp(
                xs[None], wi_l, wg_l, wo_l, counts,
                act=cfg.act, block=block, implementation=implementation,
            )[0]

        # ---- return all-to-all + combine on the source --------------
        with jax.named_scope("moe.combine"):
            ys_pad = jnp.concatenate(
                [ys, jnp.zeros((1, d), ys.dtype)], axis=0
            )
            y_recv = (
                jnp.zeros((Rr, d), ys.dtype)
                .at[perm].set(jnp.take(ys_pad, dest, axis=0))
            )
            y_ret = jax.lax.all_to_all(y_recv, ep_axis, 0, 0, tiled=True)
            y_pad = jnp.concatenate(
                [y_ret, jnp.zeros((1, d), y_ret.dtype)], axis=0
            )
            w_eff = jnp.where(keep, wf, 0.0).astype(xg_l.dtype)
            contrib = jnp.take(y_pad, slot, axis=0).astype(xg_l.dtype)
            contrib = contrib * w_eff[:, None]
            tok_dst = jnp.where(keep, tokf, Gl * g)
            y_l = (
                jnp.zeros((Gl * g + 1, d), xg_l.dtype)
                .at[tok_dst].add(contrib)[: Gl * g]
            ).reshape(Gl, g, d)

        # ---- overflow metric (EP drops on top of capacity drops) ----
        n_over = jax.lax.psum(
            jax.lax.stop_gradient((valid & ~keep).sum().astype(f32)),
            token_axes,
        )
        n_valid = jax.lax.psum(
            jax.lax.stop_gradient(valid.sum().astype(f32)), token_axes
        )
        over_frac = n_over / jnp.maximum(n_valid, 1.0)
        return y_l, over_frac

    # Token-side arrays shard their G dim over EVERY mesh axis (each
    # device owns a distinct slice of the routing groups); weights shard
    # experts over the EP axis and replicate over the rest — their
    # transpose under grad is the psum that makes dW globally correct.
    w_spec = P(ep_axis)
    in_specs = [
        P(token_axes, None, None),  # xg
        P(token_axes, None),        # tok
        P(token_axes, None),        # eid
        P(token_axes, None),        # w
        w_spec,                   # wi (E, d, f): experts over ep axis
    ]
    weights = [wi]
    if gated:
        in_specs.append(w_spec)
        weights.append(wg)
    in_specs.append(w_spec)
    weights.append(wo)

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(token_axes, None, None), P()),
        check_vma=False,
    )
    return fn(xg, tok, eid, w, *weights)
