"""A plain MoE layer to check ``core.moe.moe_apply`` against.

``moe_reference`` groups and routes the tokens as the layer does, turns
the routing's assignment stream into a dense ``(G, g, E)`` combine weight
by one-hot sums, and runs every expert's FFN on every token in float32
at the highest matmul precision. No sort, no ragged buffer, no kernel:
the meaning of the dispatch without its data movement.
"""
import jax
import jax.numpy as jnp

from repro.core import routing as R
from repro.models.layers import activation

HIGHEST = jax.lax.Precision.HIGHEST


def moe_reference(vals, x, cfg, moe, router_kind, token_mask=None):
    """x: (B, S, d) or (N, d). Returns (y, metrics) with ``aux_loss``
    (weighted as the layer weights it) and ``dropped_frac``."""
    shape, d = x.shape, x.shape[-1]
    x2d = x.reshape(-1, d)
    n = x2d.shape[0]
    g = min(moe.group_size, n)
    pad = (-n) % g
    xg = jnp.pad(x2d, ((0, pad), (0, 0))).reshape(-1, g, d)
    G, E = xg.shape[0], moe.num_experts
    mg = None
    if token_mask is not None:
        m = jnp.broadcast_to(token_mask, shape[:-1]).reshape(-1)
        mg = jnp.pad(m.astype(bool), (0, pad)).reshape(G, g)

    logits = jnp.einsum("Ggd,de->Gge", xg, vals["router"]["w"],
                        preferred_element_type=jnp.float32)
    r = R.route(logits, moe, router_kind, token_mask=mg)
    tok, eid, w = R.assignment_stream(r, E, g)
    # one_hot of an out-of-range id (a dropped assignment) is all zeros
    weight = jnp.einsum("Gn,Gnt,Gne->Gte", w.astype(jnp.float32),
                        jax.nn.one_hot(tok, g, dtype=jnp.float32),
                        jax.nn.one_hot(eid, E, dtype=jnp.float32),
                        precision=HIGHEST)

    ex = {k: v.astype(jnp.float32) for k, v in vals["experts"].items()}
    xf = xg.astype(jnp.float32)
    h = activation(cfg.act)(
        jnp.einsum("Ggd,edf->Ggef", xf, ex["wi"], precision=HIGHEST))
    if "wg" in ex:
        h = h * jnp.einsum("Ggd,edf->Ggef", xf, ex["wg"], precision=HIGHEST)
    ye = jnp.einsum("Ggef,efd->Gged", h, ex["wo"], precision=HIGHEST)
    y = jnp.einsum("Gge,Gged->Ggd", weight, ye, precision=HIGHEST)
    y = y.reshape(-1, d)[:n].reshape(shape).astype(x.dtype)
    return y, {"aux_loss": r.aux_loss * moe.aux_loss_weight,
               "dropped_frac": r.dropped_frac}
