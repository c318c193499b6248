"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` of each kernel).

These are the ground truth for the per-kernel allclose sweeps in
tests/test_kernels_*.py. They are deliberately simple — no chunking, no
tiling — and run in fp32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _act(name: str):
    from repro.models.layers import activation

    return activation(name)


def grouped_mlp_ref(xs, wi, wg, wo, group_sizes, *, block: int = 128,
                    act: str = "silu"):
    """Grouped-GEMM (sorted ragged dispatch) oracle.

    xs: (G, M, d) expert-sorted block-aligned rows; group_sizes: (G, E)
    valid rows per expert (segment e starts at the block-aligned offset,
    see kernels/grouped_mlp.py). Per-row expert weights are selected with
    a one-hot einsum — deliberately simple, FLOPs be damned.
    """
    f32 = jnp.float32
    G, M, d = xs.shape
    E = wi.shape[0]
    aligned = jnp.maximum(1, -(-group_sizes // block)) * block
    ends = jnp.cumsum(aligned, axis=-1)  # (G, E)
    rows = jnp.arange(M, dtype=jnp.int32)
    eid = (rows[None, :, None] >= ends[:, None, :]).sum(-1)
    oh = jax.nn.one_hot(jnp.minimum(eid, E - 1), E, dtype=f32)  # (G, M, E)
    h = jnp.einsum("gme,gmd,edf->gmf", oh, xs.astype(f32), wi.astype(f32))
    if wg is not None:
        g = jnp.einsum(
            "gme,gmd,edf->gmf", oh, xs.astype(f32), wg.astype(f32)
        )
        h = _act(act)(h) * g
    else:
        h = _act(act)(h)
    y = jnp.einsum("gme,gmf,efd->gmd", oh, h, wo.astype(f32))
    return y.astype(xs.dtype)


def flash_attention_ref(q, k, v, *, causal=True, q_offset=0, kv_len=None):
    """O(S^2) attention oracle (GQA-aware). Shapes as in models/attention."""
    from repro.models.attention import reference_attention

    return reference_attention(
        q, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len
    )


def rwkv6_ref(r, k, v, w, u, *, initial_state=None):
    """RWKV-6 (Finch) WKV oracle — sequential recurrence.

    r, k: (B, T, H, K); v: (B, T, H, V); w: (B, T, H, K) per-step decay
    (already exp(-exp(w_raw)) -> in (0, 1)); u: (H, K) bonus.
    state: (B, H, K, V). Returns (out (B, T, H, V), final state).

        o_t = r_t . (S + u * k_t v_t^T);  S <- diag(w_t) S + k_t v_t^T
    """
    B, T, H, K = r.shape
    V = v.shape[-1]
    f32 = jnp.float32
    r, k, v, w = (x.astype(f32) for x in (r, k, v, w))
    u = u.astype(f32)
    if initial_state is None:
        initial_state = jnp.zeros((B, H, K, V), f32)

    def step(S, xs):
        rt, kt, vt, wt = xs  # (B, H, K) / (B, H, V)
        kv = kt[..., :, None] * vt[..., None, :]  # (B, H, K, V)
        o = jnp.einsum(
            "bhk,bhkv->bhv", rt, S + u[None, :, :, None] * kv
        )
        S = wt[..., :, None] * S + kv
        return S, o

    xs = tuple(
        jnp.moveaxis(t, 1, 0) for t in (r, k, v, w)
    )  # (T, B, H, *)
    S, out = jax.lax.scan(step, initial_state.astype(f32), xs)
    out = jnp.moveaxis(out, 0, 1)  # (B, T, H, V)
    return out.astype(v.dtype), S
