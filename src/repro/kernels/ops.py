"""Public kernel entry points (the ``ops.py`` jit'd wrappers).

Every op takes ``implementation``:

* ``"xla"``     — jnp einsum / chunked-scan path. Used on CPU, for dry-run
                  lowering, and as the production fallback.
* ``"pallas"``  — the Pallas TPU kernel (pl.pallas_call with BlockSpec VMEM
                  tiling). Compiled on TPU; on CPU it runs in interpret
                  mode for validation; any other backend raises. The
                  mode is decided per call (``_interpret``), never at
                  import, so importing this module touches no device.
                  Grad-enabled: grouped_mlp and flash attention route
                  through ``jax.custom_vjp`` wrappers whose backward passes
                  are themselves fused Pallas kernels, so ``jax.grad``
                  through "pallas" never falls back to XLA einsums.
* ``"ref"``     — the pure-jnp oracle from ref.py.
* ``"auto"``    — ``default_implementation()``: "pallas" on TPU, "xla"
                  elsewhere. The train loop's default.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref


def _interpret() -> bool:
    """Pallas mode for this call: compiled on TPU, interpreted on CPU
    (validation only). Any other backend has no Pallas TPU kernels, and
    interpreting there would hide the device behind a slow emulation."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise NotImplementedError(
        f"implementation='pallas' needs a TPU (or the CPU interpreter); "
        f"the default backend is {backend!r} — use 'xla'"
    )


def default_implementation() -> str:
    """The training-grade default: fused Pallas kernels on TPU (forward
    AND backward), XLA einsums everywhere else."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _resolve(implementation: str) -> str:
    if implementation == "auto":
        return default_implementation()
    return implementation


def grouped_mlp(xs, wi, wg, wo, group_sizes, *, act: str = "silu",
                block: int = 128, implementation="xla"):
    """Grouped expert FFN over a sorted ragged token buffer — the MoE
    layer's expert FFN (no padded capacity buffer).

    xs: (G, M, d) expert-sorted rows, each expert's segment padded to a
    multiple of ``block`` rows (layout built by core/moe.py with
    ``grouped_mlp.ragged_row_offsets``); group_sizes: (G, E) valid rows
    per expert; padded/tail rows are zero and produce zero rows.

    * ``pallas`` — scalar-prefetch grouped-GEMM kernel walking expert
      boundaries (fwd + custom-VJP bwd), kernels/grouped_mlp.py.
    * ``xla``    — per-group ``jax.lax.ragged_dot`` segment GEMMs (the
      CPU/tests fallback; differentiable, dense-equivalent FLOPs).
    * ``ref``    — one-hot einsum oracle (ref.py).
    """
    implementation = _resolve(implementation)
    if implementation == "ref":
        return _ref.grouped_mlp_ref(
            xs, wi, wg, wo, group_sizes, block=block, act=act
        )
    if implementation == "pallas":
        from repro.kernels import grouped_mlp as gm

        return gm.grouped_mlp_pallas_vjp(
            xs, wi, wg, wo, group_sizes, act=act, bm=block,
            interpret=_interpret(),
        )
    return _grouped_mlp_xla(xs, wi, wg, wo, group_sizes, act=act,
                            block=block)


def _grouped_mlp_xla(xs, wi, wg, wo, group_sizes, *, act, block):
    """Segment-GEMM fallback: one ``lax.ragged_dot`` chain per group
    (``ragged_dot`` has no batching rule yet, and G is static/small).
    Segment sizes are the block-ALIGNED row counts so they tile the
    buffer exactly; aligned-pad rows are zero -> contribute zero, and
    rows past the last segment are zeroed by ragged_dot itself."""
    from repro.models.layers import activation

    sizes = jnp.maximum(1, -(-group_sizes // block)) * block  # (G, E)
    outs = []
    for g in range(xs.shape[0]):
        h = jax.lax.ragged_dot(xs[g], wi, sizes[g])
        if wg is not None:
            gt = jax.lax.ragged_dot(xs[g], wg, sizes[g])
            h = activation(act)(h) * gt
        else:
            h = activation(act)(h)
        outs.append(jax.lax.ragged_dot(h.astype(wo.dtype), wo, sizes[g]))
    return jnp.stack(outs).astype(xs.dtype)


def flash_attention(
    q, k, v, *, causal=True, q_offset=0, kv_len=None,
    q_chunk=1024, kv_chunk=1024, implementation="xla",
):
    implementation = _resolve(implementation)
    if implementation == "ref":
        return _ref.flash_attention_ref(
            q, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len
        )
    if implementation == "pallas":
        from repro.kernels import flash_attention as fa

        return fa.flash_attention_pallas_vjp(
            q, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len,
            interpret=_interpret(),
        )
    from repro.models.attention import flash_attention as fa_xla

    return fa_xla(
        q, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len,
        q_chunk=q_chunk, kv_chunk=kv_chunk,
    )


def decode_attention(q, k_pool, v_pool, block_tables, lengths, *,
                     implementation="xla"):
    """Paged single-query GQA attention over a block-pooled KV cache —
    the continuous-batching serving hot path (repro/serve).

    q: (B, 1, H, dh) one query token per sequence slot;
    k_pool/v_pool: (P, Kh, bs, dh) global KV block pools;
    block_tables: (B, nb) int32 pool block ids per slot;
    lengths: (B,) int32 valid kv tokens per slot (0 = free slot ->
    exact-zero output).

    * ``pallas`` — scalar-prefetch block-table walk with online softmax
      (kernels/decode_attention.py; interpret mode on CPU). Reads scale
      with ``ceil(length/bs)`` live blocks per slot, not ``nb``.
    * ``xla`` / ``ref`` — gather each slot's blocks into a dense
      ``(B, nb*bs, Kh, dh)`` view and run the masked-softmax oracle
      (``models/attention._decode_attention``): the production non-TPU
      fallback AND the parity ground truth (tests/test_paged_decode.py).

    Serving-only: no VJP (training-through-decode is a ROADMAP item).
    """
    implementation = _resolve(implementation)
    if implementation == "pallas":
        from repro.kernels import decode_attention as da

        y = da.paged_decode_attention_pallas(
            q[:, 0], k_pool, v_pool, block_tables, lengths,
            interpret=_interpret(),
        )
        return y[:, None]
    from repro.models.attention import _decode_attention

    return _decode_attention(
        q, _gather_blocks(k_pool, block_tables),
        _gather_blocks(v_pool, block_tables), lengths,
    )


def _gather_blocks(pool, block_tables):
    """Dense ``(B, nb*bs, Kh, dh)`` view of each row's blocks of a
    head-major ``(P, Kh, bs, dh)`` pool — the XLA fallbacks' read."""
    B, nb = block_tables.shape
    _, Kh, bs, dh = pool.shape
    blocks = pool[block_tables]  # (B, nb, Kh, bs, dh)
    return blocks.transpose(0, 1, 3, 2, 4).reshape(B, nb * bs, Kh, dh)


def prefill_attention(q, k_pool, v_pool, block_tables, starts, lens, *,
                      implementation="xla"):
    """Paged chunked-prefill GQA attention over a block-pooled KV cache
    — the prefill lane of the mixed serve step (repro/serve).

    q: (NC, C, H, dh) — NC chunks of C consecutive prompt tokens, one
    request each; k_pool/v_pool: (P, Kh, bs, dh) global KV block pools
    with the chunk's own k/v ALREADY written (the mixed step writes both
    lanes through one scatter before attention); block_tables: (NC, nb)
    int32 pool block ids of each chunk's slot; starts: (NC,) int32
    absolute position of q[c, 0]; lens: (NC,) int32 valid rows per chunk
    (0 = dead chunk lane -> exact-zero output). Row i of chunk c attends
    every pool position <= starts[c] + i (prefix blocks, earlier chunks
    and the chunk itself — causal against absolute positions).

    * ``pallas`` — scalar-prefetch q-tile x kv-block walk with online
      softmax (kernels/paged_prefill.py; interpret mode on CPU). Reads
      scale with the blocks each q tile attends, not ``nb``.
    * ``xla`` / ``ref`` — gather each chunk's blocks into a dense
      ``(NC, nb*bs, Kh, dh)`` view and run a masked softmax over
      absolute positions: the production non-TPU fallback AND the
      parity ground truth (tests/test_paged_prefill.py).

    Serving-only: no VJP (same ROADMAP item as decode_attention).
    """
    implementation = _resolve(implementation)
    if implementation == "pallas":
        from repro.kernels import paged_prefill as pp

        return pp.paged_prefill_attention_pallas(
            q, k_pool, v_pool, block_tables, starts, lens,
            interpret=_interpret(),
        )
    NC, C, H, dh = q.shape
    Kh = k_pool.shape[1]
    G = H // Kh
    k = _gather_blocks(k_pool, block_tables)
    v = _gather_blocks(v_pool, block_tables)
    qg = q.reshape(NC, C, Kh, G, dh)
    s = jnp.einsum(
        "bqkgd,btkd->bkgqt", qg, k, preferred_element_type=jnp.float32
    ) * dh ** -0.5
    q_pos = starts[:, None] + jnp.arange(C)[None, :]          # (NC, C)
    valid_q = jnp.arange(C)[None, :] < lens[:, None]
    kv_pos = jnp.arange(k.shape[1])
    mask = (
        valid_q[:, :, None]
        & (kv_pos[None, None, :] <= q_pos[:, :, None])
    )  # (NC, C, T)
    s = jnp.where(mask[:, None, None], s, float("-inf"))
    # Zero-valid-key-safe softmax (decode oracle discipline).
    m = s.max(axis=-1, keepdims=True)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(mask[:, None, None], jnp.exp(s - m_safe), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    p = p / jnp.where(l == 0.0, 1.0, l)
    y = jnp.einsum(
        "bkgqt,btkd->bqkgd", p, v, preferred_element_type=jnp.float32
    )
    return y.reshape(NC, C, H, dh).astype(q.dtype)


# One-time flag for the rwkv6 "auto" fallback warning below; tests reset
# it to re-arm the warning.
_RWKV6_AUTO_WARNED = False


def rwkv6(r, k, v, w, u, *, initial_state=None, chunk=64,
          implementation="xla"):
    """RWKV-6 WKV. Returns (out, final_state)."""
    if implementation == "auto":
        # No custom-VJP rwkv6 Pallas kernel yet (ROADMAP open item):
        # unlike grouped_mlp / flash_attention, "auto" resolves to the
        # chunked XLA path EVERYWHERE — including TPU — so rwkv6
        # training steps do not get the kernel-fused backward the other
        # hot paths get. Warn once (at trace time) so the perf cliff is
        # visible instead of silent; pass implementation="xla"
        # explicitly to acknowledge the fallback and silence this.
        global _RWKV6_AUTO_WARNED
        if not _RWKV6_AUTO_WARNED:
            _RWKV6_AUTO_WARNED = True
            warnings.warn(
                "rwkv6 implementation='auto' falls back to the chunked "
                "XLA path (no custom-VJP Pallas rwkv6 kernel yet — "
                "ROADMAP open item); training through 'auto' does not "
                "get a kernel-fused backward here. Pass "
                "implementation='xla' to silence this warning.",
                stacklevel=2,
            )
        implementation = "xla"
    if implementation == "ref":
        return _ref.rwkv6_ref(r, k, v, w, u, initial_state=initial_state)
    if implementation == "pallas":
        from repro.kernels import rwkv6_kernel

        return rwkv6_kernel.rwkv6_pallas(
            r, k, v, w, u, initial_state=initial_state, chunk=chunk,
            interpret=_interpret(),
        )
    return _rwkv6_chunked_xla(
        r, k, v, w, u, initial_state=initial_state, chunk=chunk
    )


def _rwkv6_chunked_xla(r, k, v, w, u, *, initial_state=None, chunk=64):
    """Chunked-parallel WKV6 (the XLA perf path).

    Within a chunk of length c, with cumulative decay products
    A_t = prod_{s<=t} w_s (per channel):

        intra: o_t  = sum_{s<t} (r_t * A_t / A_s) . k_s v_s + r_t.(u*k_t) v_t
        inter: o_t += (r_t * A_t / w_t^{0}) ... handled as r_t A_t . S_in
        state: S_out = A_c * S_in + sum_s (A_c / A_s) k_s v_s

    All divisions guarded in log space: w in (0,1) so log w < 0.
    """
    B, T, H, K = r.shape
    V = v.shape[-1]
    f32 = jnp.float32
    c = min(chunk, T)
    pad = (-T) % c
    if pad:
        zero = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v = zero(r), zero(k), zero(v)
        # pad decay with ones (identity)
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0), (0, 0)),
                    constant_values=1.0)
    Tp = T + pad
    n = Tp // c
    if initial_state is None:
        initial_state = jnp.zeros((B, H, K, V), f32)

    rs = r.reshape(B, n, c, H, K).astype(f32)
    ks = k.reshape(B, n, c, H, K).astype(f32)
    vs = v.reshape(B, n, c, H, V).astype(f32)
    ws = w.reshape(B, n, c, H, K).astype(f32)
    u32 = u.astype(f32)

    logw = jnp.log(jnp.clip(ws, 1e-12, 1.0))
    # A[t] = prod_{s<=t} w_s  (inclusive); computed in log space.
    logA = jnp.cumsum(logw, axis=2)  # (B, n, c, H, K)

    def chunk_step(S, xs):
        rc, kc, vc, logAc, logwc = xs  # (B, c, H, *)
        Ac = jnp.exp(logAc)
        # inter-chunk: o_inter[t] = (r_t * A[t-1]... note state S holds
        # contributions strictly before the chunk, decayed to chunk start.
        # Here decay-to-t of S is A[t] excluding w_t? The recurrence applies
        # decay before adding kv at step t: S_t = w_t S_{t-1} + k_t v_t, and
        # o_t reads S_{t-1} + u k_t v_t ... with o_t = r.(S_{t-1}+u kv_t):
        # contribution of S_in to o_t is r_t * (A[t]/w_t ... = A[t-1]) S_in.
        logA_prev = logAc - logwc  # A[t-1] inclusive-prod trick
        o_inter = jnp.einsum(
            "bchk,bhkv->bchv", rc * jnp.exp(logA_prev), S
        )
        # intra-chunk (s < t): weight A[t-1]/A[s]
        ratio = logA_prev[:, :, None] - logAc[:, None, :]  # (B,t,s,H,K)
        mask = (jnp.arange(c)[:, None] > jnp.arange(c)[None, :])
        decay = jnp.exp(
            jnp.where(mask[None, :, :, None, None], ratio, -jnp.inf)
        )
        att = jnp.einsum("bthk,btshk,bshk->btsh", rc, decay, kc)
        o_intra = jnp.einsum("btsh,bshv->bthv", att, vc)
        # diagonal (s == t) with bonus u
        o_diag = jnp.einsum("bthk,hk,bthk,bthv->bthv", rc, u32, kc, vc)
        o = o_inter + o_intra + o_diag
        # state update: S_out = A[c-1] * S + sum_s (A[c-1]/A[s]) k_s v_s
        logA_end = logAc[:, -1][:, None]  # (B,1,H,K)
        carry_w = jnp.exp(logA_end - logAc)  # (B,c,H,K)
        S = jnp.exp(logA_end[:, 0])[..., None] * S + jnp.einsum(
            "bshk,bshv->bhkv", kc * carry_w, vc
        )
        return S, o

    xs = tuple(
        jnp.moveaxis(t, 1, 0)
        for t in (rs, ks, vs, logA, logw)
    )
    S, out = jax.lax.scan(chunk_step, initial_state.astype(f32), xs)
    out = jnp.moveaxis(out, 0, 1).reshape(B, Tp, H, V)
    if pad:
        out = out[:, :T]
    return out.astype(v.dtype), S
