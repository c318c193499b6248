"""GQA attention with chunked online-softmax ("flash") compute.

The jnp implementation here is the XLA path used for training/prefill
lowering: memory is O(q_chunk * kv_chunk) per (batch, head) instead of
O(S^2), so the 32k-prefill dry-run memory analysis is meaningful. The
Pallas TPU kernel (repro/kernels/flash_attention.py) implements the same
math with explicit VMEM BlockSpecs; `ops.flash_attention` selects between
them.

Shapes: q (B, Sq, H, dh); k, v (B, Skv, Kh, dh) with H % Kh == 0 (GQA).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from repro.models import param as pm
from repro.models.layers import rope

NEG_INF = float("-inf")


@dataclasses.dataclass(frozen=True)
class MixedMeta:
    """Lane layout of the fused decode + chunked-prefill serve step.

    The mixed step's row batch is ``R = num_decode + num_chunks *
    chunk_tokens`` single-token rows: rows ``[:num_decode]`` are the
    decode lane (one per slot, position = tokens already cached — 0
    marks a free/prefilling slot), the rest are ``num_chunks`` chunk
    lanes of ``chunk_tokens`` consecutive prompt tokens each.
    ``chunk_lens`` (NC,) counts the valid rows per chunk (0 = idle
    lane). Per-row absolute positions travel as ``cache_index`` and
    per-row block tables as ``block_tables`` — this object only adds
    what cannot be derived from them.

    Speculative verify lanes extend the layout to ``R = num_decode +
    num_verify * verify_tokens + num_chunks * chunk_tokens``: rows
    ``[num_decode : num_decode + num_verify * verify_tokens]`` are
    ``num_verify`` verify lanes of ``verify_tokens`` consecutive
    positions each (pending token + k drafted tokens of one slot),
    attention-wise identical to chunk lanes — multi-query rows against
    the slot's block table, each row attending pool positions <= its
    own. ``verify_lens`` (NV,) counts valid rows per lane (0 = slot
    not verifying this tick; its rows scatter to the trash block).
    """

    num_decode: int
    num_chunks: int
    chunk_tokens: int
    chunk_lens: jax.Array  # (num_chunks,) int32
    num_verify: int = 0
    verify_tokens: int = 0
    verify_lens: Optional[jax.Array] = None  # (num_verify,) int32


def attention_init(rng, cfg: ArchConfig, *, dtype=jnp.float32):
    d, h, kh, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 4)
    p = {
        "wq": pm.dense(ks[0], (d, h, dh), "embed heads head_dim", dtype=dtype,
                       fan_in=d),
        "wk": pm.dense(ks[1], (d, kh, dh), "embed kv_heads head_dim",
                       dtype=dtype, fan_in=d),
        "wv": pm.dense(ks[2], (d, kh, dh), "embed kv_heads head_dim",
                       dtype=dtype, fan_in=d),
        "wo": pm.dense(
            ks[3], (h, dh, d), "heads head_dim embed", dtype=dtype,
            fan_in=h * dh,
        ),
    }
    if cfg.qkv_bias:
        p["bq"] = pm.zeros((h, dh), "heads head_dim", dtype=dtype)
        p["bk"] = pm.zeros((kh, dh), "kv_heads head_dim", dtype=dtype)
        p["bv"] = pm.zeros((kh, dh), "kv_heads head_dim", dtype=dtype)
    return p


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset: jax.Array | int = 0,
    kv_len: Optional[jax.Array] = None,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
) -> jax.Array:
    """Online-softmax attention; O(q_chunk*kv_chunk) live scores.

    q_offset: absolute position of q[0] (for causal masking during decode).
    kv_len: number of valid kv positions (cache may be padded).
    """
    B, Sq, H, dh = q.shape
    _, Skv, Kh, _ = k.shape
    G = H // Kh
    scale = dh ** -0.5

    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    # Pad to chunk multiples (model seq lens are powers of two; padding is a
    # no-op there but keeps odd test shapes working).
    pad_q = (-Sq) % q_chunk
    pad_kv = (-Skv) % kv_chunk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    Sq_p, Skv_p = Sq + pad_q, Skv + pad_kv
    if kv_len is None:
        kv_len = jnp.asarray(Skv, jnp.int32)

    # (B, Kh, G, S, dh) grouped-query layout.
    qg = q.reshape(B, Sq_p, Kh, G, dh).transpose(0, 2, 3, 1, 4)
    kg = k.transpose(0, 2, 1, 3)  # (B, Kh, Skv, dh)
    vg = v.transpose(0, 2, 1, 3)

    nq = Sq_p // q_chunk
    nkv = Skv_p // kv_chunk
    q_pos_base = jnp.asarray(q_offset, jnp.int32)

    qg = qg.reshape(B, Kh, G, nq, q_chunk, dh).transpose(3, 0, 1, 2, 4, 5)
    kg = kg.reshape(B, Kh, nkv, kv_chunk, dh).transpose(2, 0, 1, 3, 4)
    vg = vg.reshape(B, Kh, nkv, kv_chunk, dh).transpose(2, 0, 1, 3, 4)

    def q_block(args):
        qb, iq = args  # qb: (B, Kh, G, qc, dh)
        q_pos = q_pos_base + iq * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, xs):
            m, l, acc = carry
            kb, vb, ikv = xs  # kb: (B, Kh, kc, dh)
            kv_pos = ikv * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum(
                "bkgqd,bktd->bkgqt", qb, kb,
                preferred_element_type=jnp.float32,
            ) * scale
            mask = kv_pos[None, :] < kv_len  # valid kv
            if causal:
                mask = mask & (kv_pos[None, :] <= q_pos[:, None])
            else:
                mask = jnp.broadcast_to(mask, (q_chunk, kv_chunk))
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # Rows with no valid key yet keep m == -inf; guard the exp.
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(mask[None, None, None], p, 0.0)
            alpha = jnp.where(
                jnp.isfinite(m), jnp.exp(m - m_safe), 0.0
            )
            l = l * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqt,bktd->bkgqd", p, vb,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l, acc), None

        m0 = jnp.full((B, Kh, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Kh, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Kh, G, q_chunk, dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (kg, vg, jnp.arange(nkv))
        )
        l = jnp.where(l == 0.0, 1.0, l)
        return acc / l[..., None]

    out = jax.lax.map(q_block, (qg, jnp.arange(nq)))  # (nq,B,Kh,G,qc,dh)
    out = out.transpose(1, 2, 3, 0, 4, 5).reshape(B, Kh, G, Sq_p, dh)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq_p, H, dh)
    if pad_q:
        out = out[:, :Sq]
    return out.astype(q.dtype)


def reference_attention(q, k, v, *, causal=True, q_offset=0, kv_len=None):
    """O(S^2)-memory oracle for tests."""
    B, Sq, H, dh = q.shape
    _, Skv, Kh, _ = k.shape
    G = H // Kh
    qg = q.reshape(B, Sq, Kh, G, dh)
    s = jnp.einsum(
        "bqkgd,btkd->bkgqt", qg, k, preferred_element_type=jnp.float32
    ) * dh ** -0.5
    q_pos = jnp.asarray(q_offset) + jnp.arange(Sq)
    kv_pos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if kv_len is not None:
        mask = mask & (kv_pos[None, :] < kv_len)
    if causal:
        mask = mask & (kv_pos[None, :] <= q_pos[:, None])
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bkgqt,btkd->bqkgd", p, v, preferred_element_type=jnp.float32
    )
    return out.reshape(B, Sq, H, dh).astype(q.dtype)


@jax.named_scope("attn")
def attention_apply(
    p,
    x,
    cfg: ArchConfig,
    *,
    positions=None,
    causal: bool = True,
    cache=None,
    cache_index=None,
    kv_x=None,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    ctx=None,
    pad_heads_multiple: int = 0,
    implementation: str = "xla",
    block_tables=None,
    mixed: Optional[MixedMeta] = None,
):
    """Self- or cross-attention.

    cache: None, or dict {k: (B, S_max, Kh, dh), v: ...} — functional KV
    cache. cache_index: current length (traced int32) where new kv is
    written. kv_x: encoder states for cross-attention (no cache/causality).

    block_tables: None, or (B, nb) int32 — switches the cache to the
    PAGED layout {k: (P, Kh, bs, dh), v: ...} (a global block pool,
    repro/serve): ``cache_index`` becomes the per-slot (B,) int32 length
    vector. Prefill (Sq > 1, one request at a time) writes the prompt's
    k/v into the slot's blocks and attends over the local fresh k/v;
    decode scatters one token per live slot and runs
    ``ops.decode_attention`` (the Pallas paged flash-decode kernel when
    ``implementation="pallas"``, the gather + masked-softmax oracle on
    "xla").

    mixed: None, or a :class:`MixedMeta` — the fused decode + chunked-
    prefill step (``Sq == 1``, rows = decode slots then flattened
    chunks). ``cache_index`` carries PER-ROW absolute positions and
    ``block_tables`` per-row tables; all rows write k/v through ONE
    scatter (``paged_row_write`` — dead rows land in the trash block),
    then the decode lane reads via ``ops.decode_attention`` and the
    chunk lanes via ``ops.prefill_attention`` (the q-tile x kv-block
    paged prefill kernel on "pallas").

    implementation: "xla" | "pallas" | "ref" | "auto" — the flash-attention
    compute path (repro.kernels.ops.flash_attention). "pallas" is fully
    differentiable (custom-VJP backward kernels), so training and prefill
    both run through the fused kernels; single-query decode keeps the
    distributed-softmax path regardless (seq-sharded KV caches).

    pad_heads_multiple: zero-pad query heads (and wo) up to a multiple of
    this, so head counts that don't divide the tensor-parallel mesh axis
    (e.g. qwen2.5's 40 heads on a 16-wide axis) still shard — padded heads
    compute garbage attention that is annihilated by the zero wo rows, so
    the function is EXACTLY preserved (tests/test_attention_padding).
    Returns (y, new_cache). Its ops carry the ``attn`` named scope, the
    cache writes ``kv.write`` inside it.
    """
    from repro.sharding import act as _act

    B, Sq, _ = x.shape
    src = x if kv_x is None else kv_x
    wq, wo = p["wq"], p["wo"]
    H = wq.shape[1]
    Kh = p["wk"].shape[1]
    pad_h = 0
    if pad_heads_multiple and H % pad_heads_multiple:
        # Insert zero heads PER KV GROUP so original heads keep their kv
        # group under the (Kh, G) reshape inside flash attention.
        g0 = H // Kh
        g1 = g0
        while (Kh * g1) % pad_heads_multiple:
            g1 += 1
        pad_h = Kh * g1 - H

        def pad_grouped(w, head_axis):
            shape = w.shape
            w = jnp.moveaxis(w, head_axis, 0).reshape(
                (Kh, g0) + shape[:head_axis] + shape[head_axis + 1:]
            )
            w = jnp.pad(
                w, ((0, 0), (0, g1 - g0)) + ((0, 0),) * (w.ndim - 2)
            )
            w = w.reshape((Kh * g1,) + shape[:head_axis]
                          + shape[head_axis + 1:])
            return jnp.moveaxis(w, 0, head_axis)

        wq = pad_grouped(wq, 1)
        wo = pad_grouped(wo, 0)
    q = jnp.einsum("bsd,dhk->bshk", x, wq)
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    if "bq" in p:
        bq = p["bq"] if not pad_h else pad_grouped(p["bq"], 0)
        q, k, v = q + bq, k + p["bk"], v + p["bv"]
    q = _act(ctx, q, "batch seq heads head_dim")
    k = _act(ctx, k, "batch seq kv_heads head_dim")
    v = _act(ctx, v, "batch seq kv_heads head_dim")

    if cfg.pos_emb == "rope" and kv_x is None:
        if positions is None:
            base = jnp.asarray(0 if cache_index is None else cache_index)
            # Per-slot cache indices (paged decode) broadcast to (B, Sq).
            if base.ndim:
                positions = base[:, None] + jnp.arange(Sq)[None]
            else:
                positions = base + jnp.arange(Sq)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    q_offset = 0
    kv_len = None
    paged = block_tables is not None and cache is not None and kv_x is None
    if paged and mixed is not None:
        from repro.kernels import ops

        # Fused decode + verify + chunked-prefill step:
        # R = B_dec + NV*K1 + NC*C rows.
        B_dec, NC, C = (
            mixed.num_decode, mixed.num_chunks, mixed.chunk_tokens
        )
        NV, K1 = mixed.num_verify, mixed.verify_tokens
        v0, c0 = B_dec, B_dec + NV * K1
        pool_k, pool_v = cache["k"], cache["v"]
        positions = cache_index  # (R,) absolute write position per row
        live_parts = []
        if B_dec:
            dec_live = positions[:B_dec] > 0
            live_parts.append(dec_live)
        if NV:
            ver_live = (
                jnp.arange(K1)[None, :] < mixed.verify_lens[:, None]
            )  # (NV, K1)
            live_parts.append(ver_live.reshape(-1))
        if NC:
            chunk_live = (
                jnp.arange(C)[None, :] < mixed.chunk_lens[:, None]
            )  # (NC, C)
            live_parts.append(chunk_live.reshape(-1))
        live = jnp.concatenate(live_parts)
        # ONE cache-write path for all lanes: a single per-row scatter.
        with jax.named_scope("kv.write"):
            new_pk = paged_row_write(pool_k, k, block_tables, positions,
                                     live)
            new_pv = paged_row_write(pool_v, v, block_tables, positions,
                                     live)
        cache = {"k": new_pk, "v": new_pv}
        ys = []
        if B_dec:
            # Decode lane: live slots attend their fresh token too.
            y_dec = ops.decode_attention(
                q[:B_dec], new_pk, new_pv, block_tables[:B_dec],
                positions[:B_dec] + dec_live,
                implementation=implementation,
            )
            ys.append(y_dec)
        if NV:
            # Verify lanes: K1 rows per slot (pending token + drafts),
            # row j attends pool positions <= start + j — the draft
            # prefix written above plus everything already cached.
            qv = q[v0:c0, 0].reshape(NV, K1, *q.shape[2:])
            vtab = block_tables[v0:c0].reshape(NV, K1, -1)[:, 0]
            vstart = positions[v0:c0].reshape(NV, K1)[:, 0]
            y_v = ops.prefill_attention(
                qv, new_pk, new_pv, vtab, vstart, mixed.verify_lens,
                implementation=implementation,
            )
            ys.append(y_v.reshape(NV * K1, 1, *y_v.shape[2:]))
        if NC:
            # Chunk lanes: rows attend every pool position <= their own
            # — prefix blocks, earlier chunks and the chunk itself
            # (written above) are all just block reads.
            qc = q[c0:, 0].reshape(NC, C, *q.shape[2:])
            ctab = block_tables[c0:].reshape(NC, C, -1)[:, 0]
            cstart = positions[c0:].reshape(NC, C)[:, 0]
            y_ch = ops.prefill_attention(
                qc, new_pk, new_pv, ctab, cstart, mixed.chunk_lens,
                implementation=implementation,
            )
            ys.append(y_ch.reshape(NC * C, 1, *y_ch.shape[2:]))
        y = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=0)
        out = jnp.einsum("bshk,hkd->bsd", y, wo)
        return out, cache
    if paged:
        pool_k, pool_v = cache["k"], cache["v"]
        if Sq > 1:
            # Prefill-on-join: one request at a time into its freshly
            # allocated blocks; attention runs over the LOCAL fresh k/v
            # (a fresh sequence — same discipline as the dense prefill).
            if B != 1:
                raise ValueError(
                    "paged prefill admits one request at a time (B == 1)"
                )
            with jax.named_scope("kv.write"):
                cache = {
                    "k": paged_prefill_write(pool_k, k, block_tables),
                    "v": paged_prefill_write(pool_v, v, block_tables),
                }
        else:
            lengths = cache_index  # (B,) tokens already cached per slot
            with jax.named_scope("kv.write"):
                new_pk = paged_decode_write(pool_k, k, block_tables,
                                            lengths)
                new_pv = paged_decode_write(pool_v, v, block_tables,
                                            lengths)
            cache = {"k": new_pk, "v": new_pv}
            from repro.kernels import ops

            # Live slots attend over their freshly written token too;
            # FREE slots (length 0) stay at length 0 — their write went
            # to the trash block, which is never read, and the kernel's
            # zero-valid-key guard gives them exact-zero outputs.
            y = ops.decode_attention(
                q, new_pk, new_pv, block_tables,
                lengths + (lengths > 0),
                implementation=implementation,
            )
            out = jnp.einsum("bshk,hkd->bsd", y, wo)
            return out, cache
    elif cache is not None and kv_x is None:
        with jax.named_scope("kv.write"):
            new_k = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), cache_index, axis=1
            )
            new_v = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), cache_index, axis=1
            )
        cache = {"k": new_k, "v": new_v}
        q_offset = cache_index
        if Sq > 1:
            # Prefill: attend over the LOCAL fresh k/v, not the cache view.
            # The cache is seq-sharded over the `model` axis (decode-optimal
            # layout); chunked flash over that view forces a reshard per
            # (q, kv) tile — the cache write below is ONE reshard per layer
            # instead. Assumes prefill starts from an empty cache
            # (cache_index == 0), which is how prefill() drives it.
            kv_len = None
        else:
            k, v = new_k, new_v
            kv_len = cache_index + Sq

    if pad_h and (q.shape[2] % k.shape[2]) != 0:
        raise ValueError("padded heads must remain a multiple of kv heads")
    if q.shape[1] == 1 and cache is not None:
        # Decode: one query. Direct attention — XLA lowers the reductions
        # over a seq-sharded KV cache to all-reduce (distributed softmax),
        # so 500k caches shard over the `model` axis with no KV gather.
        y = _decode_attention(q, k, v, kv_len)
    else:
        from repro.kernels import ops

        attend = functools.partial(
            ops.flash_attention,
            causal=causal and kv_x is None,
            q_chunk=q_chunk,
            kv_chunk=kv_chunk,
            implementation=implementation,
        )
        if (ops._resolve(implementation) == "pallas" and ctx is not None
                and ctx.mesh.devices.size > 1):
            y = _attend_per_shard(attend, q, k, v, q_offset, kv_len, ctx)
        else:
            y = attend(q, k, v, q_offset=q_offset, kv_len=kv_len)
    out = jnp.einsum("bshk,hkd->bsd", y, wo)
    return out, cache


def _attend_per_shard(attend, q, k, v, q_offset, kv_len, ctx):
    """Run a Pallas attention kernel once per shard of ``ctx.mesh``:
    GSPMD cannot partition a Mosaic kernel. The batch shards as the
    activation rules say; heads shard only where the query and the KV
    heads take the same mesh axis, so every shard holds whole GQA
    groups (local head h still reads local kv head h // G)."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding import spec_for

    mesh, rules = ctx.mesh, ctx.act_rules

    def spec(logical, shape):
        s = tuple(spec_for(logical, shape, mesh, rules))
        return s + (None,) * (len(shape) - len(s))

    qs = spec("batch seq heads head_dim", q.shape)
    ks = spec("batch seq kv_heads head_dim", k.shape)
    if qs[2] != ks[2]:
        qs, ks = qs[:2] + (None, None), ks[:2] + (None, None)
    kv_len = jnp.asarray(k.shape[1] if kv_len is None else kv_len,
                         jnp.int32)
    fn = jax.shard_map(
        lambda q, k, v, o, n: attend(q, k, v, q_offset=o, kv_len=n),
        mesh=mesh,
        in_specs=(P(*qs), P(*ks), P(*ks), P(), P()),
        out_specs=P(*qs),
        check_vma=False,
    )
    return fn(q, k, v, jnp.asarray(q_offset, jnp.int32), kv_len)


def _decode_attention(q, k, v, kv_len):
    """q: (B, 1, H, dh); k, v: (B, S, Kh, dh). Softmax over all valid S.

    ``kv_len`` may be a scalar (the static-batch engine's shared cache
    index) or a per-slot (B,) vector (the continuous-batching engine's
    ragged lengths; 0 marks a free slot and yields an exact-zero output
    instead of a NaN softmax). This is the oracle the Pallas paged
    decode kernel is validated against (``ops.decode_attention``).
    """
    B, _, H, dh = q.shape
    Skv, Kh = k.shape[1], k.shape[2]
    G = H // Kh
    qg = q.reshape(B, Kh, G, dh)
    s = jnp.einsum(
        "bkgd,btkd->bkgt", qg, k, preferred_element_type=jnp.float32
    ) * dh ** -0.5
    mask = (
        jnp.arange(Skv)[None, :]
        < jnp.reshape(jnp.asarray(kv_len), (-1, 1))
    )  # (B, Skv) or (1, Skv)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    # Zero-valid-key-safe softmax (identical to jax.nn.softmax wherever
    # at least one key is valid).
    m = s.max(axis=-1, keepdims=True)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(mask[:, None, None, :], jnp.exp(s - m_safe), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    p = p / jnp.where(l == 0.0, 1.0, l)
    y = jnp.einsum(
        "bkgt,btkd->bkgd", p, v, preferred_element_type=jnp.float32
    )
    return y.reshape(B, 1, H, dh).astype(q.dtype)


def init_cache(cfg: ArchConfig, batch: int, max_len: int, *, dtype=jnp.bfloat16):
    shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


# ---------------------------------------------------------------------------
# paged KV cache (repro/serve continuous-batching engine)
# ---------------------------------------------------------------------------


def init_paged_cache(cfg: ArchConfig, num_blocks: int, block_size: int, *,
                     dtype=jnp.bfloat16):
    """Global KV block pool replacing the dense (B, max_len, ...) cache:
    fixed-size blocks owned by sequence slots via per-slot block tables
    (allocated/freed by repro.serve.BlockPool). Block 0 is the trash
    block free slots write into.

    Head-major ``(num_blocks, Kh, block_size, dh)``: the Pallas paged
    kernels read one ``(block_size, dh)`` slab per (block, kv head), and
    Mosaic tiles only a block's two trailing dims."""
    shape = (num_blocks, cfg.n_kv_heads, block_size, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def paged_prefill_write(pool, kv, block_table):
    """Write a full prompt's k or v into its slot's blocks.

    pool: (P, Kh, bs, dh); kv: (1, S, Kh, dh) with S % bs == 0 (the
    serve engine buckets prompt lengths to block multiples — padded tail
    positions carry garbage that stays masked by the slot length until
    decode overwrites it); block_table: (1, nb), nb >= S // bs.
    """
    bs = pool.shape[2]
    S = kv.shape[1]
    if S % bs:
        raise ValueError(
            f"paged prefill length ({S}) must be a multiple of the "
            f"block size ({bs}); bucket the prompt before prefill"
        )
    nbu = S // bs
    blocks = kv[0].reshape(nbu, bs, *kv.shape[2:]).transpose(0, 2, 1, 3)
    return pool.at[block_table[0, :nbu]].set(blocks.astype(pool.dtype))


def paged_decode_write(pool, kv, block_tables, lengths):
    """Scatter one decode token's k or v per slot into the pool.

    pool: (P, Kh, bs, dh); kv: (B, 1, Kh, dh); block_tables: (B, nb);
    lengths: (B,) write position per slot (the token count already
    cached). Free slots (length 0, all-zero table rows) land in trash
    block 0 — never read.
    """
    bs = pool.shape[2]
    blk = lengths // bs
    bids = jnp.take_along_axis(block_tables, blk[:, None], axis=1)[:, 0]
    return _scatter_rows(pool, kv[:, 0], bids, lengths % bs)


def paged_row_write(pool, kv, row_tables, positions, live):
    """Scatter one token per ROW into the pool at its absolute position
    — the single cache-write path of the mixed serve step (decode rows
    AND chunk rows go through this one scatter).

    pool: (P, Kh, bs, dh); kv: (R, 1, Kh, dh); row_tables: (R, nb) each
    row's slot block table; positions: (R,) absolute token position to
    write; live: (R,) bool — dead rows (free slots, padded chunk rows,
    idle chunk lanes) land in trash block 0, which is never read.
    Positions are clamped into the table so padded rows whose nominal
    position runs past the slot's allocation stay in bounds (they are
    dead and routed to trash anyway).
    """
    bs = pool.shape[2]
    nb = row_tables.shape[1]
    blk = jnp.clip(positions // bs, 0, nb - 1)
    bids = jnp.take_along_axis(row_tables, blk[:, None], axis=1)[:, 0]
    bids = jnp.where(live, bids, 0)
    off = jnp.where(live, positions % bs, 0)
    return _scatter_rows(pool, kv[:, 0], bids, off)


def _scatter_rows(pool, rows, bids, offs):
    """Write token rows (R, Kh, dh) into the head-major pool (P, Kh, bs,
    dh) at (block ``bids[r]``, offset ``offs[r]``) — one scatter over
    every kv head, in place under buffer donation (no pool transpose)."""
    kh = jnp.arange(pool.shape[1])
    return pool.at[bids[:, None], kh[None, :], offs[:, None]].set(
        rows.astype(pool.dtype)
    )


CACHE_AXES = {"k": "batch cache_seq kv_heads head_dim",
              "v": "batch cache_seq kv_heads head_dim"}
