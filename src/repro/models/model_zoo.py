"""Top-level model builders: init / train-forward / loss / prefill / decode
for all architecture families (decoder-only LM, encoder-decoder, encoder-
only ViT), selected purely by ``ArchConfig``.

Batch formats
  decoder_only : {"tokens": (B,S) i32, "targets": (B,S) i32}
                 (+ "patch_embeds": (B,P,d) f for vlm frontends)
  encoder_decoder: {"enc_tokens": (B,Se) i32 | "frames": (B,Se,d) f,
                    "dec_tokens": (B,Sd), "targets": (B,Sd)}
  encoder_only : {"patch_embeds": (B,P,d), "labels": (B,) i32}

Targets use -1 for masked-out positions.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from repro.models import param as pm
from repro.models import stack as stk
from repro.models.layers import (
    embed_apply,
    embed_init,
    frontend_apply,
    frontend_init,
    head_apply,
    head_init,
    norm_apply,
    norm_init,
)
from repro.sharding import ShardCtx, act


@dataclasses.dataclass(frozen=True)
class ApplyCfg:
    """Runtime knobs (everything static at trace time).

    The kernel implementation knobs (moe_impl, attn_impl) default to
    "auto": fused Pallas kernels — forward AND custom-VJP backward — on
    TPU, XLA einsums on CPU. ``resolve()`` pins "auto" to a concrete
    backend at trace time.
    """

    dispatch: str = "gather"  # moe dispatch: gather | einsum | sorted
    # Row-block alignment of the sorted dispatch's ragged buffer. 128
    # matches the grouped-GEMM kernel's MXU tiles (training / TPU); the
    # layout guarantees >= 1 block per expert, so tiny decode batches
    # want a small block (the serve engine picks 8 on the XLA backend —
    # E*128 floor rows for a 16-assignment decode batch otherwise).
    sorted_block: int = 128
    moe_impl: str = "auto"  # auto | xla | pallas | ref
    attn_impl: str = "auto"  # auto | xla | pallas | ref
    mixer_impl: str = "xla"
    remat: str = "none"  # none | full | dots | moe
    compute_dtype: str = "float32"  # float32 | bfloat16
    # Chunked cross-entropy: compute logits+CE in seq chunks under remat so
    # the (B, S, V) logits tensor is never materialized (0 = full logits;
    # beyond-paper memory optimization, see EXPERIMENTS.md SPerf).
    ce_chunk: int = 0
    # Zero-pad attention heads to a multiple of this so indivisible head
    # counts still tensor-parallel shard (0 = off; see models/attention).
    pad_heads_multiple: int = 0

    @property
    def cdtype(self):
        return jnp.bfloat16 if self.compute_dtype == "bfloat16" else jnp.float32

    def resolve(self) -> "ApplyCfg":
        """Pin "auto" impls to the backend default (pallas on TPU, xla on
        CPU). Idempotent; called at every model entry point."""
        from repro.kernels.ops import default_implementation

        if self.moe_impl != "auto" and self.attn_impl != "auto":
            return self
        default = default_implementation()
        return dataclasses.replace(
            self,
            moe_impl=default if self.moe_impl == "auto" else self.moe_impl,
            attn_impl=(
                default if self.attn_impl == "auto" else self.attn_impl
            ),
        )


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(rng, cfg: ArchConfig, *, dtype=jnp.float32):
    """Returns a wrapped (Param-leaf) tree."""
    ks = jax.random.split(rng, 8)
    p = {}
    if cfg.structure == "encoder_only":
        p["frontend"] = frontend_init(ks[0], cfg, dtype=dtype)
        p["pos"] = pm.normal(
            ks[1], (cfg.n_frontend_positions, cfg.d_model), "pos embed",
            std=0.02, dtype=dtype,
        )
        p["stack"] = stk.stack_init(
            ks[2], cfg, stk.layer_descs(cfg, stack="decoder"), dtype=dtype
        )
        p["final_norm"] = norm_init(cfg)
        p["head"] = {
            "w": pm.dense(ks[3], (cfg.d_model, cfg.vocab_size),
                          "embed vocab", dtype=dtype)
        }
        return p

    p["embed"] = embed_init(ks[0], cfg, dtype=dtype)
    if cfg.frontend is not None:
        p["frontend"] = frontend_init(ks[1], cfg, dtype=dtype)
    if cfg.structure == "encoder_decoder":
        p["encoder"] = stk.stack_init(
            ks[2], cfg, stk.layer_descs(cfg, stack="encoder"), dtype=dtype
        )
        p["enc_final_norm"] = norm_init(cfg)
    p["stack"] = stk.stack_init(
        ks[3], cfg, stk.layer_descs(cfg, stack="decoder"), dtype=dtype
    )
    p["final_norm"] = norm_init(cfg)
    p["head"] = head_init(ks[4], cfg, dtype=dtype)
    return p


def _cast_params(params, dtype):
    """Mixed precision: compute with a low-precision view of the weights
    (grads flow through the cast back to the fp32 masters)."""
    return jax.tree.map(
        lambda p: p.astype(dtype)
        if jnp.issubdtype(p.dtype, jnp.floating) else p,
        params,
    )


def _lm_head(params, x, cfg: ArchConfig):
    """Final norm and the (tied) vocabulary projection -> float32 logits,
    under the ``lm_head`` scope."""
    with jax.named_scope("lm_head"):
        x = norm_apply(params["final_norm"], x, cfg)
        return head_apply(
            params.get("head", {}), x, params.get("embed"), cfg
        ).astype(jnp.float32)


# ---------------------------------------------------------------------------
# forward (train / eval)
# ---------------------------------------------------------------------------


def _embed_decoder_input(params, batch, cfg: ArchConfig, ac: ApplyCfg):
    tokens = batch["tokens"] if "tokens" in batch else batch["dec_tokens"]
    S = tokens.shape[1]
    positions = jnp.arange(S)
    x = embed_apply(params["embed"], tokens, cfg, positions=positions)
    if cfg.frontend is not None and "patch_embeds" in batch:
        front = frontend_apply(
            params["frontend"], batch["patch_embeds"], cfg
        ).astype(x.dtype)
        n_front = front.shape[1]
        x = jnp.concatenate([front, x[:, n_front:]], axis=1)
    return x.astype(ac.cdtype)


def _encode(params, batch, cfg: ArchConfig, ac: ApplyCfg, ctx):
    """Encoder stack of enc-dec models."""
    if cfg.frontend == "frame":
        x = frontend_apply(params["frontend"], batch["frames"], cfg)
        from repro.models.layers import sinusoidal

        S = x.shape[1]
        x = x + sinusoidal(jnp.arange(S), cfg.d_model).astype(x.dtype)
    else:
        S = batch["enc_tokens"].shape[1]
        x = embed_apply(
            params["embed"], batch["enc_tokens"], cfg,
            positions=jnp.arange(S),
        )
    x = act(ctx, x.astype(ac.cdtype), "batch seq embed")
    x, mets, _ = stk.stack_apply(
        params["encoder"], x, cfg, stk.layer_descs(cfg, stack="encoder"),
        mode="train", causal=False,
        router_kind=stk.stack_router_kind(cfg, stack="encoder"),
        dispatch=ac.dispatch, sorted_block=ac.sorted_block,
        moe_impl=ac.moe_impl,
        attn_impl=ac.attn_impl,
        mixer_impl=ac.mixer_impl,
        pad_heads_multiple=ac.pad_heads_multiple,
        ctx=ctx, remat=ac.remat,
    )
    return norm_apply(params["enc_final_norm"], x, cfg), mets


def forward_train(
    params,
    batch,
    cfg: ArchConfig,
    *,
    ac: ApplyCfg = ApplyCfg(),
    ctx: Optional[ShardCtx] = None,
    return_hidden: bool = False,
):
    """Returns (logits, metrics); (hidden, metrics) if return_hidden."""
    ac = ac.resolve()
    params = _cast_params(params, ac.cdtype)
    if cfg.structure == "encoder_only":
        x = frontend_apply(params["frontend"], batch["patch_embeds"], cfg)
        x = x + params["pos"][None]
        x = act(ctx, x.astype(ac.cdtype), "batch seq embed")
        x, mets, _ = stk.stack_apply(
            params["stack"], x, cfg,
            stk.layer_descs(cfg, stack="decoder"),
            mode="train", causal=False,
            router_kind=stk.stack_router_kind(cfg, stack="encoder"),
            dispatch=ac.dispatch, sorted_block=ac.sorted_block,
            moe_impl=ac.moe_impl,
            attn_impl=ac.attn_impl,
            mixer_impl=ac.mixer_impl, ctx=ctx, remat=ac.remat,
        )
        with jax.named_scope("lm_head"):
            x = norm_apply(params["final_norm"], x, cfg)
            pooled = x.mean(axis=1)  # global average pooling (paper §2.2)
            logits = jnp.einsum(
                "bd,dv->bv", pooled, params["head"]["w"]
            ).astype(jnp.float32)
        return logits, mets

    enc = None
    enc_mets = stk.zero_metrics()
    if cfg.structure == "encoder_decoder":
        enc, enc_mets = _encode(params, batch, cfg, ac, ctx)

    x = _embed_decoder_input(params, batch, cfg, ac)
    x = act(ctx, x, "batch seq embed")
    x, mets, _ = stk.stack_apply(
        params["stack"], x, cfg, stk.layer_descs(cfg, stack="decoder"),
        enc=enc, mode="train", causal=True,
        router_kind=stk.stack_router_kind(cfg, stack="decoder"),
        dispatch=ac.dispatch, sorted_block=ac.sorted_block,
        moe_impl=ac.moe_impl,
        attn_impl=ac.attn_impl,
        mixer_impl=ac.mixer_impl,
        pad_heads_multiple=ac.pad_heads_multiple,
        ctx=ctx, remat=ac.remat,
    )
    with jax.named_scope("lm_head"):
        x = norm_apply(params["final_norm"], x, cfg)
    mets = jax.tree.map(jnp.add, mets, enc_mets)
    if return_hidden:
        return x, mets
    with jax.named_scope("lm_head"):
        logits = head_apply(
            params.get("head", {}), x, params["embed"], cfg
        ).astype(jnp.float32)
        logits = act(ctx, logits, "batch seq vocab")
    return logits, mets


def loss_fn(
    params,
    batch,
    cfg: ArchConfig,
    *,
    ac: ApplyCfg = ApplyCfg(),
    ctx: Optional[ShardCtx] = None,
):
    """Returns (loss, metrics-dict). CE + weighted MoE aux losses."""
    if cfg.structure == "encoder_only":
        logits, mets = forward_train(params, batch, cfg, ac=ac, ctx=ctx)
        labels = batch["labels"]
        with jax.named_scope("loss"):
            ce = -jnp.mean(
                jnp.take_along_axis(
                    jax.nn.log_softmax(logits), labels[:, None], axis=-1
                )
            )
    elif ac.ce_chunk:
        hidden, mets = forward_train(
            params, batch, cfg, ac=ac, ctx=ctx, return_hidden=True
        )
        w = (
            params["embed"]["tokens"].T
            if cfg.tie_embeddings
            else params["head"]["w"]
        ).astype(ac.cdtype)
        ce = _chunked_ce(hidden, w, batch["targets"], ac.ce_chunk)
    else:
        logits, mets = forward_train(params, batch, cfg, ac=ac, ctx=ctx)
        targets = batch["targets"]
        with jax.named_scope("loss"):
            valid = targets >= 0
            tgt = jnp.maximum(targets, 0)
            logp = jax.nn.log_softmax(logits)
            ce_tok = -jnp.take_along_axis(
                logp, tgt[..., None], axis=-1)[..., 0]
            denom = jnp.maximum(valid.sum(), 1)
            ce = jnp.where(valid, ce_tok, 0.0).sum() / denom
    loss = ce + mets["aux_loss"] + mets["z_loss"]
    out = dict(mets)
    out.update(loss=loss, ce=ce)
    return loss, out


def _chunked_ce(hidden, w, targets, chunk: int):
    """CE over seq chunks with per-chunk logits rematerialization.

    hidden: (B, S, d); w: (d, V); targets: (B, S) with -1 = masked.
    Never materializes (B, S, V): each chunk computes its logits, reduces
    to per-token CE, and the backward pass recomputes them (jax.checkpoint
    around the chunk body).
    """
    B, S, d = hidden.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)),
                          constant_values=-1)
    nc = (S + pad) // chunk
    hc = hidden.reshape(B, nc, chunk, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, nc, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def body(carry, xs):
        ce_sum, n = carry
        xch, tch = xs
        with jax.named_scope("lm_head"):
            logits = jnp.einsum(
                "bsd,dv->bsv", xch, w, preferred_element_type=jnp.float32
            )
        with jax.named_scope("loss"):
            valid = tch >= 0
            tgt = jnp.maximum(tch, 0)
            logp = jax.nn.log_softmax(logits)
            ce_tok = -jnp.take_along_axis(
                logp, tgt[..., None], axis=-1)[..., 0]
            ce_sum = ce_sum + jnp.where(valid, ce_tok, 0.0).sum()
            n = n + valid.sum()
        return (ce_sum, n), None

    (ce_sum, n), _ = jax.lax.scan(
        body, (jnp.zeros(()), jnp.zeros((), jnp.int32)), (hc, tc)
    )
    return ce_sum / jnp.maximum(n, 1)


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------


def init_serve_cache(
    cfg: ArchConfig, batch: int, max_len: int, *, dtype=jnp.bfloat16,
    enc_len: int = 0,
):
    descs = stk.layer_descs(cfg, stack="decoder")
    cache = {"stack": stk.stack_cache_init(cfg, descs, batch, max_len,
                                           dtype=dtype)}
    if cfg.structure == "encoder_decoder":
        cache["enc"] = jnp.zeros((batch, enc_len, cfg.d_model), dtype)
    return cache


def init_paged_serve_cache(
    cfg: ArchConfig, num_blocks: int, block_size: int, *,
    dtype=jnp.bfloat16,
):
    """Paged serve cache: per-layer KV block pools addressed by shared
    per-slot block tables (repro/serve continuous-batching engine).

    Paged serving is decoder-only + attention-only: encoder-decoder
    models carry a dense encoder cache and mamba/rwkv6 mixers keep
    per-slot state vectors with no seq dim to page — both raise here
    (serve them through the static-batch engine instead)."""
    if cfg.structure != "decoder_only":
        raise ValueError(
            "paged serving supports decoder-only models; "
            f"{cfg.name} is {cfg.structure}"
        )
    descs = stk.layer_descs(cfg, stack="decoder")
    if any(d.mixer != "attn" for d in descs):
        raise ValueError(
            "paged serving requires an attention-only decoder stack "
            f"(got {sorted({d.mixer for d in descs})} in {cfg.name})"
        )
    return {
        "stack": stk.stack_paged_cache_init(
            cfg, descs, num_blocks, block_size, dtype=dtype
        )
    }


def paged_prefill(
    params,
    tokens,
    cache,
    block_table,
    length,
    cfg: ArchConfig,
    *,
    ac: ApplyCfg = ApplyCfg(),
    ctx: Optional[ShardCtx] = None,
):
    """Prefill ONE request into its freshly allocated KV blocks
    (continuous batching's prefill-on-join).

    tokens: (1, Sp) right-padded prompt with Sp a multiple of the block
    size (the engine buckets prompt lengths — padded tail k/v land in
    the slot's own blocks and stay masked by ``length`` until decode
    overwrites them); block_table: (1, nb) pool block ids; length:
    traced int32 true prompt length. Returns (cache, logits (1, 1, V))
    — the logits at the TRUE last prompt position (length - 1), not the
    padded one.
    """
    ac = ac.resolve()
    params = _cast_params(params, ac.cdtype)
    x = _embed_decoder_input(params, {"tokens": tokens}, cfg, ac)
    x = act(ctx, x, "batch seq embed")
    x, _, stack_cache = stk.stack_apply(
        params["stack"], x, cfg, stk.layer_descs(cfg, stack="decoder"),
        cache=cache["stack"],
        cache_index=jnp.zeros((1,), jnp.int32),
        block_tables=block_table,
        mode="prefill", causal=True,
        router_kind=stk.stack_router_kind(cfg, stack="decoder"),
        dispatch=ac.dispatch, sorted_block=ac.sorted_block,
        moe_impl=ac.moe_impl,
        attn_impl=ac.attn_impl,
        mixer_impl=ac.mixer_impl,
        pad_heads_multiple=ac.pad_heads_multiple,
        ctx=ctx, remat="none",
    )
    new_cache = dict(cache)
    new_cache["stack"] = stack_cache
    with jax.named_scope("sample"):
        x_last = jax.lax.dynamic_slice_in_dim(
            x, jnp.asarray(length, jnp.int32) - 1, 1, axis=1
        )
    return new_cache, _lm_head(params, x_last, cfg)


def paged_decode_step(
    params,
    tokens,
    cache,
    block_tables,
    lengths,
    cfg: ArchConfig,
    *,
    ac: ApplyCfg = ApplyCfg(),
    ctx: Optional[ShardCtx] = None,
):
    """One continuous-batching decode step over the slot batch.

    tokens: (B, 1) current token per slot; block_tables: (B, nb);
    lengths: (B,) int32 tokens already cached per slot — 0 marks a FREE
    slot: its token is masked out of MoE routing (no capacity claims,
    no grouped-GEMM rows — expert compute scales with live slots), its
    cache write lands in the trash block, and its logits are garbage the
    engine never samples. Returns (cache, logits (B, 1, V)).
    """
    ac = ac.resolve()
    params = _cast_params(params, ac.cdtype)
    live = lengths > 0
    x = embed_apply(
        params["embed"], tokens, cfg, positions=lengths[:, None]
    ).astype(ac.cdtype)
    x = act(ctx, x, "batch seq embed")
    x, _, stack_cache = stk.stack_apply(
        params["stack"], x, cfg, stk.layer_descs(cfg, stack="decoder"),
        cache=cache["stack"], cache_index=lengths,
        block_tables=block_tables,
        token_mask=live[:, None],
        mode="decode", causal=True,
        router_kind=stk.stack_router_kind(cfg, stack="decoder"),
        dispatch=ac.dispatch, sorted_block=ac.sorted_block,
        moe_impl=ac.moe_impl,
        attn_impl=ac.attn_impl,
        mixer_impl=ac.mixer_impl,
        pad_heads_multiple=ac.pad_heads_multiple,
        ctx=ctx, remat="none",
    )
    new_cache = dict(cache)
    new_cache["stack"] = stack_cache
    return new_cache, _lm_head(params, x, cfg)


def paged_mixed_step(
    params,
    dec_tokens,
    chunk_tokens,
    cache,
    dec_tables,
    dec_lengths,
    chunk_tables,
    chunk_starts,
    chunk_lens,
    cfg: ArchConfig,
    *,
    ac: ApplyCfg = ApplyCfg(),
    ctx: Optional[ShardCtx] = None,
):
    """One fused continuous-batching step: the decode batch AND the
    pending prefill chunks through a SINGLE forward (one jit signature
    per engine — no per-admission B=1 prefill, no bucketed-length
    compile zoo).

    dec_tokens: (B, 1) current token per decode slot; dec_lengths: (B,)
    tokens already cached (0 = slot free or still prefilling -> masked
    out of routing, write lands in the trash block); dec_tables: (B, nb)
    — rows of non-decoding slots must be zeroed by the engine.
    chunk_tokens: (NC, C) — NC chunk lanes of C consecutive prompt
    tokens each; chunk_tables: (NC, nb) the owning slot's block table;
    chunk_starts: (NC,) absolute position of the chunk's first token;
    chunk_lens: (NC,) valid tokens in the lane (0 = idle lane).

    The row batch is R = B + NC*C single-token rows. All rows write
    their k/v through one paged scatter; decode rows read via the paged
    flash-decode kernel, chunk rows via the paged prefill kernel
    (models/attention mixed mode). MoE routes with dead rows masked, so
    expert FLOPs track live tokens: decode rows ride the live-token
    sorted dispatch, chunk rows keep expert work dense.

    Returns ``(cache, logits (B + NC, V))``: rows [:B] are the decode
    slots' next-token logits, rows [B:] each chunk lane's logits at its
    LAST valid row — the engine samples a request's first token from
    them when a chunk completes the prompt. One array so the engine
    pays ONE host sync per mixed step.
    """
    ac = ac.resolve()
    params = _cast_params(params, ac.cdtype)
    B = dec_tokens.shape[0]
    NC, C = chunk_tokens.shape
    dec_lengths = dec_lengths.astype(jnp.int32)
    chunk_starts = chunk_starts.astype(jnp.int32)
    chunk_lens = chunk_lens.astype(jnp.int32)
    dec_live = dec_lengths > 0
    chunk_live = jnp.arange(C)[None, :] < chunk_lens[:, None]  # (NC, C)
    tokens = jnp.concatenate(
        [dec_tokens.reshape(B), chunk_tokens.reshape(NC * C)]
    )[:, None].astype(jnp.int32)  # (R, 1)
    positions = jnp.concatenate([
        dec_lengths,
        (chunk_starts[:, None] + jnp.arange(C)[None, :]).reshape(NC * C),
    ]).astype(jnp.int32)  # (R,)
    row_tables = jnp.concatenate(
        [dec_tables, jnp.repeat(chunk_tables, C, axis=0)], axis=0
    ).astype(jnp.int32)  # (R, nb)
    token_mask = jnp.concatenate(
        [dec_live, chunk_live.reshape(NC * C)]
    )[:, None]
    from repro.models.attention import MixedMeta

    x = embed_apply(
        params["embed"], tokens, cfg, positions=positions[:, None]
    ).astype(ac.cdtype)
    x = act(ctx, x, "batch seq embed")
    x, _, stack_cache = stk.stack_apply(
        params["stack"], x, cfg, stk.layer_descs(cfg, stack="decoder"),
        cache=cache["stack"], cache_index=positions,
        block_tables=row_tables,
        token_mask=token_mask,
        mixed=MixedMeta(
            num_decode=B, num_chunks=NC, chunk_tokens=C,
            chunk_lens=chunk_lens,
        ),
        mode="decode", causal=True,
        router_kind=stk.stack_router_kind(cfg, stack="decoder"),
        dispatch=ac.dispatch, sorted_block=ac.sorted_block,
        moe_impl=ac.moe_impl,
        attn_impl=ac.attn_impl,
        mixer_impl=ac.mixer_impl,
        pad_heads_multiple=ac.pad_heads_multiple,
        ctx=ctx, remat="none",
    )
    new_cache = dict(cache)
    new_cache["stack"] = stack_cache
    # Head only over the rows the engine samples: the B decode rows plus
    # each chunk lane's last valid row (the TRUE last prompt position
    # when the chunk completes a prompt).
    d = x.shape[-1]
    with jax.named_scope("sample"):
        xd = x[:B, 0]
        last = jnp.clip(chunk_lens - 1, 0, C - 1)
        xc = x[B:, 0].reshape(NC, C, d)[jnp.arange(NC), last]
        h = jnp.concatenate([xd, xc], axis=0)[:, None]  # (B + NC, 1, d)
    return new_cache, _lm_head(params, h, cfg)[:, 0]


def paged_verify_step(
    params,
    verify_tokens,
    chunk_tokens,
    cache,
    verify_tables,
    verify_starts,
    verify_lens,
    chunk_tables,
    chunk_starts,
    chunk_lens,
    cfg: ArchConfig,
    *,
    ac: ApplyCfg = ApplyCfg(),
    ctx: Optional[ShardCtx] = None,
):
    """One fused speculative-verify + chunked-prefill step: the target
    model scores B verify lanes of K1 = k+1 positions each (the slot's
    pending token plus its k drafted tokens) AND the pending prefill
    chunks through a SINGLE forward (one jit signature per engine).

    verify_tokens: (B, K1) per slot [pending, d_1..d_k] right-padded;
    verify_tables: (B, nb) the slot's block table (zeroed for slots not
    verifying); verify_starts: (B,) tokens already cached (the pending
    token's write position); verify_lens: (B,) valid rows per lane,
    1 + k_eff, 0 = slot idle this tick. chunk_*: exactly as in
    :func:`paged_mixed_step`.

    The row batch is R = B*K1 + NC*C single-token rows, all sharing the
    one paged k/v scatter; verify rows read via the paged prefill
    kernel (row j attends positions <= starts + j), so verification IS
    a chunk-lane pass over already-drafted tokens. Dead rows (beyond
    verify_lens, idle lanes) scatter to the trash block and are masked
    out of MoE routing — rejected drafts leak no pool state because the
    engine simply rewinds ``slot.length``; stale rows past the new
    length are never attended and get overwritten by later writes.

    Returns ``(cache, logits (B*K1 + NC, V))``: rows [:B*K1] are the
    target logits at EVERY verify position (row b*K1 + j scores the
    token following verify_tokens[b, j]), rows [B*K1:] each chunk
    lane's last-valid-row logits. One array, one host sync per step.
    """
    ac = ac.resolve()
    params = _cast_params(params, ac.cdtype)
    B, K1 = verify_tokens.shape
    NC, C = chunk_tokens.shape
    verify_starts = verify_starts.astype(jnp.int32)
    verify_lens = verify_lens.astype(jnp.int32)
    chunk_starts = chunk_starts.astype(jnp.int32)
    chunk_lens = chunk_lens.astype(jnp.int32)
    ver_live = jnp.arange(K1)[None, :] < verify_lens[:, None]  # (B, K1)
    chunk_live = jnp.arange(C)[None, :] < chunk_lens[:, None]  # (NC, C)
    tokens = jnp.concatenate(
        [verify_tokens.reshape(B * K1), chunk_tokens.reshape(NC * C)]
    )[:, None].astype(jnp.int32)  # (R, 1)
    positions = jnp.concatenate([
        (verify_starts[:, None] + jnp.arange(K1)[None, :]).reshape(B * K1),
        (chunk_starts[:, None] + jnp.arange(C)[None, :]).reshape(NC * C),
    ]).astype(jnp.int32)  # (R,)
    row_tables = jnp.concatenate([
        jnp.repeat(verify_tables, K1, axis=0),
        jnp.repeat(chunk_tables, C, axis=0),
    ], axis=0).astype(jnp.int32)  # (R, nb)
    token_mask = jnp.concatenate(
        [ver_live.reshape(B * K1), chunk_live.reshape(NC * C)]
    )[:, None]
    from repro.models.attention import MixedMeta

    x = embed_apply(
        params["embed"], tokens, cfg, positions=positions[:, None]
    ).astype(ac.cdtype)
    x = act(ctx, x, "batch seq embed")
    x, _, stack_cache = stk.stack_apply(
        params["stack"], x, cfg, stk.layer_descs(cfg, stack="decoder"),
        cache=cache["stack"], cache_index=positions,
        block_tables=row_tables,
        token_mask=token_mask,
        mixed=MixedMeta(
            num_decode=0, num_chunks=NC, chunk_tokens=C,
            chunk_lens=chunk_lens,
            num_verify=B, verify_tokens=K1, verify_lens=verify_lens,
        ),
        mode="decode", causal=True,
        router_kind=stk.stack_router_kind(cfg, stack="decoder"),
        dispatch=ac.dispatch, sorted_block=ac.sorted_block,
        moe_impl=ac.moe_impl,
        attn_impl=ac.attn_impl,
        mixer_impl=ac.mixer_impl,
        pad_heads_multiple=ac.pad_heads_multiple,
        ctx=ctx, remat="none",
    )
    new_cache = dict(cache)
    new_cache["stack"] = stack_cache
    # Head over ALL verify rows (the engine needs the target
    # distribution at every drafted position for rejection sampling)
    # plus each chunk lane's last valid row.
    d = x.shape[-1]
    with jax.named_scope("sample"):
        xv = x[: B * K1, 0]
        last = jnp.clip(chunk_lens - 1, 0, C - 1)
        xc = x[B * K1:, 0].reshape(NC, C, d)[jnp.arange(NC), last]
        h = jnp.concatenate([xv, xc], axis=0)[:, None]  # (B*K1 + NC, 1, d)
    return new_cache, _lm_head(params, h, cfg)[:, 0]


def serve_cache_axes(cfg: ArchConfig):
    descs = stk.layer_descs(cfg, stack="decoder")
    axes = {"stack": stk.stack_cache_axes(descs)}
    if cfg.structure == "encoder_decoder":
        axes["enc"] = "batch seq embed"
    return axes


def prefill(
    params,
    batch,
    cache,
    cfg: ArchConfig,
    *,
    ac: ApplyCfg = ApplyCfg(),
    ctx: Optional[ShardCtx] = None,
):
    """Run the full prompt, writing caches. Returns (cache, last_logits)."""
    ac = ac.resolve()
    params = _cast_params(params, ac.cdtype)
    enc = None
    if cfg.structure == "encoder_decoder":
        enc, _ = _encode(params, batch, cfg, ac, ctx)
        cache = dict(cache)
        cache["enc"] = enc.astype(cache["enc"].dtype)
    x = _embed_decoder_input(params, batch, cfg, ac)
    x = act(ctx, x, "batch seq embed")
    x, _, stack_cache = stk.stack_apply(
        params["stack"], x, cfg, stk.layer_descs(cfg, stack="decoder"),
        enc=enc, cache=cache["stack"], cache_index=jnp.asarray(0, jnp.int32),
        mode="prefill", causal=True,
        router_kind=stk.stack_router_kind(cfg, stack="decoder"),
        dispatch=ac.dispatch, sorted_block=ac.sorted_block,
        moe_impl=ac.moe_impl,
        attn_impl=ac.attn_impl,
        mixer_impl=ac.mixer_impl,
        pad_heads_multiple=ac.pad_heads_multiple,
        ctx=ctx, remat=ac.remat,
    )
    new_cache = dict(cache)
    new_cache["stack"] = stack_cache
    return new_cache, _lm_head(params, x[:, -1:], cfg)


def decode_step(
    params,
    tokens,
    cache,
    cache_index,
    cfg: ArchConfig,
    *,
    ac: ApplyCfg = ApplyCfg(),
    ctx: Optional[ShardCtx] = None,
):
    """One autoregressive step. tokens: (B, 1). Returns (cache, logits)."""
    ac = ac.resolve()
    params = _cast_params(params, ac.cdtype)
    enc = cache.get("enc") if cfg.structure == "encoder_decoder" else None
    x = embed_apply(
        params["embed"], tokens, cfg,
        positions=cache_index + jnp.arange(1),
    ).astype(ac.cdtype)
    x, _, stack_cache = stk.stack_apply(
        params["stack"], x, cfg, stk.layer_descs(cfg, stack="decoder"),
        enc=None if enc is None else enc.astype(ac.cdtype),
        cache=cache["stack"], cache_index=cache_index,
        mode="decode", causal=True,
        router_kind=stk.stack_router_kind(cfg, stack="decoder"),
        dispatch=ac.dispatch, sorted_block=ac.sorted_block,
        moe_impl=ac.moe_impl,
        attn_impl=ac.attn_impl,
        mixer_impl=ac.mixer_impl,
        pad_heads_multiple=ac.pad_heads_multiple,
        ctx=ctx, remat="none",
    )
    new_cache = dict(cache)
    new_cache["stack"] = stack_cache
    return new_cache, _lm_head(params, x, cfg)
