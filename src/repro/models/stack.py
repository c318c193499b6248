"""Layer stacks: descriptors, segment (superblock) detection, scan-over-
layers application.

A stack is a list of ``LayerDesc`` (mixer x ffn x cross). Heterogeneous
layouts (jamba's 1-attn:7-mamba, paper's every-other MoE, last-half MoE)
are factored into *segments*: maximal runs with a repeating period. Params
of each segment position are stacked over repeats and applied with
``lax.scan`` — one traced layer body per position regardless of depth, so
a 72-layer jamba compiles as one 8-position superblock scanned 9 times.

The same desc machinery drives the upcycling surgery (core/upcycle.py):
dense parent and sparse target enumerate layers identically, so parameter
mapping is positional and exact.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig, MoECfg
from repro.core.moe import moe_apply, moe_init
from repro.models import param as pm
from repro.models import rwkv, ssm
from repro.models.attention import (
    CACHE_AXES,
    attention_apply,
    attention_init,
    init_cache as attn_cache_init,
)
from repro.models.layers import mlp_apply, mlp_init, norm_apply, norm_init
from repro.sharding import ShardCtx


@dataclasses.dataclass(frozen=True)
class LayerDesc:
    mixer: str  # attn | mamba | rwkv6
    ffn: str  # dense | moe
    cross: bool = False


def layer_descs(cfg: ArchConfig, *, stack: str = "decoder") -> list[LayerDesc]:
    n = cfg.n_encoder_layers if stack == "encoder" else cfg.n_layers
    cross = stack == "decoder" and cfg.structure == "encoder_decoder"
    descs = []
    for l in range(n):
        if stack == "encoder" or cfg.attn_pattern == "all":
            mixer = "attn"
        elif cfg.attn_pattern == "none":
            mixer = "rwkv6"
        elif cfg.attn_pattern == "jamba":
            mixer = "attn" if l % 8 == 4 else "mamba"
        else:
            raise ValueError(cfg.attn_pattern)
        ffn = "dense"
        if cfg.moe is not None:
            pat = cfg.moe.layer_pattern
            if pat == "all":
                ffn = "moe"
            elif pat == "every_other":
                ffn = "moe" if l % 2 == 1 else "dense"
            elif pat == "last_half":
                ffn = "moe" if l >= n - n // 2 else "dense"
            elif pat != "none":
                raise ValueError(pat)
        descs.append(LayerDesc(mixer=mixer, ffn=ffn, cross=cross))
    return descs


def stack_router_kind(cfg: ArchConfig, *, stack: str) -> str:
    """Paper §3.1: Expert Choice in encoders, Top-K in decoders."""
    if cfg.moe is None:
        return "top_k"
    if stack == "decoder" and cfg.moe.router == "expert_choice":
        return "top_k"
    return cfg.moe.router


def find_segments(descs: list[LayerDesc]) -> list[tuple[int, list[LayerDesc]]]:
    """-> [(repeats, period_descs), ...]; greedy smallest-period split."""
    n = len(descs)
    if n == 0:
        return []
    for p in range(1, n + 1):
        if n % p:
            continue
        if all(descs[i] == descs[i % p] for i in range(n)):
            return [(n // p, descs[:p])]
    half = n // 2
    return find_segments(descs[:half]) + find_segments(descs[half:])


# ---------------------------------------------------------------------------
# Single layer
# ---------------------------------------------------------------------------


def layer_init(rng, cfg: ArchConfig, desc: LayerDesc, *, dtype=jnp.float32):
    ks = jax.random.split(rng, 6)
    p = {"pre_norm": norm_init(cfg)}
    if desc.mixer == "attn":
        p["mixer"] = attention_init(ks[0], cfg, dtype=dtype)
    elif desc.mixer == "mamba":
        p["mixer"] = ssm.mamba_init(ks[0], cfg, dtype=dtype)
    elif desc.mixer == "rwkv6":
        p["mixer"] = rwkv.time_mix_init(ks[0], cfg, dtype=dtype)
    else:
        raise ValueError(desc.mixer)
    if desc.cross:
        p["cross_norm"] = norm_init(cfg)
        p["cross"] = attention_init(ks[1], cfg, dtype=dtype)
    p["ffn_norm"] = norm_init(cfg)
    if desc.mixer == "rwkv6":
        p["cm"] = rwkv.channel_mix_init(ks[2], cfg, dtype=dtype)
    if desc.ffn == "moe":
        p["ffn"] = moe_init(ks[3], cfg, cfg.moe, dtype=dtype)
    else:
        p["ffn"] = mlp_init(ks[3], cfg, dtype=dtype)
    return p


def layer_cache_init(
    cfg: ArchConfig, desc: LayerDesc, batch: int, max_len: int,
    *, dtype=jnp.bfloat16
):
    c = {}
    if desc.mixer == "attn":
        c["mixer"] = attn_cache_init(cfg, batch, max_len, dtype=dtype)
    elif desc.mixer == "mamba":
        c["mixer"] = ssm.mamba_cache_init(cfg, batch, dtype=dtype)
    elif desc.mixer == "rwkv6":
        c["mixer"] = rwkv.time_mix_cache_init(cfg, batch, dtype=dtype)
        c["cm"] = rwkv.channel_mix_cache_init(cfg, batch, dtype=dtype)
    return c


def layer_paged_cache_init(
    cfg: ArchConfig, desc: LayerDesc, num_blocks: int, block_size: int,
    *, dtype=jnp.bfloat16
):
    """Per-layer KV block pool for the paged serving engine. Paged
    serving is attention-only: mamba/rwkv6 caches are per-slot state
    vectors with no seq dim (nothing to page) — the serve engine rejects
    those stacks up front (see repro/serve)."""
    if desc.mixer != "attn":
        raise ValueError(
            f"paged serving supports attention mixers only, got "
            f"{desc.mixer!r}"
        )
    from repro.models.attention import init_paged_cache

    return {"mixer": init_paged_cache(cfg, num_blocks, block_size,
                                      dtype=dtype)}


def layer_cache_axes(desc: LayerDesc):
    c = {}
    if desc.mixer == "attn":
        c["mixer"] = dict(CACHE_AXES)
    elif desc.mixer == "mamba":
        c["mixer"] = dict(ssm.MAMBA_CACHE_AXES)
    elif desc.mixer == "rwkv6":
        c["mixer"] = dict(rwkv.TIME_MIX_CACHE_AXES)
        c["cm"] = dict(rwkv.CHANNEL_MIX_CACHE_AXES)
    return c


def zero_metrics():
    return {
        "aux_loss": jnp.zeros((), jnp.float32),
        "z_loss": jnp.zeros((), jnp.float32),
        "dropped_frac_sum": jnp.zeros((), jnp.float32),
        "moe_layer_count": jnp.zeros((), jnp.float32),
    }


def layer_apply(
    p,
    x,
    cfg: ArchConfig,
    desc: LayerDesc,
    *,
    enc=None,
    cache=None,
    cache_index=None,
    mode: str = "train",
    causal: bool = True,
    router_kind: str = "top_k",
    sorted_block: int = 128,
    moe_impl: str = "xla",
    mixer_impl: str = "xla",
    attn_impl: str = "xla",
    pad_heads_multiple: int = 0,
    ctx: Optional[ShardCtx] = None,
    block_tables=None,
    token_mask=None,
    mixed=None,
):
    cache = cache or None
    mix_cache = cache.get("mixer") if cache else None
    h = norm_apply(p["pre_norm"], x, cfg)
    if desc.mixer == "attn":
        y, mix_cache = attention_apply(
            p["mixer"], h, cfg,
            causal=causal,
            cache=mix_cache,
            cache_index=cache_index,
            ctx=ctx,
            pad_heads_multiple=pad_heads_multiple,
            implementation=attn_impl,
            block_tables=block_tables,
            mixed=mixed,
        )
    elif desc.mixer == "mamba":
        y, mix_cache = ssm.mamba_apply(
            p["mixer"], h, cfg, cache=mix_cache, mode=mode
        )
    else:
        y, mix_cache = rwkv.time_mix_apply(
            p["mixer"], h, cfg, cache=mix_cache, mode=mode,
            implementation=mixer_impl,
        )
    x = x + y

    if desc.cross:
        hc = norm_apply(p["cross_norm"], x, cfg)
        yc, _ = attention_apply(
            p["cross"], hc, cfg, kv_x=enc, causal=False, ctx=ctx,
            pad_heads_multiple=pad_heads_multiple,
            implementation=attn_impl,
        )
        x = x + yc

    h = norm_apply(p["ffn_norm"], x, cfg)
    gate = None
    cm_cache = None
    if "cm" in p:
        h, gate, cm_cache = rwkv.channel_mix_pre(
            p["cm"], h, cache=cache.get("cm") if cache else None
        )

    metrics = zero_metrics()
    if desc.ffn == "moe":
        y, m = moe_apply(
            p["ffn"], h, cfg, cfg.moe,
            router_kind=router_kind,
            sorted_block=sorted_block,
            ctx=ctx,
            implementation=moe_impl,
            token_mask=token_mask,
        )
        metrics["aux_loss"] = m["aux_loss"]
        metrics["z_loss"] = m["z_loss"]
        metrics["dropped_frac_sum"] = m["dropped_frac"]
        metrics["moe_layer_count"] = jnp.ones((), jnp.float32)
    else:
        y = mlp_apply(p["ffn"], h, cfg)
    if gate is not None:
        y = gate * y
    x = x + y

    new_cache = {}
    if cache is not None:
        if mix_cache is not None:
            new_cache["mixer"] = mix_cache
        if cm_cache is not None:
            new_cache["cm"] = cm_cache
    return x, metrics, new_cache


# ---------------------------------------------------------------------------
# Stack init / apply
# ---------------------------------------------------------------------------


def stack_init(rng, cfg: ArchConfig, descs, *, dtype=jnp.float32):
    """Layer ``l`` draws from ``fold_in(rng, l)``. Each segment position
    inits all its repeats under one ``vmap`` (the same values as one
    call per layer): a per-layer loop puts every layer's RNG ops into
    the program, and the TPU compile of a 24-layer init grows to a
    minute."""
    segs = find_segments(descs)
    out = []
    layer = 0
    for reps, pdescs in segs:
        n = len(pdescs)
        seg = {}
        for i, d in enumerate(pdescs):
            keys = jax.vmap(lambda l: jax.random.fold_in(rng, l))(
                layer + i + n * jnp.arange(reps)
            )
            stacked = jax.vmap(
                lambda k, d=d: layer_init(k, cfg, d, dtype=dtype)
            )(keys)
            seg[f"pos{i}"] = jax.tree.map(
                lambda p: pm.Param(p.value, ("layer " + p.axes).strip()),
                stacked, is_leaf=lambda x: isinstance(x, pm.Param),
            )
        out.append(seg)
        layer += reps * n
    return {"segments": out}


def unstack_layers(stack_params, descs):
    """Stacked wrapped params -> ordered list of per-layer wrapped trees."""
    segs = find_segments(descs)
    layers = []
    for si, (reps, pdescs) in enumerate(segs):
        seg = stack_params["segments"][si]
        for r in range(reps):
            for i in range(len(pdescs)):
                layers.append(
                    jax.tree.map(
                        lambda prm, r=r: pm.Param(
                            prm.value[r],
                            prm.axes.split(" ", 1)[1]
                            if " " in prm.axes else "",
                        ),
                        seg[f"pos{i}"],
                        is_leaf=lambda x: isinstance(x, pm.Param),
                    )
                )
    return layers


def restack_layers(layer_trees, descs):
    """Inverse of unstack_layers: per-layer trees -> segment stacks."""
    segs = find_segments(descs)
    out = []
    it = iter(layer_trees)
    for reps, pdescs in segs:
        per_pos = {f"pos{i}": [] for i in range(len(pdescs))}
        for _ in range(reps):
            for i in range(len(pdescs)):
                per_pos[f"pos{i}"].append(next(it))
        out.append({k: pm.stack_layers(v) for k, v in per_pos.items()})
    return {"segments": out}


def stack_cache_init(
    cfg: ArchConfig, descs, batch: int, max_len: int, *, dtype=jnp.bfloat16
):
    segs = find_segments(descs)
    out = []
    for reps, pdescs in segs:
        seg = {}
        for i, d in enumerate(pdescs):
            one = layer_cache_init(cfg, d, batch, max_len, dtype=dtype)
            seg[f"pos{i}"] = jax.tree.map(
                lambda v: jnp.broadcast_to(v, (reps,) + v.shape).copy(), one
            )
        out.append(seg)
    return {"segments": out}


def stack_paged_cache_init(
    cfg: ArchConfig, descs, num_blocks: int, block_size: int, *,
    dtype=jnp.bfloat16
):
    """Paged serve cache: one KV block pool per layer (stacked over
    segment repeats like ``stack_cache_init``); every layer's pool is
    addressed by the SAME per-slot block table (the vLLM layout)."""
    segs = find_segments(descs)
    out = []
    for reps, pdescs in segs:
        seg = {}
        for i, d in enumerate(pdescs):
            one = layer_paged_cache_init(
                cfg, d, num_blocks, block_size, dtype=dtype
            )
            seg[f"pos{i}"] = jax.tree.map(
                lambda v: jnp.broadcast_to(v, (reps,) + v.shape).copy(), one
            )
        out.append(seg)
    return {"segments": out}


def stack_cache_axes(descs):
    segs = find_segments(descs)
    out = []
    for reps, pdescs in segs:
        seg = {}
        for i, d in enumerate(pdescs):
            one = layer_cache_axes(d)
            seg[f"pos{i}"] = jax.tree.map(
                lambda a: ("layer " + a).strip(), one
            )
        out.append(seg)
    return {"segments": out}


def stack_apply(
    params,
    x,
    cfg: ArchConfig,
    descs,
    *,
    enc=None,
    cache=None,
    cache_index=None,
    mode: str = "train",
    causal: bool = True,
    router_kind: str = "top_k",
    sorted_block: int = 128,
    moe_impl: str = "xla",
    mixer_impl: str = "xla",
    attn_impl: str = "xla",
    pad_heads_multiple: int = 0,
    ctx: Optional[ShardCtx] = None,
    remat: str = "none",  # none | full | dots | moe
    block_tables=None,
    token_mask=None,
    mixed=None,
):
    segs = find_segments(descs)
    totals = zero_metrics()
    new_cache_segs = []

    for si, (reps, pdescs) in enumerate(segs):
        seg_params = params["segments"][si]
        have_cache = cache is not None
        seg_cache = (
            cache["segments"][si]
            if have_cache
            else {f"pos{i}": {} for i in range(len(pdescs))}
        )

        def body(carry, xs, pdescs=pdescs):
            h = carry
            lp, lc = xs
            mets = zero_metrics()
            out_cache = {}
            for i, d in enumerate(pdescs):
                entry = lc.get(f"pos{i}") or None
                h, m, c_new = layer_apply(
                    lp[f"pos{i}"], h, cfg, d,
                    enc=enc,
                    cache=entry,
                    cache_index=cache_index,
                    mode=mode,
                    causal=causal,
                    router_kind=router_kind,
                    sorted_block=sorted_block,
                    moe_impl=moe_impl,
                    mixer_impl=mixer_impl,
                    attn_impl=attn_impl,
                    pad_heads_multiple=pad_heads_multiple,
                    ctx=ctx,
                    block_tables=block_tables,
                    token_mask=token_mask,
                    mixed=mixed,
                )
                mets = jax.tree.map(jnp.add, mets, m)
                out_cache[f"pos{i}"] = c_new
            return h, (mets, out_cache)

        if remat == "full":
            body = jax.checkpoint(body)
        elif remat == "dots":
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            )
        elif remat == "moe":
            # MoE-block-boundary remat: save ONLY the combined MoE layer
            # outputs (tagged `moe_block` in core/moe.py). Everything else
            # in the layer — attention activations, the ragged expert
            # buffers, router tensors — is recomputed in the backward,
            # so the step's memory high-water mark is set by the Pallas
            # VJP residuals (kernel inputs), not full activations.
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.save_only_these_names(
                    "moe_block"
                ),
            )

        x, (mets, seg_cache_new) = jax.lax.scan(
            body, x, (seg_params, seg_cache)
        )
        totals = jax.tree.map(
            lambda t, m: t + m.sum(), totals, mets
        )
        new_cache_segs.append(seg_cache_new)

    new_cache = {"segments": new_cache_segs} if cache is not None else None
    return x, totals, new_cache
