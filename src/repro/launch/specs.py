"""Dry-run cell construction: step functions + ShapeDtypeStruct input specs
(weak-type-correct, sharding-attached, no device allocation) for every
(architecture x input-shape x mesh x profile) combination.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs import ArchConfig, SHAPES, ShapeCfg, get_config
from repro.models import model_zoo as zoo
from repro.models import param as pm
from repro.optim import adafactor, inverse_sqrt
from repro.sharding import ShardCtx, constrain, tree_shardings
from repro.training.train_loop import (
    TrainConfig,
    init_train_state,
    make_train_step,
    state_axes,
)

BIG_PARAM_THRESHOLD = 2e10  # >20B params -> bf16 weights for training
WHISPER_ENC_FRAMES = 3000
PIXTRAL_PATCHES = 1024

BATCH_AXES = {
    "tokens": "batch seq",
    "targets": "batch seq",
    "dec_tokens": "batch seq",
    "enc_tokens": "batch seq",
    "frames": "batch seq embed",
    "patch_embeds": "batch seq embed",
    "labels": "batch",
}


@dataclasses.dataclass(frozen=True)
class Profile:
    name: str
    ce_chunk: int
    fsdp: bool  # shard weight `embed` dims over `data` (beyond-paper)
    remat: str = "full"
    act_overrides: Optional[dict] = None
    param_overrides: Optional[dict] = None
    fsdp_over_pod: bool = False
    pad_heads_multiple: int = 0


PROFILES = {
    # Paper-faithful: DP + TP + expert partitioning, full logits (§A.4 —
    # "expert partitioning ... model partitioning" only; no weight-FSDP
    # in 2022 T5X MoE).
    "baseline": Profile("baseline", ce_chunk=0, fsdp=False),
    # Beyond-paper: FSDP weights, chunked CE, head-padding TP for
    # indivisible head counts (qwen2.5's 40 heads).
    "optimized": Profile("optimized", ce_chunk=2048, fsdp=True,
                         pad_heads_multiple=16),
    # Inference-only weight-stationary layout: no FSDP gathers — expert
    # weights shard (E -> model, F -> data) and stay resident; the second
    # expert matmul all-reduces ACTIVATIONS over `data` instead (far
    # smaller than weights at prefill batch sizes). Dense d_ff shards over
    # model (classic TP). SPerf iteration 2 for collective-bound serving.
    "serve_tp": Profile(
        "serve_tp", ce_chunk=0, fsdp=False,
        pad_heads_multiple=16,
        param_overrides={
            "embed": (),
            "mlp": (("model",), ("data",)),
        },
    ),
}


def count_params(cfg: ArchConfig) -> tuple[int, int]:
    """(total, active) param counts from eval_shape (no allocation)."""
    wrapped = jax.eval_shape(
        lambda: zoo.init_params(jax.random.PRNGKey(0), cfg)
    )
    vals, axes = pm.split(wrapped)
    total = 0
    active = 0
    moe = cfg.moe
    for leaf, a in zip(jax.tree.leaves(vals), jax.tree.leaves(axes)):
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        if moe is not None and "expert" in a.split():
            k = moe.top_k if moe.router in ("top_k", "switch") \
                else moe.capacity_factor
            active += int(n * min(k, moe.num_experts) / moe.num_experts)
        else:
            active += n
    return total, active


def make_ctx(mesh: Mesh, cfg: ArchConfig, profile: Profile) -> ShardCtx:
    from repro.sharding import make_rules

    overrides = dict(cfg.sharding_overrides or {})
    overrides.update(profile.param_overrides or {})
    act_overrides = dict(profile.act_overrides or {})
    return ShardCtx(
        mesh=mesh,
        act_rules=make_rules(mesh, params=False, overrides=act_overrides),
        param_rules=make_rules(
            mesh, params=True,
            dp_only=not profile.fsdp,
            fsdp_over_pod=profile.fsdp_over_pod,
            overrides=overrides,
        ),
    )


def _sds_with_shardings(sds_tree, axes_tree, mesh, rules):
    sh = tree_shardings(axes_tree, sds_tree, mesh, rules)
    return jax.tree.map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
        sds_tree, sh,
    )


def _batch_struct(cfg: ArchConfig, shape: ShapeCfg) -> dict:
    B, S = shape.global_batch, shape.seq_len
    if cfg.structure == "encoder_decoder":
        dec = S // 4 if shape.kind == "train" else S
        enc = S if shape.kind == "train" else WHISPER_ENC_FRAMES
        b = {
            "dec_tokens": jax.ShapeDtypeStruct((B, dec), jnp.int32),
        }
        if shape.kind == "train":
            b["targets"] = jax.ShapeDtypeStruct((B, dec), jnp.int32)
        if cfg.frontend == "frame":
            b["frames"] = jax.ShapeDtypeStruct(
                (B, enc, cfg.d_model), jnp.bfloat16
            )
        else:
            b["enc_tokens"] = jax.ShapeDtypeStruct((B, enc), jnp.int32)
        return b
    b = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    if shape.kind == "train":
        b["targets"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if cfg.frontend == "patch":
        b["patch_embeds"] = jax.ShapeDtypeStruct(
            (B, min(PIXTRAL_PATCHES, S), cfg.d_model), jnp.bfloat16
        )
    return b


def batch_axes(batch_struct: dict) -> dict:
    return {k: BATCH_AXES[k] for k in batch_struct}


def build_cell(
    arch: str,
    shape_name: str,
    mesh: Mesh,
    *,
    profile: str = "baseline",
    extra_ac: Optional[dict] = None,
):
    """Returns (step_fn, args: tuple of SDS trees, info dict)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    prof = PROFILES[profile]
    ctx = make_ctx(mesh, cfg, prof)
    total, active = count_params(cfg)

    ac_kw = dict(
        compute_dtype="bfloat16",
        remat=prof.remat,
        ce_chunk=prof.ce_chunk,
        pad_heads_multiple=prof.pad_heads_multiple,
    )
    ac_kw.update(extra_ac or {})
    info = {
        "arch": arch, "shape": shape_name, "profile": profile,
        "params_total": total, "params_active": active,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "kind": shape.kind,
    }

    if shape.kind == "train":
        ac = zoo.ApplyCfg(**ac_kw)
        param_dtype = (
            jnp.bfloat16 if total > BIG_PARAM_THRESHOLD else jnp.float32
        )
        info["param_dtype"] = str(jnp.dtype(param_dtype))
        opt = adafactor(inverse_sqrt(peak=0.01, warmup_steps=10_000))
        tc = TrainConfig()
        state_sds = jax.eval_shape(
            lambda: init_train_state(
                jax.random.PRNGKey(0), cfg, opt, dtype=param_dtype, tc=tc
            )
        )
        st_axes = state_axes(cfg, dtype=param_dtype, tc=tc)
        state_in = _sds_with_shardings(
            state_sds, st_axes, mesh, ctx.param_rules
        )
        bstruct = _batch_struct(cfg, shape)
        batch_in = _sds_with_shardings(
            bstruct, batch_axes(bstruct), mesh, ctx.act_rules
        )
        step = make_train_step(cfg, opt, ac=ac, ctx=ctx, tc=tc)
        return step, (state_in, batch_in), info

    # Serving cells: bf16 weights.
    ac = zoo.ApplyCfg(**{**ac_kw, "remat": "none", "ce_chunk": 0})
    wrapped = jax.eval_shape(
        lambda: zoo.init_params(jax.random.PRNGKey(0), cfg,
                                dtype=jnp.bfloat16)
    )
    p_sds, p_axes = pm.split(wrapped)
    params_in = _sds_with_shardings(p_sds, p_axes, mesh, ctx.param_rules)
    info["param_dtype"] = "bfloat16"
    B, S = shape.global_batch, shape.seq_len
    enc_len = (
        WHISPER_ENC_FRAMES if cfg.structure == "encoder_decoder" else 0
    )

    cache_sds = jax.eval_shape(
        lambda: zoo.init_serve_cache(
            cfg, B, S, dtype=jnp.bfloat16, enc_len=enc_len
        )
    )
    c_axes = zoo.serve_cache_axes(cfg)

    if shape.kind == "prefill":
        bstruct = _batch_struct(cfg, shape)
        batch_in = _sds_with_shardings(
            bstruct, batch_axes(bstruct), mesh, ctx.act_rules
        )
        cache_shardings = tree_shardings(
            c_axes, cache_sds, mesh, ctx.act_rules
        )

        def prefill_step(params, batch):
            cache = zoo.init_serve_cache(
                cfg, B, S, dtype=jnp.bfloat16, enc_len=enc_len
            )
            cache = jax.tree.map(
                jax.lax.with_sharding_constraint, cache, cache_shardings
            )
            return zoo.prefill(params, batch, cache, cfg, ac=ac, ctx=ctx)

        return prefill_step, (params_in, batch_in), info

    # decode: one new token against a cache of length S (cache capacity S;
    # S-1 tokens already present).
    cache_in = _sds_with_shardings(cache_sds, c_axes, mesh, ctx.act_rules)
    tokens_in = _sds_with_shardings(
        {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32)},
        {"tokens": "batch seq"}, mesh, ctx.act_rules,
    )["tokens"]
    index_in = jax.ShapeDtypeStruct((), jnp.int32)

    def serve_step(params, tokens, cache, index):
        return zoo.decode_step(
            params, tokens, cache, index, cfg, ac=ac, ctx=ctx
        )

    return serve_step, (params_in, tokens_in, cache_in, index_in), info


def input_specs(arch: str, shape_name: str = "train_4k",
                mesh: Optional[Mesh] = None, profile: str = "baseline"):
    """ShapeDtypeStruct stand-ins for every model input of a cell.

    Returns the positional argument tuple for the cell's step function
    (train: (state, batch); prefill: (params, batch); decode: (params,
    tokens, cache, index)).
    """
    if mesh is None:
        from repro.launch.mesh import make_production_mesh

        mesh = make_production_mesh()
    _, args, _ = build_cell(arch, shape_name, mesh, profile=profile)
    return args
