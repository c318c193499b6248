"""Core NN layers: norms, MLPs, embeddings, positional encodings.

All layers follow the init/apply convention from ``repro.models.param``:
``*_init`` returns a wrapped Param tree; ``*_apply`` takes the plain-array
tree. Logical axis names (see repro/sharding/logical.py): embed, mlp, heads,
kv_heads, head_dim, vocab, expert, layer, pos, state, conv, _.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from repro.models import param as pm

# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu":
        return jax.nn.relu
    if name == "sqrelu":  # RWKV channel-mix
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {name}")


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def norm_init(cfg: ArchConfig, d: int | None = None):
    d = d or cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"scale": pm.ones((d,), "_")}
    return {"scale": pm.ones((d,), "_"), "bias": pm.zeros((d,), "_")}


def norm_apply(p, x, cfg: ArchConfig, *, eps: float = 1e-6):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    else:
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32)
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(dtype)


# ---------------------------------------------------------------------------
# MLP (gated SwiGLU-style or 2-matrix)
# ---------------------------------------------------------------------------


def mlp_init(rng, cfg: ArchConfig, *, dtype=jnp.float32):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(rng, 3)
    if cfg.gated_mlp:
        return {
            "wi": pm.dense(ks[0], (d, f), "embed mlp", dtype=dtype),
            "wg": pm.dense(ks[1], (d, f), "embed mlp", dtype=dtype),
            "wo": pm.dense(ks[2], (f, d), "mlp embed", dtype=dtype),
        }
    return {
        "wi": pm.dense(ks[0], (d, f), "embed mlp", dtype=dtype),
        "wo": pm.dense(ks[2], (f, d), "mlp embed", dtype=dtype),
    }


def mlp_apply(p, x, cfg: ArchConfig):
    """x: (..., d) -> (..., d)."""
    act = activation(cfg.act)
    h = jnp.einsum("...d,df->...f", x, p["wi"])
    if cfg.gated_mlp:
        g = jnp.einsum("...d,df->...f", x, p["wg"])
        h = act(h) * g
    else:
        h = act(h)
    return jnp.einsum("...f,fd->...d", h, p["wo"])


# ---------------------------------------------------------------------------
# Embeddings / heads
# ---------------------------------------------------------------------------


def embed_init(rng, cfg: ArchConfig, *, dtype=jnp.float32):
    p = {
        "tokens": pm.normal(
            rng, (cfg.vocab_size, cfg.d_model), "vocab embed", dtype=dtype
        )
    }
    if cfg.pos_emb == "learned":
        p["pos"] = pm.normal(
            jax.random.fold_in(rng, 1),
            (max(cfg.n_frontend_positions, 1) + 8, cfg.d_model),
            "pos embed",
            std=0.02,
            dtype=dtype,
        )
    return p


@jax.named_scope("embed")
def embed_apply(p, tokens, cfg: ArchConfig, *, positions=None):
    x = jnp.take(p["tokens"], tokens, axis=0)
    if cfg.pos_emb == "learned" and positions is not None:
        x = x + jnp.take(p["pos"], positions, axis=0)
    elif cfg.pos_emb == "sinusoidal" and positions is not None:
        x = x + sinusoidal(positions, cfg.d_model).astype(x.dtype)
    return x


def head_init(rng, cfg: ArchConfig, *, dtype=jnp.float32):
    if cfg.tie_embeddings:
        return {}
    return {
        "w": pm.dense(rng, (cfg.d_model, cfg.vocab_size), "embed vocab",
                      dtype=dtype)
    }


def head_apply(p, x, embed_params, cfg: ArchConfig):
    if cfg.tie_embeddings:
        w = embed_params["tokens"].T  # (d, V)
    else:
        w = p["w"]
    return jnp.einsum("...d,dv->...v", x, w)


def sinusoidal(positions, d_model: int):
    """positions: int array (...,) -> (..., d_model) float32."""
    half = d_model // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half) / max(half - 1, 1))
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x, positions, theta: float):
    """Rotary embedding. x: (B, S, H, dh); positions: (S,) or (B, S)."""
    dh = x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freq  # (S, half)
        ang = ang[None, :, None, :]  # (1, S, 1, half)
    else:
        ang = positions[..., None].astype(jnp.float32) * freq  # (B, S, half)
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Modality frontends (assignment: stubs fed by precomputed embeddings)
# ---------------------------------------------------------------------------


def frontend_init(rng, cfg: ArchConfig, *, dtype=jnp.float32):
    """Projection from stub patch/frame embeddings into the backbone."""
    if cfg.frontend is None:
        return {}
    return {
        "proj": pm.dense(rng, (cfg.d_model, cfg.d_model), "embed embed",
                         dtype=dtype)
    }


def frontend_apply(p, embeds, cfg: ArchConfig):
    if cfg.frontend is None:
        return embeds
    return jnp.einsum("...d,de->...e", embeds, p["proj"])
