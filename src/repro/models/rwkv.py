"""RWKV-6 "Finch" blocks (arXiv:2404.05892): time-mix with data-dependent
decay + channel-mix.

Faithfulness notes (DESIGN.md §7): the data-dependent decay LoRA
(w = exp(-exp(w0 + tanh(x @ A) @ B))) and the per-head bonus ``u`` follow
the paper; the 5-way ddlerp token-shift is simplified to per-stream
mu-lerp (RWKV-5 style shift, RWKV-6 decay). The WKV recurrence runs through
repro.kernels.ops.rwkv6 (chunked XLA or the Pallas TPU kernel).

Channel-mix exposes its core 2-matrix sqrelu MLP through the stack's FFN
slot so sparse upcycling applies to it (DESIGN.md §Arch-applicability);
receptance gating and token shift stay per-layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from repro.models import param as pm

LORA_DIM = 64


def _hk(cfg: ArchConfig):
    K = cfg.ssm.head_size
    H = cfg.d_model // K
    return H, K


def time_mix_init(rng, cfg: ArchConfig, *, dtype=jnp.float32):
    d = cfg.d_model
    H, K = _hk(cfg)
    ks = jax.random.split(rng, 8)
    # decay base: spread so exp(-exp(w0)) covers slow..fast per channel.
    w0 = -5.0 + 8.0 * (jnp.arange(d) / max(d - 1, 1)) ** 0.7
    return {
        "mu": pm.Param(
            0.5 * jnp.ones((5, d), dtype), "_ embed"
        ),  # lerp for w,k,v,r,g
        "w0": pm.Param(w0.astype(dtype), "embed"),
        "w_lora_a": pm.normal(ks[0], (d, LORA_DIM), "embed _", std=0.02,
                              dtype=dtype),
        "w_lora_b": pm.zeros((LORA_DIM, d), "_ embed", dtype=dtype),
        **{
            name: pm.dense(k, (d, H, K), "embed heads head_dim", fan_in=d,
                           dtype=dtype)
            for name, k in zip(("wr", "wk", "wv", "wg"), ks[1:5])
        },
        "u": pm.normal(ks[5], (H, K), "heads head_dim", std=0.02,
                       dtype=dtype),
        "wo": pm.dense(ks[6], (H, K, d), "heads head_dim embed",
                       fan_in=H * K, dtype=dtype),
        "ln_x": {
            "scale": pm.ones((d,), "embed", dtype=dtype),
            "bias": pm.zeros((d,), "embed", dtype=dtype),
        },
    }


def time_mix_cache_init(cfg: ArchConfig, batch: int, *, dtype=jnp.float32):
    H, K = _hk(cfg)
    return {
        "x_prev": jnp.zeros((batch, cfg.d_model), dtype),
        "wkv": jnp.zeros((batch, H, K, K), jnp.float32),
    }


TIME_MIX_CACHE_AXES = {
    "x_prev": "batch embed",
    "wkv": "batch heads head_dim head_dim",
}


def _shift(x, x_prev):
    """x: (B,T,d); x_prev: (B,d) state or None -> previous-token stream."""
    if x_prev is None:
        pad = jnp.zeros_like(x[:, :1])
    else:
        pad = x_prev[:, None]
    return jnp.concatenate([pad, x[:, :-1]], axis=1)


def _group_norm(x, scale, bias, H):
    """Per-head groupnorm on (B, T, d)."""
    B, T, d = x.shape
    xh = x.reshape(B, T, H, d // H).astype(jnp.float32)
    mu = xh.mean(-1, keepdims=True)
    var = xh.var(-1, keepdims=True)
    xh = (xh - mu) * jax.lax.rsqrt(var + 1e-5)
    return (xh.reshape(B, T, d) * scale + bias).astype(x.dtype)


def time_mix_apply(
    p, x, cfg: ArchConfig, *, cache=None, mode="train", implementation="xla"
):
    """x: (B, T, d) -> (y, new_cache)."""
    from repro.kernels import ops

    H, K = _hk(cfg)
    B, T, d = x.shape
    x_prev = cache["x_prev"] if cache is not None else None
    xs = _shift(x, x_prev)
    xx = xs - x
    xw, xk, xv, xr, xg = (
        x + xx * p["mu"][i] for i in range(5)
    )
    w_raw = p["w0"] + jnp.einsum(
        "btl,ld->btd", jnp.tanh(xw @ p["w_lora_a"]), p["w_lora_b"]
    )
    w = jnp.exp(-jnp.exp(w_raw.astype(jnp.float32)))  # (B,T,d) in (0,1)
    w = w.reshape(B, T, H, K)
    r = jnp.einsum("btd,dhk->bthk", xr, p["wr"])
    k = jnp.einsum("btd,dhk->bthk", xk, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", xv, p["wv"])
    g = jax.nn.silu(jnp.einsum("btd,dhk->bthk", xg, p["wg"]))

    state0 = cache["wkv"] if cache is not None else None
    o, state = ops.rwkv6(
        r, k, v, w, p["u"], initial_state=state0,
        implementation=implementation,
    )  # (B,T,H,K)
    o = _group_norm(
        o.reshape(B, T, d), p["ln_x"]["scale"], p["ln_x"]["bias"], H
    )
    o = o.reshape(B, T, H, K) * g
    y = jnp.einsum("bthk,hkd->btd", o, p["wo"])

    new_cache = None
    if mode in ("decode", "prefill"):
        new_cache = {"x_prev": x[:, -1], "wkv": state}
    return y, new_cache


# ---------------------------------------------------------------------------
# Channel-mix wrapper: token shift + receptance around the (upcyclable) MLP
# ---------------------------------------------------------------------------


def channel_mix_init(rng, cfg: ArchConfig, *, dtype=jnp.float32):
    d = cfg.d_model
    ks = jax.random.split(rng, 2)
    return {
        "mu_k": pm.Param(0.5 * jnp.ones((d,), dtype), "embed"),
        "mu_r": pm.Param(0.5 * jnp.ones((d,), dtype), "embed"),
        "wr": pm.dense(ks[0], (d, d), "embed embed", dtype=dtype),
    }


def channel_mix_cache_init(cfg: ArchConfig, batch: int, *, dtype=jnp.float32):
    return {"x_prev": jnp.zeros((batch, cfg.d_model), dtype)}


CHANNEL_MIX_CACHE_AXES = {"x_prev": "batch embed"}


def channel_mix_pre(p, x, *, cache=None):
    """Returns (mlp input xk, receptance gate r, new_cache)."""
    x_prev = cache["x_prev"] if cache is not None else None
    xs = _shift(x, x_prev)
    xx = xs - x
    xk = x + xx * p["mu_k"]
    xr = x + xx * p["mu_r"]
    r = jax.nn.sigmoid(jnp.einsum("btd,de->bte", xr, p["wr"]))
    new_cache = {"x_prev": x[:, -1]} if cache is not None else None
    return xk, r, new_cache
