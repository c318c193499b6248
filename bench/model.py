"""A configuration file -> the program's ``ArchConfig``, the reference's
sizes, and weights made from a seed.

The benchmark makes the dense parent itself (``reference.dense_parent``)
and hands it to the program's ``upcycle_params``: the users' first step,
dense checkpoint -> MoE. The reference upcycles the same parent with its
own code, so it takes no weight the program made.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax

from reference import granite as ref


def load_config(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def dims_of(conf: dict) -> dict:
    """The sizes and settings the reference and the FLOP counts read."""
    m = conf["moe"]
    return {
        "d": conf["hidden_size"], "L": conf["num_hidden_layers"],
        "H": conf["num_attention_heads"], "Kh": conf["num_key_value_heads"],
        "dh": conf["head_dim"], "f": conf["intermediate_size"],
        "E": conf["num_local_experts"], "k": conf["num_experts_per_tok"],
        "V": conf["vocab_size"], "theta": float(conf["rope_theta"]),
        "eps": float(conf["rms_norm_eps"]),
        "group": m["group_size"], "aux_weight": m["aux_loss_weight"],
        "router_std": m["router_init_std"], "noise_std": m["init_noise_std"],
        "expert_init": m["expert_init"],
    }


def arch_of(conf: dict):
    """The program's ArchConfig for this file: every size from the file,
    dropless routing (capacity factor = expert count)."""
    from repro.configs import ArchConfig, MoECfg

    m = conf["moe"]
    if conf["hidden_act"] != "silu" or not conf["tie_word_embeddings"]:
        raise ValueError("bench configurations are tied SwiGLU decoders")
    E = conf["num_local_experts"]
    moe = MoECfg(
        num_experts=E, router="top_k", top_k=conf["num_experts_per_tok"],
        capacity_factor=float(E), layer_pattern="all",
        group_size=m["group_size"], aux_loss_weight=m["aux_loss_weight"],
        normalize_combine_weights=False, expert_init=m["expert_init"],
        init_noise_std=m["init_noise_std"],
        router_init_std=m["router_init_std"],
    )
    return ArchConfig(
        name=conf["name"], family="moe", structure="decoder_only",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], d_head=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        gated_mlp=True, norm="rmsnorm", pos_emb="rope",
        rope_theta=float(conf["rope_theta"]), tie_embeddings=True, moe=moe,
        act="silu", source=conf["source"],
    )


def key_of(seed: int):
    """A PRNG key from any non-negative seed, also past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def program_weights(cfg, dims):
    """Jitted ``seed key -> MoE value tree``: the reference's dense
    parent, wrapped with the program's axes and upcycled by the
    program's ``upcycle_params``, all in one device call."""
    from repro.core.upcycle import upcycle_params
    from repro.models import model_zoo as zoo
    from repro.models import param as pm

    dense_cfg = cfg.dense_parent()
    axes = pm.split(jax.eval_shape(
        lambda: zoo.init_params(jax.random.PRNGKey(0), dense_cfg)))[1]

    def build(key):
        k_dense, k_moe = jax.random.split(key)
        dense = ref.dense_parent(k_dense, dims)
        wrapped = pm.wrap(dense, axes)
        return pm.split(upcycle_params(wrapped, dense_cfg, cfg, k_moe))[0]

    return jax.jit(build)


def reference_weights(dims):
    """Jitted ``seed key -> MoE value tree`` by the reference alone."""
    def build(key):
        k_dense, k_moe = jax.random.split(key)
        return ref.upcycle(ref.dense_parent(k_dense, dims), k_moe, dims)

    return jax.jit(build)

