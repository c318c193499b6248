"""The granite decoder mixture-of-experts: a family of the benchmark.

A configuration file names its family (``"family": "granite"``), and
``bench/model.py`` loads ``bench/families/<family>.py`` by its file. A
family is what the shared harness may not know about an architecture;
another one joins as new files beside this one (its family file, its
reference under ``bench/reference/``, its configuration and traffic
files, and readers for its own kernels, scopes and counters), with no
edit to a file the harness has. A family file provides:

* ``dims_of(conf)`` -- the sizes and settings its reference and counts
  read, from the configuration file. The shared readers (the kernel
  counts of ``bench/flops.py``, ``bench/metrics/``, the drivers) read
  ``d`` (model width), ``L`` (layers), ``H`` and ``Kh`` (query and
  key/value heads), ``dh`` (head width), ``f`` (expert width), ``E``
  (experts), ``k`` (experts per token) and ``V`` (vocabulary); a family
  may add keys of its own. ``model.dims_of`` adds ``family``, so a
  ``dims`` finds its family again.
* ``arch_of(conf)`` -- the program's ``ArchConfig`` for the file. It and
  ``model.program_weights`` are all that touch the program.
* ``reference`` -- the plain float32 reference module, which imports
  nothing of the program: ``dense_parent(key, dims)``, ``upcycle(dense,
  key, dims)``, ``logits(params, tokens, dims, *, dtype, q_block)``,
  ``loss(params, batch, dims, *, dtype, q_block)`` -> (loss, ce),
  ``adafactor_init(params)`` and ``adafactor_update(params, grads,
  state, lr, t)``, on parameter trees laid out as the program's.
* ``matmul_params_per_token(dims)`` and ``attn_flops(dims, ctx_sum)`` --
  the whole-model counts that ``flops.train_step_flops`` and
  ``flops.serve_flops`` (and so the step MFU readers) take.
* ``SCOPES`` (optional) -- a tuple of the further ``jax.named_scope``
  names that the family's program path sets beyond the shared ones of
  ``bench/scopes.py`` (a latent projection, a shared expert, a mixer
  that is not attention). The traced split counts them after the shared
  scopes, and a reader takes one's share as
  ``scopes.of(ctx).share(("<scope>",))``. A name that repeats a shared
  scope is refused.

What the family's own readers (``bench/metrics/<name>.py``, ``read(ctx)``)
can read besides the trace: in a traced training run,
``ctx.res["step_metrics"]`` holds the scalar metrics the program's train
step returns (``loss``, ``ce``, ``aux_loss``, ``dropped_frac_sum``,
``moe_layer_count``, ``grad_norm`` and any counter the program adds),
``{name: [one float per traced step]}``. A reader whose counter is not
there returns None, and the run then stops naming it.

Granite: a tied SwiGLU decoder, every layer MoE, grouped-query attention
with one head width, full causal attention in every layer.
"""
from __future__ import annotations

from reference import granite as reference  # noqa: F401 (the contract)


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
        raise ValueError("granite configurations are tied SwiGLU decoders")
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


def matmul_params_per_token(dims: dict) -> int:
    """Weights one token multiplies through in the decoder stack: the
    attention projections, its top-k experts and the router."""
    d, H, Kh, dh, f = (dims[k] for k in ("d", "H", "Kh", "dh", "f"))
    attn = d * (H + 2 * Kh) * dh + H * dh * d
    return dims["L"] * (attn + dims["k"] * 3 * d * f + d * dims["E"])


def attn_flops(dims: dict, ctx_sum: float) -> float:
    """QK^T and PV of every layer for query rows whose key counts sum to
    ``ctx_sum``."""
    return 4.0 * dims["L"] * dims["H"] * dims["dh"] * ctx_sum
