"""Logical-axis -> mesh-axis sharding rules engine.

t5x/MaxText-style: every tensor dim carries a logical axis name; a rules
table maps each name to an ordered list of mesh-axis *candidates* (each
candidate is a tuple of mesh axes the dim may be sharded over). A candidate
applies only if (a) all its axes exist in the mesh, (b) none is already used
by another dim of the same tensor, and (c) the dim size is divisible by the
candidate's total device count. First applicable candidate wins; otherwise
the dim is replicated. This divisibility fallback is what lets one rules
table serve all 10 assigned architectures (e.g. grok's E=8 experts cannot
shard over the 16-wide ``model`` axis -> falls back to expert-tensor
parallelism; granite's vocab 49155 is odd -> embedding shards over ``embed``
instead).

Two tables: PARAM_RULES (weights; ``embed`` is the FSDP dim) and ACT_RULES
(activations; only batch/seq/expert dims shard).
"""
from __future__ import annotations

from typing import Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Candidate = tuple[str, ...]
Rules = Mapping[str, Sequence[Candidate]]

# Weights. Order of dict entries is irrelevant; per-tensor assignment is
# greedy left-to-right over the tensor's dims.
PARAM_RULES: Rules = {
    "layer": (),  # scan-stacked layer dim: never sharded
    "expert": (("model",),),
    "mlp": (("model",),),
    "heads": (("model",),),
    "kv_heads": (("model",),),
    "vocab": (("model",),),
    "embed": (("data",),),  # FSDP / ZeRO-3 dim
    "head_dim": (),
    "state": (),
    "conv": (),
    "pos": (),
    "_": (),
}

# Activations / inputs / caches.
ACT_RULES: Rules = {
    "batch": (("pod", "data"), ("data",)),
    "seq": (),
    "embed": (),
    "heads": (("model",),),
    "kv_heads": (("model",),),
    "head_dim": (),
    "mlp": (("model",),),
    "expert": (("model",),),
    "vocab": (("model",),),
    # KV caches: shard the time dim over `model` (sequence parallelism for
    # decode); falls back to replication for short caches.
    "cache_seq": (("model",),),
    "state": (),
    "layer": (),
    "conv": (),
    "pos": (),
    "_": (),
}


def make_rules(
    mesh: Mesh,
    *,
    params: bool,
    fsdp_over_pod: bool = False,
    overrides: Mapping[str, Sequence[Candidate]] | None = None,
    dp_only: bool = False,
) -> Rules:
    """Build a rules table for a mesh.

    ``dp_only`` gives the paper-faithful baseline: weights replicated
    (expert partitioning only), activations batch-sharded.
    ``fsdp_over_pod`` extends weight FSDP across the pod axis (beyond-paper;
    default off so cross-pod traffic stays pure-DP gradient reduction).
    """
    base = dict(PARAM_RULES if params else ACT_RULES)
    if params:
        if dp_only:
            base["embed"] = ()
        elif fsdp_over_pod and "pod" in mesh.axis_names:
            base["embed"] = (("pod", "data"), ("data",))
    if overrides:
        base.update(overrides)
    return base


# The mesh axis the sorted-dispatch expert-parallel all-to-all runs over.
# Matches PARAM_RULES["expert"]: expert weights already live on `model`,
# so the EP path keeps them resident and moves tokens instead.
EP_AXIS = "model"


def expert_parallel_layout(mesh, num_experts: int):
    """EP layout for the sorted-dispatch all-to-all (core/ep.py), or
    ``None`` when the mesh cannot host it (no ``model`` axis, axis of
    size 1, or experts not divisible — the same graceful-fallback
    discipline as the rules engine, cf. grok's E=8 on a 16-wide axis).

    Returns ``(ep_axis, ep_size, token_axes)``: the a2a axis, its device
    count, and the full tuple of mesh axes the token-group dim shards
    over (every device owns a distinct token shard; expert weights are
    sharded over ``ep_axis`` and replicated over the rest).
    """
    if mesh is None or EP_AXIS not in mesh.axis_names:
        return None
    ep = dict(mesh.shape)[EP_AXIS]
    if ep <= 1 or num_experts % ep:
        return None
    return EP_AXIS, ep, tuple(mesh.axis_names)


def spec_for(logical: str, shape: tuple[int, ...], mesh: Mesh, rules: Rules) -> P:
    """PartitionSpec for one tensor given its space-joined logical axes."""
    names = logical.split() if logical else []
    if len(names) != len(shape):
        raise ValueError(f"logical {logical!r} does not match shape {shape}")
    used: set[str] = set()
    out: list = []
    axis_sizes = dict(mesh.shape)  # works for Mesh and AbstractMesh
    for name, dim in zip(names, shape):
        assigned = None
        for cand in rules.get(name, ()):  # type: ignore[arg-type]
            if not all(a in axis_sizes for a in cand):
                continue
            if any(a in used for a in cand):
                continue
            total = 1
            for a in cand:
                total *= axis_sizes[a]
            if total == 0 or dim % total != 0:
                continue
            assigned = cand
            used.update(cand)
            break
        if assigned is None:
            out.append(None)
        elif len(assigned) == 1:
            out.append(assigned[0])
        else:
            out.append(tuple(assigned))
    # Trim trailing Nones (canonical form).
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def sharding_for(
    logical: str, shape: tuple[int, ...], mesh: Mesh, rules: Rules
) -> NamedSharding:
    return NamedSharding(mesh, spec_for(logical, shape, mesh, rules))


def tree_shardings(axes_tree, shapes_tree, mesh: Mesh, rules: Rules):
    """Map (axes-string tree, shape tree) -> NamedSharding tree.

    ``shapes_tree`` leaves may be arrays, ShapeDtypeStructs, or shape tuples.
    """

    def one(axes: str, shaped):
        shape = shaped if isinstance(shaped, tuple) else tuple(shaped.shape)
        return sharding_for(axes, shape, mesh, rules)

    return jax.tree.map(one, axes_tree, shapes_tree)


def constrain(x: jax.Array, logical: str, mesh: Mesh, rules: Rules) -> jax.Array:
    """with_sharding_constraint by logical axes (no-op on 1-device mesh)."""
    if mesh.devices.size == 1:
        return x
    return jax.lax.with_sharding_constraint(
        x, sharding_for(logical, tuple(x.shape), mesh, rules)
    )
