"""Minimal parameter/module convention (flax is not in the environment).

Parameters are nested dicts whose leaves are ``Param`` objects: a jnp array
plus a space-joined string of *logical axis names* (one per dim, "_" for an
unsharded dim). ``init`` functions return wrapped trees; training code calls
``split`` once to obtain (plain-array tree, axes tree) — the axes tree (str
leaves) feeds the sharding engine and is stored in checkpoints so restores
can re-shard onto any mesh.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import tree_util


@tree_util.register_pytree_node_class
class Param:
    """A parameter leaf: array value + logical axes (static aux data)."""

    __slots__ = ("value", "axes")

    def __init__(self, value: jax.Array, axes: str):
        self.value = value
        self.axes = axes

    def tree_flatten(self):
        return (self.value,), self.axes

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux)

    def __repr__(self):
        return f"Param({self.value.shape}, {self.value.dtype}, '{self.axes}')"


def _is_param(x: Any) -> bool:
    return isinstance(x, Param)


def split(tree):
    """Wrapped tree -> (plain array tree, axes-string tree)."""
    values = jax.tree.map(lambda p: p.value, tree, is_leaf=_is_param)
    axes = jax.tree.map(lambda p: p.axes, tree, is_leaf=_is_param)
    return values, axes


def wrap(values, axes):
    return jax.tree.map(Param, values, axes)


def validate(values, axes) -> None:
    """Assert axes tree matches values tree and ranks agree."""

    def check(v, a):
        names = a.split() if a else []
        if len(names) != v.ndim:
            raise ValueError(f"axes {a!r} rank != array rank {v.shape}")

    jax.tree.map(check, values, axes)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense(rng, shape, axes: str, *, dtype=jnp.float32, fan_in: int | None = None):
    """Truncated-normal fan-in init (lecun_normal-style). ``fan_in``
    defaults to ``shape[-2]``, the input dim of ``(d_in, d_out)`` and
    ``(E, d_in, d_out)``; pass it where the input dim is elsewhere, as
    in a ``(d, heads, head_dim)`` projection."""
    if fan_in is None:
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    v = std * jax.random.truncated_normal(rng, -2.0, 2.0, shape, dtype)
    return Param(v, axes)


def normal(rng, shape, axes: str, *, std=0.02, dtype=jnp.float32):
    return Param(std * jax.random.normal(rng, shape, dtype), axes)


def zeros(shape, axes: str, *, dtype=jnp.float32):
    return Param(jnp.zeros(shape, dtype), axes)


def ones(shape, axes: str, *, dtype=jnp.float32):
    return Param(jnp.ones(shape, dtype), axes)


def count_params(values) -> int:
    return sum(int(v.size) for v in jax.tree.leaves(values))


def stack_layers(param_trees: list):
    """Stack per-layer wrapped trees along a new leading 'layer' axis.

    Used to build scan-over-layers parameter stacks.
    """

    def stack(*ps):
        axes = ps[0].axes
        return Param(
            jnp.stack([p.value for p in ps]),
            ("layer " + axes).strip(),
        )

    return jax.tree.map(stack, *param_trees, is_leaf=_is_param)
