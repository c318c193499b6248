"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (required so tests/benches see 1 CPU device while
the dry-run sees the 512 forced host devices).

Production target: TPU v5e, 256 chips per pod (16x16 ICI torus), 2 pods
over DCN. Axes:
  single-pod : (data=16, model=16)
  multi-pod  : (pod=2, data=16, model=16)  — "pod" is the DCN axis; default
               sharding rules keep only batch (pure DP gradient reduction)
               on it, FSDP-over-pod is an opt-in (sharding/logical.py).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes)
    )


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh: CPU multi-device tests and the 2x2 chip mesh."""
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes)
    )


def ep_degree(mesh) -> int:
    """Expert-parallel width of a mesh: the size of the EP a2a axis
    (sharding/logical.py EP_AXIS, i.e. ``model``); 1 when absent."""
    from repro.sharding.logical import EP_AXIS

    return dict(mesh.shape).get(EP_AXIS, 1)


# v5e hardware constants used by the roofline (benchmarks/roofline.py).
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link (conservative single-link figure)
HBM_BYTES = 16 * 1024 ** 3  # v5e HBM capacity
