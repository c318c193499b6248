"""The main path's Pallas kernels compiled for TPU v5e, without a chip.

Every kernel the upcycle -> train -> serve path runs on the chip is
lowered and compiled here by the TPU compiler against a described (not
attached) v5e topology, at granite-moe-1b-a400m's published widths
(d_model 1024, 16 query / 8 KV heads of 64, 32 experts, d_ff 512), in
float32 — the Trainer's and the serve engine's default dtype. Interpret
mode cannot see what the compiler refuses (block shapes Mosaic cannot
tile, scoped-VMEM overruns); these tests can. Nothing runs.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and every xdist worker
imports this file. The worker that runs these tests holds it until it
exits, so nothing here may start a child that needs it.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention import paged_decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas_vjp
from repro.kernels.grouped_mlp import (
    grouped_mlp_pallas_vjp,
    ragged_buffer_rows,
)
from repro.kernels.paged_prefill import paged_prefill_attention_pallas

D, H, KH, DH, E, F = 1024, 16, 8, 64, 32, 512  # granite widths
B, S, TOP_K = 2, 512, 8  # the smoke train batch
F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """ShapeDtypeStruct factory on one described v5e chip. A compile for
    it cannot be read back from the persistent cache without a chip, so
    the cache is off while these tests run."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda dims, dtype=F32: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip
    )
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _grad(fn, n_args):
    """Gradient program of sum(fn(...)) w.r.t. its first n_args inputs."""
    return jax.grad(lambda *a: fn(*a).sum(), argnums=tuple(range(n_args)))


def _flash(q, k, v):
    return flash_attention_pallas_vjp(q, k, v, causal=True, interpret=False)


def test_flash_attention_forward(shape):
    q, kv = shape((B, S, H, DH)), shape((B, S, KH, DH))
    _compiled_kernel(_flash, q, kv, kv)


def test_flash_attention_backward(shape):
    q, kv = shape((B, S, H, DH)), shape((B, S, KH, DH))
    _compiled_kernel(_grad(_flash, 3), q, kv, kv)


def _grouped(xs, wi, wg, wo, sizes):
    return grouped_mlp_pallas_vjp(xs, wi, wg, wo, sizes, interpret=False)


# (groups, assignment rows per group, experts, d_model, d_ff): the smoke
# train batch at granite-1b widths, and the granite-3b fine-tune cell's
# step (2 x 4096 tokens, top-8 of 40 experts), whose whole-expert weight
# windows are the largest the tile rule picks on this path.
GROUPED_SHAPES = pytest.mark.parametrize(
    "dims",
    [(1, B * S * TOP_K, E, D, F), (2, 4096 * TOP_K, 40, 1536, 512)],
    ids=["granite1b", "granite3b-finetune"],
)


def _grouped_args(shape, dims):
    g, n, e, d, f = dims
    m = ragged_buffer_rows(n, e, 128)
    return (shape((g, m, d)), shape((e, d, f)), shape((e, d, f)),
            shape((e, f, d)), shape((g, e), I32))


@GROUPED_SHAPES
def test_grouped_mlp_forward(shape, dims):
    _compiled_kernel(_grouped, *_grouped_args(shape, dims))


@GROUPED_SHAPES
def test_grouped_mlp_backward(shape, dims):
    _compiled_kernel(_grad(_grouped, 4), *_grouped_args(shape, dims))


BS, NB = 16, 4  # serve block size; blocks per slot (max_len 64)


def test_paged_decode(shape):
    slots = 4
    pool = shape((1 + slots * NB, KH, BS, DH))
    _compiled_kernel(
        lambda *a: paged_decode_attention_pallas(*a, interpret=False),
        shape((slots, H, DH)), pool, pool, shape((slots, NB), I32),
        shape((slots,), I32),
    )


def test_paged_prefill(shape):
    lanes, chunk = 1, 32  # the engine's default chunk lane
    pool = shape((1 + 4 * NB, KH, BS, DH))
    _compiled_kernel(
        lambda *a: paged_prefill_attention_pallas(*a, interpret=False),
        shape((lanes, chunk, H, DH)), pool, pool,
        shape((lanes, NB), I32), shape((lanes,), I32),
        shape((lanes,), I32),
    )
