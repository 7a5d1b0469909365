"""Compile the fused gather+Gram kernels for a described TPU v5e chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is only described, and refuses what the chip would
refuse (SMEM overflow, unaligned DMA slices, packed-dtype stores) — what
interpret mode cannot show.  The shapes are the capacity buckets of the
served similarity plan (m=8192 Zipf profile at q=8: widths 4-256 at their
reducer counts), the largest bucket of its X2Y counterpart, and the
narrow buckets of the x2y cell's plan (4 x 4, 128 x 4, 4 x 128).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and a decision taken
at import would give parallel test workers different tests to collect.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.pairwise.fused_gather_gram import (
    fused_gather_gram,
    fused_gather_gram_rect,
)

M = 8192                       # table rows of the served similarity plan


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 host, with the persistent compile
    cache off: entries written here could never be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("R,L,d,dtype", [
    (22791, 4, 128, jnp.float32),
    (146245, 8, 128, jnp.float32),
    (17862, 64, 64, jnp.float32),
    (19009, 256, 128, jnp.float32),
    (17862, 64, 128, jnp.bfloat16),
], ids=["L4", "L8", "L64-d64", "L256", "L64-bf16"])
def test_square_kernel_compiles(one_chip, R, L, d, dtype):
    compiled = jax.jit(fused_gather_gram).lower(
        _spec((M, d), dtype, one_chip),
        _spec((R, L), jnp.int32, one_chip),
        _spec((R, L), jnp.bool_, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("R,Lx,Ly,d", [
    (98428, 32, 128, 128),
    (300, 64, 32, 64),
    (73948, 4, 4, 128),
    (16226, 128, 4, 128),
    (8062, 4, 128, 128),
], ids=["P2-widest", "d64", "x2y-4x4", "x2y-128x4", "x2y-4x128"])
def test_rect_kernel_compiles(one_chip, R, Lx, Ly, d):
    compiled = jax.jit(fused_gather_gram_rect).lower(
        _spec((M // 2, d), jnp.float32, one_chip),
        _spec((M // 2, d), jnp.float32, one_chip),
        _spec((R, Lx), jnp.int32, one_chip),
        _spec((R, Lx), jnp.bool_, one_chip),
        _spec((R, Ly), jnp.int32, one_chip),
        _spec((R, Ly), jnp.bool_, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
