"""Ahead-of-time compiles of the fitmask device path for a TPU v5e.

The TPU compiler ships with JAX, so the programs the scheduler runs on
the chip compile here for a v5e that is described, not attached. This
catches what interpret mode cannot: a primitive Mosaic does not lower,
a block shape the tiling rules refuse, a store the chip cannot make.
Both grid shapes the engine sees are covered: the static 16^3 torus
(one large grid) and the paper's pod of 64 cubes of 4^3.

The topology is described inside a module-scoped fixture, never at
import, so that only the test worker that runs this file loads the TPU
library.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fitmask import kernel
from repro.kernels.fitmask.ops import JaxEngine

GRIDS = [(1, 16, 16, 16), (64, 4, 4, 4)]
# K = 8: the size the engine pads a small candidate set to.
BOXES = ((1, 1, 1), (2, 2, 2), (4, 4, 4), (4, 2, 1), (2, 4, 4),
         (3, 1, 2), (1, 4, 4), (8, 2, 1))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # libtpu would otherwise write its logs under /tmp.
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure to describe
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to the persistent
        # cache but can never be read back without one: keep it out.
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


def _occ(grid, one_chip, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(grid, dtype, sharding=one_chip)


@pytest.mark.parametrize("grid", GRIDS, ids=lambda g: "x".join(map(str, g)))
def test_fitmask_multibox_compiles_for_v5e(one_chip, grid):
    fn = functools.partial(kernel.fitmask_multibox, boxes=BOXES,
                           interpret=False)
    compiled = jax.jit(fn).lower(_occ(grid, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("grid", GRIDS, ids=lambda g: "x".join(map(str, g)))
def test_occupancy_counts_compiles_for_v5e(one_chip, grid):
    fn = functools.partial(kernel.occupancy_counts, interpret=False)
    compiled = jax.jit(fn).lower(_occ(grid, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("grid", GRIDS, ids=lambda g: "x".join(map(str, g)))
def test_jax_engine_bucket_program_compiles_for_v5e(one_chip, grid):
    compiled = JaxEngine._bucket_fn(BOXES).lower(
        _occ(grid, one_chip, jnp.bool_)).compile()
    assert compiled.as_text()
