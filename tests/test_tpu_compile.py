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
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fitmask import kernel
from repro.kernels.fitmask.ops import JaxEngine

GRIDS = [(1, 16, 16, 16), (64, 4, 4, 4)]
ROOT = Path(__file__).resolve().parents[1]


def _warmed(config):
    """The (B, K) multibox shapes a benchmark configuration warms for
    its what-if cell, with its grid."""
    warm = json.loads((ROOT / "bench/configs" / f"{config}.json")
                      .read_text())["warm"]["whatif"]
    return [(b, k, tuple(warm["grid"])) for b, k in warm["multibox"]]


# Every shape the static 16^3 what-if cell warms (its K is tiled), and
# the paper's pod at B = 64 and K = 64 (one tile).
MULTIBOX = _warmed("folding16") + [(64, 64, (4, 4, 4))]
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


@pytest.mark.parametrize("b,k,grid", MULTIBOX,
                         ids=lambda v: "x".join(map(str, v))
                         if isinstance(v, tuple) else str(v))
def test_warmed_multibox_shapes_compile_for_v5e(one_chip, b, k, grid):
    """Planes and counts at every warmed (B, K): without K tiles, any
    B >= 8 with K >= 128 on 16^3 ran out of VMEM."""
    fn = functools.partial(kernel.fitmask_multibox_counts,
                           boxes=((1, 1, 1),) * k, interpret=False)
    compiled = jax.jit(fn).lower(_occ((b,) + grid, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    tiles = k // kernel.k_tile(k, grid)
    assert tiles == (1 if grid == (4, 4, 4) else max(1, k // 16))
