"""Free-box search ("fitmask") as a Pallas TPU kernel.

The allocator's hot spot: for every origin of an occupancy grid, is the
(a, b, c) window entirely free? TPU-native formulation: one VMEM pass
per grid — a 3D integral image built once, then every candidate box's
window sums by nested per-axis differencing — batched over
cubes/candidate grids on the Pallas grid axis. Cluster grids are tiny
(<= 64^3 int32 = 1 MiB), so a whole grid fits VMEM comfortably;
batching is the tiling axis.

* The prefix sums are matmuls against strictly triangular 0/1 matrices
  (Mosaic has no cumsum lowering), which also write the integral
  image's zero border; the leading axis is a running sum of slabs.
* The boxes are data, not code: a ``(K, 3)`` int32 table read from SMEM
  (scalar prefetch), so one compiled program serves every box set of
  the same (B, K, X, Y, Z) shape. Each box's differences along Y and Z
  are dynamic rotations of a lane-aligned ``(Yp, Zp)`` slab of the
  integral image, and origins where the box overhangs are masked.

Every entry point takes ``interpret`` explicitly: the one place that
chooses it is ``repro.kernels.fitmask.ops.pallas_interpret`` (compiled
on a TPU, interpreted on the CPU backend, refused elsewhere).
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Box = Tuple[int, int, int]

# One TPU vreg holds (8, 128) 32-bit values: the integral-image slabs
# are padded to whole vregs so their rotations are aligned, and the
# per-grid occupancy count is written as one lane-dense (1, 128) row,
# the smallest per-grid output block the TPU compiler accepts.
_SUBLANES = 8
_LANES = 128


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _strict_prefix_matrix(rows: int, cols: int, left: bool) -> jnp.ndarray:
    """0/1 float matrix turning an axis into its exclusive prefix sums
    (a leading zero, then running totals): ``M[j, i] = i < j`` for left
    multiplication (sublanes), ``M[i, j] = i < j`` for right
    multiplication (lanes). Rows or columns beyond the axis length
    repeat the full total; the kernel masks them."""
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    return ((col < row) if left else (row < col)).astype(jnp.float32)


def _integral_image(occ_ref, ii_ref) -> None:
    """Write the (X+1, Yp, Zp) int32 integral image of the (X, Y, Z)
    grid in ``occ_ref[0]`` into ``ii_ref``: ``ii[i, j, k]`` counts the
    occupied cells with x < i, y < j, z < k.

    Exact at any matmul precision for Z <= 256: the Z pass multiplies
    0/1 by 0/1, the Y pass multiplies values <= Z (integers that
    bfloat16 holds exactly) by 0/1, and both accumulate in float32,
    which is exact for sums below 2**24. ``HIGHEST`` is kept anyway so
    the guarantee does not rest on that bound."""
    _, x, y, z = occ_ref.shape
    yp, zp = ii_ref.shape[1:]
    left = _strict_prefix_matrix(yp, y, left=True)
    right = _strict_prefix_matrix(z, zp, left=False)
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    acc = jnp.zeros((yp, zp), jnp.int32)
    ii_ref[0] = acc
    for i in range(x):
        plane = dot(left, dot(occ_ref[0, i].astype(jnp.float32), right))
        acc = acc + plane.astype(jnp.int32)
        ii_ref[i + 1] = acc


def _fitmask_multibox_kernel(boxes_ref, occ_ref, out_ref, ii_ref):
    """One grid per program: build its integral image once in VMEM,
    then answer the K boxes of the SMEM table in a loop."""
    _, x, y, z = occ_ref.shape
    yp, zp = ii_ref.shape[1:]
    _integral_image(occ_ref, ii_ref)
    row = jax.lax.broadcasted_iota(jnp.int32, (yp, zp), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (yp, zp), 1)

    def one_box(k, carry):
        a, b, c = boxes_ref[k, 0], boxes_ref[k, 1], boxes_ref[k, 2]
        inside = (row + b <= y) & (col + c <= z)
        # Rotating by (len - d) brings index j + d to j; the wrapped
        # tail lands only where ``inside`` is false.
        shift_y = yp - jnp.minimum(b, y)
        shift_z = zp - jnp.minimum(c, z)
        for i in range(x):
            s = ii_ref[jnp.minimum(i + a, x)] - ii_ref[i]     # x in [i, i+a)
            s = pltpu.roll(s, shift_y, 0) - s                 # y in [j, j+b)
            s = pltpu.roll(s, shift_z, 1) - s                 # z in [k, k+c)
            fits = (s == 0) & inside & (i + a <= x)
            out_ref[0, k, i] = fits[:y, :z].astype(jnp.int32)
        return carry

    jax.lax.fori_loop(0, out_ref.shape[1], one_box, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fitmask_multibox(occ: jnp.ndarray, table: jnp.ndarray, *,
                      interpret: bool) -> jnp.ndarray:
    bsz, x, y, z = occ.shape
    k = table.shape[0]
    if k == 0:
        return jnp.zeros((bsz, 0, x, y, z), jnp.int32)
    yp, zp = _round_up(y + 1, _SUBLANES), _round_up(z + 1, _LANES)
    return pl.pallas_call(
        _fitmask_multibox_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz,),
            in_specs=[pl.BlockSpec((1, x, y, z),
                                   lambda i, boxes: (i, 0, 0, 0))],
            out_specs=pl.BlockSpec((1, k, x, y, z),
                                   lambda i, boxes: (i, 0, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((x + 1, yp, zp), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((bsz, k, x, y, z), jnp.int32),
        interpret=interpret,
    )(table.astype(jnp.int32), occ.astype(jnp.int32))


def _box_table(boxes: Sequence[Box]) -> np.ndarray:
    """Boxes as the kernel's ``(K, 3)`` int32 table."""
    return np.asarray(boxes, np.int32).reshape(-1, 3)


def fitmask_multibox(occ: jnp.ndarray, boxes: Sequence[Box], *,
                     interpret: bool) -> jnp.ndarray:
    """All K candidate boxes from one VMEM integral-image pass.

    occ: (B, X, Y, Z) bool/int; ``boxes``: K (a, b, c) shapes, or their
    ``(K, 3)`` table. Returns (B, K, X, Y, Z) int32 — ``out[i, k]`` is
    the full-grid fit mask of ``boxes[k]`` on grid ``i``; boxes that
    cannot fit anywhere (including ones larger than the grid) are
    all-zero planes, so callers never special-case K. The program is
    compiled per (B, K, X, Y, Z) shape, never per box set.
    """
    return _fitmask_multibox(occ, _box_table(boxes), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fitmask_batched(occ: jnp.ndarray, table: jnp.ndarray, *,
                     interpret: bool) -> jnp.ndarray:
    return _fitmask_multibox(occ, table, interpret=interpret)[:, 0]


def fitmask_batched(occ: jnp.ndarray, box: Box, *,
                    interpret: bool) -> jnp.ndarray:
    """occ: (B, X, Y, Z) bool/int. Returns (B, X, Y, Z) int32 — 1 where
    an un-wrapped box fits with its origin at that cell (the K=1 case
    of :func:`fitmask_multibox`)."""
    return _fitmask_batched(occ, _box_table([box]), interpret=interpret)


def _occupancy_counts_kernel(occ_ref, out_ref):
    plane = jnp.sum(occ_ref[0], axis=0)                    # (Y, Z)
    total = jnp.sum(plane, axis=(0, 1), keepdims=True)     # (1, 1)
    out_ref[0] = jnp.broadcast_to(total, (1, _LANES))


@functools.partial(jax.jit, static_argnames=("interpret",))
def occupancy_counts(occ: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    """Occupied-cell count per grid: (B, X, Y, Z) bool/int -> (B,) int32.

    The engine registry's ``free_counts`` query runs on this (free =
    X*Y*Z - occupied): the reconfigurable-torus allocator needs per-cube
    free counts for its best-fit ordering every occupancy epoch, and
    answering them device-side is what lets accelerator engines drop the
    host integral-image pass entirely. One program per grid, whole grid
    in VMEM (same batching axis as the fitmask kernel); each program
    writes its count across one lane-dense (1, 128) row, of which lane
    0 is returned."""
    bsz, x, y, z = occ.shape
    out = pl.pallas_call(
        _occupancy_counts_kernel,
        grid=(bsz,),
        in_specs=[pl.BlockSpec((1, x, y, z), lambda i: (i, 0, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, _LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, 1, _LANES), jnp.int32),
        interpret=interpret,
    )(occ.astype(jnp.int32))
    return out[:, 0, 0]


def fitmask_multibox_singlepass_baseline(
        occ: jnp.ndarray, boxes: Sequence[Box], *,
        interpret: bool) -> jnp.ndarray:
    """K independent single-box ``pallas_call``s stacked on a new axis —
    the pre-multibox design, kept as the benchmark baseline (each call
    rebuilds the integral image)."""
    return jnp.stack([fitmask_batched(occ, tuple(b), interpret=interpret)
                      for b in boxes], axis=1)
