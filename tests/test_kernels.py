"""Per-kernel validation: shape/dtype sweeps asserting allclose against
the pure-jnp oracles (kernels run in interpret mode on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.fitmask import kernel as fit_kernel
from repro.kernels.fitmask import ops as fit_ops
from repro.kernels.fitmask import ref as fit_ref
from repro.kernels.flash_attention import kernel as fa_kernel
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.ssd_scan import kernel as ssd_kernel
from repro.kernels.ssd_scan import ref as ssd_ref

# ------------------------------------------------------------ flash attn
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,h,kh,d,bq,bk", [
    (128, 4, 4, 64, 128, 128),    # MHA, single block
    (256, 4, 2, 64, 128, 128),    # GQA 2:1
    (256, 8, 1, 32, 64, 128),     # MQA, mixed blocks
    (192, 2, 2, 128, 128, 64),    # non-multiple seq/block
])
def test_flash_attention_sweep(dtype, s, h, kh, d, bq, bk):
    rng = np.random.default_rng(0)
    q = jnp.array(rng.normal(size=(2, s, h, d)), dtype)
    k = jnp.array(rng.normal(size=(2, s, kh, d)), dtype)
    v = jnp.array(rng.normal(size=(2, s, kh, d)), dtype)
    out = fa_kernel.flash_attention(q, k, v, causal=True, block_q=bq,
                                    block_k=bk, interpret=True)
    ref = fa_ref.attention_reference(q, k, v, causal=True)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [16, 64, 1])
def test_flash_attention_sliding_window(window):
    rng = np.random.default_rng(1)
    q = jnp.array(rng.normal(size=(1, 128, 2, 32)), jnp.float32)
    k = jnp.array(rng.normal(size=(1, 128, 2, 32)), jnp.float32)
    v = jnp.array(rng.normal(size=(1, 128, 2, 32)), jnp.float32)
    out = fa_kernel.flash_attention(q, k, v, causal=True, window=window,
                                    block_q=64, block_k=64, interpret=True)
    ref = fa_ref.attention_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)


def test_flash_attention_matches_model_path():
    """The einsum path used by the models equals the kernel (arange
    positions)."""
    from repro.models.attention import _gqa_attend
    rng = np.random.default_rng(2)
    b, s, h, kh, d = 2, 128, 4, 2, 64
    q = jnp.array(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.array(rng.normal(size=(b, s, kh, d)), jnp.float32)
    v = jnp.array(rng.normal(size=(b, s, kh, d)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    ref = _gqa_attend(q, k, v, pos, pos, 0)
    out = fa_kernel.flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# -------------------------------------------------------------- ssd scan
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,h,p,n,chunk", [
    (64, 2, 8, 16, 16),
    (128, 3, 16, 8, 32),
    (32, 1, 4, 4, 32),     # single chunk
    (96, 2, 8, 8, 16),     # many chunks
])
def test_ssd_kernel_sweep(dtype, s, h, p, n, chunk):
    rng = np.random.default_rng(3)
    x = jnp.array(rng.normal(size=(2, s, h, p)), dtype)
    dt = jnp.array(rng.uniform(0.01, 0.2, size=(2, s, h)), jnp.float32)
    a = jnp.array(-rng.uniform(0.5, 2.0, size=(h,)), jnp.float32)
    b = jnp.array(rng.normal(size=(2, s, h, n)), dtype)
    c = jnp.array(rng.normal(size=(2, s, h, n)), dtype)
    d = jnp.array(rng.normal(size=(h,)), jnp.float32)
    y_k, s_k = ssd_kernel.ssd_scan_kernel(x, dt, a, b, c, d_skip=d,
                                          chunk=chunk, interpret=True)
    y_r, s_r = ssd_ref.ssd_reference(x, dt, a, b, c, chunk=chunk, d_skip=d)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               rtol=1e-4, atol=1e-4)


def test_ssd_chunked_equals_sequential():
    rng = np.random.default_rng(4)
    B, S, H, P, N = 1, 48, 2, 4, 8
    x = jnp.array(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.array(rng.uniform(0.01, 0.3, size=(B, S, H)), jnp.float32)
    a = jnp.array(-rng.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    b = jnp.array(rng.normal(size=(B, S, H, N)), jnp.float32)
    c = jnp.array(rng.normal(size=(B, S, H, N)), jnp.float32)
    y1, s1 = ssd_ref.ssd_reference(x, dt, a, b, c, chunk=16)
    y2, s2 = ssd_ref.ssd_sequential_reference(x, dt, a, b, c)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-5)


def test_ssd_decode_step_consistent_with_scan():
    """Running ssd_step token by token reproduces the chunked scan."""
    rng = np.random.default_rng(5)
    B, S, H, P, N = 2, 16, 2, 4, 8
    x = jnp.array(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.array(rng.uniform(0.01, 0.3, size=(B, S, H)), jnp.float32)
    a = jnp.array(-rng.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    b = jnp.array(rng.normal(size=(B, S, H, N)), jnp.float32)
    c = jnp.array(rng.normal(size=(B, S, H, N)), jnp.float32)
    y_scan, s_scan = ssd_ref.ssd_reference(x, dt, a, b, c, chunk=8)
    st = jnp.zeros((B, H, P, N), jnp.float32)
    ys = []
    for t in range(S):
        y, st = ssd_ref.ssd_step(st, x[:, t], dt[:, t], a, b[:, t], c[:, t])
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)),
                               np.asarray(y_scan), rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------- fitmask
@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10_000),
       st.tuples(st.integers(1, 6), st.integers(1, 6), st.integers(1, 6)),
       st.integers(1, 4))
def test_fitmask_kernel_matches_oracles(seed, box, bsz):
    rng = np.random.default_rng(seed)
    occ = rng.uniform(size=(bsz, 6, 6, 6)) < 0.3
    out_k = np.asarray(fit_kernel.fitmask_batched(jnp.array(occ), box,
                                                  interpret=True))
    out_r = np.asarray(fit_ref.fitmask_reference(jnp.array(occ), box))
    out_n = np.asarray(fit_ops.fitmask(jnp.array(occ), box, engine="numpy"))
    assert (out_k == out_r).all()
    assert (out_k == out_n).all()


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000),
       st.integers(1, 3),
       st.tuples(st.integers(3, 7), st.integers(3, 7), st.integers(3, 7)),
       st.integers(1, 6))
def test_fitmask_multibox_matches_numpy_oracle(seed, bsz, grid, k):
    """The multi-box kernel (one VMEM integral-image pass for all K
    boxes) is bit-exact vs the numpy oracle across random grids, batch
    sizes and box lists — including boxes that fit nowhere or overhang
    the grid entirely (all-zero planes)."""
    from repro.core import fitmask as np_engine
    rng = np.random.default_rng(seed)
    occ = rng.uniform(size=(bsz,) + grid) < 0.3
    # box extents up to 8 on 3..7 grids: not-fitting boxes included
    boxes = tuple(tuple(int(v) for v in rng.integers(1, 9, size=3))
                  for _ in range(k))
    out = np.asarray(fit_kernel.fitmask_multibox(jnp.array(occ), boxes,
                                                 interpret=True))
    assert out.shape == (bsz, k) + grid
    expect = np.zeros((bsz, k) + grid, np.int32)
    for i, box in enumerate(boxes):
        m = np_engine.fit_mask_batched(occ, box)
        if m.size:
            expect[:, i, :m.shape[1], :m.shape[2], :m.shape[3]] = m
    assert (out == expect).all()
    assert (out == np_engine.fit_mask_multi(occ, boxes)).all()


def test_fitmask_multibox_k1_equals_single_box_kernel():
    """Explicit K=1 equivalence: the multi-box kernel degenerates to
    the old single-box kernel output, box by box."""
    rng = np.random.default_rng(7)
    occ = jnp.array(rng.uniform(size=(4, 6, 5, 6)) < 0.35)
    for box in [(1, 1, 1), (2, 3, 2), (6, 5, 6), (4, 4, 4), (7, 1, 1)]:
        single = np.asarray(fit_kernel.fitmask_batched(occ, box,
                                                       interpret=True))
        multi = np.asarray(fit_kernel.fitmask_multibox(occ, (box,),
                                                       interpret=True))
        assert multi.shape[1] == 1
        assert (multi[:, 0] == single).all(), box


def test_fitmask_multibox_empty_box_list():
    occ = jnp.zeros((2, 4, 4, 4), jnp.int32)
    out = fit_kernel.fitmask_multibox(occ, (), interpret=True)
    assert out.shape == (2, 0, 4, 4, 4)


def _tiled_case(seed, bsz, n, k):
    """Random (bsz, n, n, n) occupancy and K boxes, some overhanging."""
    rng = np.random.default_rng(seed)
    occ = rng.uniform(size=(bsz, n, n, n)) < 0.3
    boxes = tuple(tuple(int(v) for v in rng.integers(1, n + 2, size=3))
                  for _ in range(k))
    return occ, boxes


@pytest.mark.parametrize("n,k,tile", [(16, 8, 2), (16, 6, 3), (4, 16, 4),
                                      (4, 8, 8)])
def test_fitmask_multibox_k_tiles_match_engines(n, k, tile):
    """K cut into tiles (the integral image built at the first tile,
    the others reading it from scratch) answers every box as the numpy
    and ``ref`` engines do, counts included, on 16^3 and on 4^3."""
    occ, boxes = _tiled_case(n * 100 + k, 2, n, k)
    table = fit_kernel._box_table(boxes)
    planes, counts = fit_kernel._fitmask_multibox(
        jnp.array(occ), table, tile=tile, interpret=True)
    planes = np.asarray(planes)
    assert planes.shape == (2, k, n, n, n)
    np.testing.assert_array_equal(
        planes, fit_ops.get_engine("numpy").multibox(occ, boxes))
    np.testing.assert_array_equal(
        planes, np.asarray(fit_ops.get_engine("ref").multibox(occ, boxes)))
    np.testing.assert_array_equal(np.asarray(counts), occ.sum(axis=(1, 2, 3)))


def test_k_tile_keeps_small_grids_whole_and_tiles_16_cubed():
    """The tile comes from the grid's padded plane size: every K up to
    64 on a 4^3 grid is one tile (with the same results as before),
    a 16^3 grid runs 16 boxes per tile, and a tile always divides K."""
    for k in (1, 2, 8, 64):
        assert fit_kernel.k_tile(k, (4, 4, 4)) == k
    assert fit_kernel.k_tile(128, (16, 16, 16)) == 16
    assert fit_kernel.k_tile(8, (16, 16, 16)) == 8
    for k in range(1, 130):
        for grid in ((4, 4, 4), (8, 8, 8), (16, 16, 16), (64, 64, 64)):
            tile = fit_kernel.k_tile(k, grid)
            assert tile >= 1 and k % tile == 0


def test_fitmask_multibox_16_cubed_public_entry_tiles_k():
    """The public entry picks the tile itself: 32 boxes on a 16^3 grid
    run as two tiles and match the numpy engine."""
    occ, boxes = _tiled_case(5, 1, 16, 32)
    assert 32 // fit_kernel.k_tile(32, (16, 16, 16)) == 2
    planes, counts = fit_kernel.fitmask_multibox_counts(
        jnp.array(occ), boxes, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(planes), fit_ops.get_engine("numpy").multibox(occ, boxes))
    np.testing.assert_array_equal(np.asarray(counts), occ.sum(axis=(1, 2, 3)))


def test_fitmask_batched_cubes_use_case():
    """The reconfig allocator's batched per-cube check."""
    rng = np.random.default_rng(0)
    cubes = rng.uniform(size=(64, 4, 4, 4)) < 0.4
    box = (4, 2, 1)
    out = np.asarray(fit_ops.fitmask(jnp.array(cubes), box, engine="pallas"))
    for i in range(64):
        brute = np.zeros((4, 4, 4), np.int32)
        for y in range(3):
            for z in range(4):
                brute[0, y, z] = not cubes[i, :, y:y + 2, z:z + 1].any()
        assert (out[i] == brute).all()
