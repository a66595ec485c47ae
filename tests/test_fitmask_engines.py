"""Fitmask engine registry + allocator routing tests: the registry's
name rule, cross-engine parity on the multibox contract, the numpy
engine's no-jax guarantee, and the placement engines (StaticTorus /
ReconfigTorus / policies) producing identical decisions on every
backend."""
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import fitmask as core_fitmask
from repro.core.allocator import make_policy
from repro.core.reconfig import ReconfigTorus
from repro.core.torus import StaticTorus
from repro.kernels.fitmask import ops

ENGINES = ("numpy", "jax", "pallas", "ref")
BOXES = ((1, 1, 1), (2, 2, 2), (4, 2, 1), (3, 3, 3), (9, 1, 1),
         (8, 8, 8))


def _occ(seed=0, shape=(4, 8, 8, 8), p=0.35):
    return np.random.default_rng(seed).uniform(size=shape) < p


# ------------------------------------------------------- registry
def test_registry_lists_all_engines():
    assert set(ENGINES) <= set(ops.available_engines())


def test_unknown_engine_raises():
    with pytest.raises(KeyError):
        ops.get_engine("tpu-v7")
    with pytest.raises(KeyError):
        make_policy("folding", dims=(4, 4, 4), engine="nope")


def test_default_is_numpy():
    """An unnamed selection is the numpy registry engine, and a
    default-built policy takes the host path with no client."""
    from repro.core.geometry import JobShape
    assert ops.get_engine() is ops.get_engine("numpy")
    pol = make_policy("folding", dims=(6, 6, 6))
    assert pol.torus.mask_client is None
    assert pol.try_place(1, JobShape((2, 2, 2))) is not None
    assert pol.torus._fit_ii is not None       # host integral image


# ------------------------------------------------------- parity
@pytest.mark.parametrize("backend,interpret", [
    ("tpu", False), ("cpu", True), ("gpu", None)])
def test_pallas_interpret_follows_the_backend(monkeypatch, backend,
                                              interpret):
    """Interpret mode is chosen in one place: compiled on a TPU,
    interpreted on the CPU, refused on any other backend."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            ops.pallas_interpret()
    else:
        assert ops.pallas_interpret() is interpret


def test_all_engines_agree_on_free_counts():
    occ = _occ(seed=7)
    ref = np.asarray(ops.get_engine("numpy").free_counts(occ))
    assert ref.shape == (occ.shape[0],)
    assert np.array_equal(ref, [(~occ[i]).sum()
                                for i in range(occ.shape[0])])
    for name in ENGINES:
        out = np.asarray(ops.get_engine(name).free_counts(occ))
        assert np.array_equal(out, ref), name
    assert np.array_equal(np.asarray(ops.free_counts(occ, engine="jax")),
                          ref)


def test_all_engines_agree_on_multibox():
    occ = _occ()
    ref = ops.get_engine("numpy").multibox(occ, BOXES)
    assert ref.dtype == np.int32
    for name in ENGINES:
        out = np.asarray(ops.get_engine(name).multibox(occ, BOXES))
        assert (out == ref).all(), name


def test_all_engines_agree_on_single_box():
    occ = _occ(seed=1)
    ref = np.asarray(ops.fitmask(occ, (2, 3, 2), engine="numpy"))
    for name in ENGINES:
        out = np.asarray(ops.fitmask(occ, (2, 3, 2), engine=name))
        assert (out == ref).all(), name


# ---------------------------------------------- batched numpy fast path
@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000),
       st.tuples(st.integers(1, 6), st.integers(3, 9), st.integers(3, 9),
                 st.integers(3, 9)),
       st.integers(1, 6))
def test_fit_mask_multi_fast_matches_oracle(seed, shape, k):
    """The (B, K)-vectorized numpy multibox (int16 integral images,
    nested differencing) is exact against the straight-line oracle,
    including overhanging/infeasible boxes, and its fused free counts
    match the host reduction."""
    rng = np.random.default_rng(seed)
    occ = rng.uniform(size=shape) < 0.4
    boxes = tuple(tuple(int(v) for v in rng.integers(1, 11, size=3))
                  for _ in range(k))
    ref = core_fitmask.fit_mask_multi(occ, boxes)
    fast, free = core_fitmask.fit_mask_multi_fast(occ, boxes)
    assert fast.dtype == np.int32
    np.testing.assert_array_equal(fast, ref)
    np.testing.assert_array_equal(free, core_fitmask.free_counts(occ))


def test_fit_mask_multi_fast_matches_reduce_window_reference():
    """Batched numpy multibox vs the jax.lax.reduce_window oracle in
    ref.py (the satellite parity contract)."""
    import jax.numpy as jnp
    from repro.kernels.fitmask import ref as refmod
    occ = _occ(seed=9, shape=(3, 7, 6, 5))
    boxes = ((1, 1, 1), (2, 3, 2), (7, 6, 5), (8, 1, 1), (3, 3, 3))
    fast, _ = core_fitmask.fit_mask_multi_fast(occ, boxes)
    oracle = np.asarray(
        refmod.fitmask_multibox_reference(jnp.asarray(occ), boxes))
    np.testing.assert_array_equal(fast, oracle)


def test_fit_mask_multi_fast_large_grid_uses_wide_accumulator():
    """32^3 cells overflow int16 — the wide-accumulator fallback stays
    exact."""
    rng = np.random.default_rng(5)
    occ = rng.uniform(size=(2, 32, 32, 32)) < 0.5
    boxes = ((5, 5, 5), (32, 32, 32), (1, 1, 33))
    ref = core_fitmask.fit_mask_multi(occ, boxes)
    fast, free = core_fitmask.fit_mask_multi_fast(occ, boxes)
    np.testing.assert_array_equal(fast, ref)
    np.testing.assert_array_equal(free, core_fitmask.free_counts(occ))


def test_all_engines_agree_on_multibox_bucketed():
    """The broker's fused flush entry: planes are nonzero-where-fits
    (dtype is the engine's choice) and the free counts ride along."""
    occ = _occ(seed=12)
    ref = ops.get_engine("numpy").multibox(occ, BOXES)
    fc = np.asarray(ops.get_engine("numpy").free_counts(occ))
    for name in ENGINES:
        planes, free = ops.get_engine(name).multibox_bucketed(occ, BOXES)
        np.testing.assert_array_equal(np.asarray(planes) != 0, ref != 0,
                                      err_msg=name)
        np.testing.assert_array_equal(np.asarray(free).astype(np.int64),
                                      fc, err_msg=name)


def test_jax_compile_caches_are_bounded():
    """Satellite: the per-box and per-bucket program caches are LRU
    with a size cap, not unbounded functools.cache — long multi-shape
    sweeps cannot grow them without limit."""
    info = ops.JaxEngine._window_fn.cache_info()
    assert info.maxsize == ops.WINDOW_CACHE_SIZE
    info = ops.JaxEngine._bucket_fn.cache_info()
    assert info.maxsize == ops.BUCKET_CACHE_SIZE


# ------------------------------------------------------- numpy purity
class _Poison:
    """Stand-in for the jax modules: any attribute access fails the
    test, so the numpy path provably never calls into jax."""

    def __getattr__(self, name):
        raise AssertionError(f"numpy engine touched jax (.{name})")


def test_numpy_engine_makes_no_jax_calls(monkeypatch):
    """Regression for the old wrapper's host round-trip (np.pad ->
    jnp.asarray on every call): the numpy engine must return numpy
    arrays without a single jax call."""
    poison = _Poison()
    for mod in ("jax", "jax.numpy", "jax.experimental.pallas"):
        monkeypatch.setitem(sys.modules, mod, poison)
    occ = _occ(seed=2, shape=(2, 6, 6, 6))
    out = ops.fitmask(occ, (2, 2, 2), engine="numpy")
    assert isinstance(out, np.ndarray) and out.dtype == np.int32
    out3 = ops.fitmask(occ[0], (2, 2, 2), engine="numpy")
    assert isinstance(out3, np.ndarray) and out3.shape == (6, 6, 6)
    multi = ops.fitmask_multi(occ, BOXES, engine="numpy")
    assert isinstance(multi, np.ndarray)
    assert multi.shape == (2, len(BOXES), 6, 6, 6)


def test_numpy_allocator_path_makes_no_jax_calls(monkeypatch):
    """The default placement hot path (policies -> torus -> fitmask)
    stays jax-free too."""
    poison = _Poison()
    for mod in ("jax", "jax.numpy", "jax.experimental.pallas"):
        monkeypatch.setitem(sys.modules, mod, poison)
    from repro.core.geometry import JobShape
    pol = make_policy("rfold", num_xpus=128, cube_n=4)
    assert pol.try_place(1, JobShape((4, 4, 2))) is not None
    pol2 = make_policy("folding", dims=(8, 8, 8))
    assert pol2.try_place(1, JobShape((2, 2, 2))) is not None


# ------------------------------------------------------- torus routing
def test_static_torus_engine_parity():
    """find_free_box / count_free_boxes identical across engines on a
    randomly occupied torus, with and without prefetch."""
    rng = np.random.default_rng(3)
    boxes = [(2, 2, 2), (4, 1, 1), (3, 2, 2), (8, 8, 8), (2, 4, 2)]
    toruses = {name: StaticTorus((8, 8, 8), engine=name)
               for name in ENGINES}
    mask = rng.uniform(size=(8, 8, 8)) < 0.4
    for t in toruses.values():
        t.occ[:] = mask
        t.bump_epoch()
    toruses["pallas"].prefetch_boxes(boxes)    # batch path
    ref = toruses["numpy"]
    for box in boxes:
        for name, t in toruses.items():
            assert t.find_free_box(box) == ref.find_free_box(box), \
                (name, box)
            assert t.count_free_boxes(box) == ref.count_free_boxes(box), \
                (name, box)


def test_static_torus_engine_epoch_invalidation():
    """Engine-cached masks refresh when occupancy changes."""
    t = StaticTorus((6, 6, 6), engine="pallas")
    assert t.find_free_box((2, 2, 2)) == (0, 0, 0)
    t.commit_box(1, (0, 0, 0), (2, 2, 2))
    origin = t.find_free_box((2, 2, 2))
    assert origin is not None and origin != (0, 0, 0)
    t.release(1)
    assert t.find_free_box((2, 2, 2)) == (0, 0, 0)


def test_reconfig_block_free_engine_parity():
    """ReconfigTorus sub-block freeness via the engine equals the host
    integral-image path, across cube occupancy states."""
    rng = np.random.default_rng(4)
    locals_ = [((0, 2), (0, 2), (0, 2)), ((1, 4), (0, 4), (2, 3)),
               ((0, 4), (0, 4), (0, 4)), ((3, 4), (3, 4), (3, 4))]
    rts = {name: ReconfigTorus(512, 4, engine=name)
           for name in ENGINES}
    mask = rng.uniform(size=(8, 4, 4, 4)) < 0.3
    for rt in rts.values():
        rt.occ[:] = mask
        rt.bump_epoch()
    ref = rts["numpy"]
    for local in locals_:
        expect = ref._block_free_mask(local)
        naive = ref._block_free_mask_naive(local)
        assert (expect == naive).all()
        for name, rt in rts.items():
            assert (rt._block_free_mask(local) == expect).all(), \
                (name, local)


@pytest.mark.parametrize("engine", ["jax", "pallas"])
def test_engine_runs_build_no_host_integral_image(engine, monkeypatch):
    """ROADMAP item closed by PR 4: with an accelerator engine active,
    the reconfigurable torus answers BOTH sub-block freeness and
    per-cube free counts from the engine — zero host integral-image
    builds on the placement path (same poison pattern as the numpy
    engine's no-jax guarantee)."""
    from repro.core import fitmask as core_fitmask
    from repro.core.geometry import JobShape

    def _poisoned(*a, **kw):
        raise AssertionError("engine run built a host integral image")

    monkeypatch.setattr(core_fitmask, "integral_image", _poisoned)
    monkeypatch.setattr(core_fitmask, "batched_integral_image", _poisoned)
    for policy in ("reconfig", "rfold"):
        pol = make_policy(policy, num_xpus=256, cube_n=4,
                          engine=engine)
        assert pol.try_place(1, JobShape((4, 4, 2))) is not None
        assert pol.try_place(2, JobShape((8, 2, 2))) is not None
        pol.release(1)
        assert pol.try_place(3, JobShape((4, 4, 4))) is not None
        assert pol.cluster._ii is None


def test_policy_engine_parity_small_sim():
    """End-to-end: a seeded trace schedules identically on every
    engine for a static-torus and a reconfigurable policy."""
    from repro.sim.metrics import summarize
    from repro.sim.simulator import Simulator
    from repro.traces.generator import TraceConfig, generate_trace

    jobs = generate_trace(TraceConfig(num_jobs=18, seed=11,
                                      target_load=1.5))
    for policy, kw in (("folding", dict(dims=(8, 8, 8))),
                       ("rfold", dict(num_xpus=512, cube_n=4))):
        base = None
        for name in ENGINES:
            pol = make_policy(policy, engine=name, **kw)
            s = summarize(Simulator(pol, list(jobs)).run())
            if base is None:
                base = s
            else:
                assert s == base, (policy, name)


@pytest.mark.parametrize("policy,kw", [
    ("folding", dict(dims=(6, 6, 6))),
    ("rfold", dict(num_xpus=128, cube_n=4))])
def test_placement_calls_the_engine_singleton(policy, kw):
    """The benchmark's contract: a method set on the registry's engine
    instance after the policy is built (as span instrumentation does)
    is what the policy's next placement calls."""
    from repro.core.geometry import JobShape
    pol = make_policy(policy, engine="jax", **kw)
    eng = ops.get_engine("jax")
    calls = []
    real = eng.multibox

    def counted(occ, boxes):
        calls.append(len(boxes))
        return real(occ, boxes)

    eng.multibox = counted
    try:
        assert pol.try_place(1, JobShape((2, 2, 2))) is not None
    finally:
        del eng.multibox
    assert calls


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
