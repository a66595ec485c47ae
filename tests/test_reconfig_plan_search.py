"""Batched reconfiguration plan-search parity + incremental
invalidation tests (PR 4).

The batched engine (pre-scored per-fold offset tables, vectorized
single-cube search, fresh-cube bound pruning, dirty-cube cache
updates) must be behavior-preserving: identical plans and
byte-identical schedules versus the retained naive oracle, across cube
sizes, multi-cube offsets and release/re-place sequences."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import fitmask
from repro.core.allocator import make_policy
from repro.core.folding import enumerate_folds
from repro.core.geometry import JobShape
from repro.core.reconfig import ReconfigTorus, _row_demand, fold_plan_table
from repro.sim.simulator import Simulator
from repro.traces.generator import TraceConfig, generate_trace

CUBE_SIZES = [(512, 2), (512, 4), (4096, 8)]
# Cluster variants: as built, the chained-cube ablation, and a quarter
# of the cubes with a failed OCS port.
VARIANTS = ["plain", "chained", "ocs"]


def _torus(size, variant, rng) -> ReconfigTorus:
    num_xpus, cube_n = size
    rt = ReconfigTorus(num_xpus, cube_n, dedicate_chained=variant == "chained")
    if variant == "ocs":
        rt.fail_ocs_port(rng.choice(rt.num_cubes, size=rt.num_cubes // 4,
                                    replace=False))
    return rt


def _random_fill(rt: ReconfigTorus, rng, steps=14):
    """Random occupancy via real commit/release traffic."""
    live = []
    jid = 0
    for _ in range(steps):
        if live and rng.uniform() < 0.4:
            rt.release(live.pop(int(rng.integers(len(live)))))
            continue
        dims = tuple(int(rng.integers(1, 9)) for _ in range(3))
        for f in enumerate_folds(JobShape(dims), max_dim=rt.max_extent):
            plan = rt.place_fold(f)
            if plan is not None:
                rt.commit(jid, plan)
                live.append(jid)
                jid += 1
                break
    return live


# ----------------------------------------------------- hypothesis sweep
@settings(max_examples=40, deadline=None)
@given(st.sampled_from(CUBE_SIZES),
       st.integers(0, 10_000),
       st.tuples(st.integers(1, 12), st.integers(1, 12),
                 st.integers(1, 12)),
       st.sampled_from([True, False]),
       st.sampled_from(VARIANTS))
def test_place_fold_parity_sweep(size, seed, dims, offset_search, variant):
    """Batched place_fold == naive oracle for every fold of a random
    shape on a randomly filled torus, across cube sizes, offset modes
    and cluster variants; against an incumbent's score (the best plan
    of the folds before it), only a strictly better plan comes back."""
    rng = np.random.default_rng(seed)
    rt = _torus(size, variant, rng)
    _random_fill(rt, rng, steps=10)
    incumbent = None
    for f in enumerate_folds(JobShape(dims), max_dim=rt.max_extent):
        want = rt.place_fold_naive(f, offset_search=offset_search)
        assert rt.place_fold(f, offset_search=offset_search) == want, \
            (dims, f)
        if incumbent is not None:
            beats = (want if want is not None and want.score() < incumbent
                     else None)
            assert rt.place_fold(f, offset_search=offset_search,
                                 bound=incumbent) == beats, (dims, f)
        if want is not None and (incumbent is None
                                 or want.score() < incumbent):
            incumbent = want.score()


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("size", CUBE_SIZES, ids=lambda s: "%d-%d" % s)
def test_supply_test_rules_out_only_unplaceable_rows(size, variant):
    """Every multi-cube row the supply test rules out is a row on which
    cube assignment fails, with every fit mask the test may read at
    hand; and on random occupancies the test does rule rows out."""
    cut = 0
    for seed in range(4):
        rng = np.random.default_rng(seed)
        rt = _torus(size, variant, rng)
        n = rt.cube_n
        target = rng.uniform(0.2, 0.8)     # occupancy to fill up to
        for jid in range(400):
            if rt.utilization() >= target:
                break
            dims = JobShape(tuple(int(v) for v in rng.integers(1, n + 1, 3)))
            plan = rt.plan_search(enumerate_folds(dims,
                                                  max_dim=rt.max_extent))
            if plan is not None:
                rt.commit(jid, plan)
        for dims in [(8, 4, 4), (16, 2, 2), (6, 6, 2), (12, 8, 4),
                     (10, 3, 2)]:
            for f in enumerate_folds(JobShape(dims), max_dim=rt.max_extent):
                tab = fold_plan_table(f, n, rt.num_cubes)
                if tab is None or all(ext <= n for ext in f.box):
                    continue
                for _, shape in _row_demand(f.box, tab.offs_arr, n).shapes:
                    rt._shape_fit_mask(shape)
                ok = rt._supply_ok(f, tab)
                for t in np.flatnonzero(~ok).tolist():
                    assert rt._assign_plan(f, tab, t) is None, (f, t)
                cut += int((~ok).sum())
    assert cut > 0


@settings(max_examples=15, deadline=None)
@given(st.sampled_from(CUBE_SIZES), st.integers(0, 10_000))
def test_release_replace_sequence_parity(size, seed):
    """Interleaved commit/release traffic: after every mutation the
    batched search must agree with the naive oracle (the dirty-cube
    incremental refresh cannot drift from a from-scratch rebuild)."""
    num_xpus, cube_n = size
    rng = np.random.default_rng(seed)
    rt = ReconfigTorus(num_xpus, cube_n)
    probe_shapes = [(8, 4, 4), (6, 6, 1), (4, 4, 2), (2, 2, 2)]
    live = []
    jid = 0
    for _ in range(12):
        if live and rng.uniform() < 0.45:
            rt.release(live.pop(int(rng.integers(len(live)))))
        else:
            dims = tuple(int(rng.integers(1, 9)) for _ in range(3))
            for f in enumerate_folds(JobShape(dims),
                                     max_dim=rt.max_extent):
                plan = rt.place_fold(f)
                assert plan == rt.place_fold_naive(f)
                if plan is not None:
                    rt.commit(jid, plan)
                    live.append(jid)
                    jid += 1
                    break
        probe = JobShape(probe_shapes[int(rng.integers(len(probe_shapes)))])
        for f in enumerate_folds(probe, max_dim=rt.max_extent):
            assert rt.place_fold(f) == rt.place_fold_naive(f)
    rt.check_invariants()


@pytest.mark.parametrize("name", ["reconfig", "rfold", "rfold_be"])
@pytest.mark.parametrize("num_xpus,cube_n", CUBE_SIZES)
def test_schedule_parity_all_cube_sizes(name, num_xpus, cube_n):
    """Byte-identical schedules on a seeded trace: batched plan search
    + gated drain vs naive engine + ungated drain, at every cube
    size the paper evaluates."""
    cfg = TraceConfig(num_jobs=30, seed=13, target_load=1.8)
    fast = make_policy(name, num_xpus=num_xpus, cube_n=cube_n)
    res_fast = Simulator(fast, generate_trace(cfg), gated=True).run()
    naive = make_policy(name, num_xpus=num_xpus, cube_n=cube_n)
    naive.use_naive = True
    res_naive = Simulator(naive, generate_trace(cfg), gated=False).run()
    sig = lambda r: [(j.job_id, j.start, j.finish, j.dropped, j.slowdown,
                      j.placement_meta) for j in r.jobs]  # noqa: E731
    assert sig(res_fast) == sig(res_naive)
    assert res_fast.utilization_samples == res_naive.utilization_samples


def test_dedicate_chained_parity():
    """The chained-cube ablation flows through the fresh-bound prune
    (fresh == ncubes exactly for chained plans)."""
    rng = np.random.default_rng(3)
    rt = ReconfigTorus(512, 4, dedicate_chained=True)
    rt_ref = ReconfigTorus(512, 4, dedicate_chained=True)
    _random_fill(rt, rng, steps=8)
    rt_ref.occ[:] = rt.occ
    rt_ref.dedicated[:] = rt.dedicated
    rt_ref.bump_epoch()
    for dims in [(8, 4, 4), (16, 2, 2), (6, 6, 2), (4, 8, 2)]:
        for f in enumerate_folds(JobShape(dims), max_dim=rt.max_extent):
            assert rt.place_fold(f) == rt_ref.place_fold_naive(f), (dims, f)


# ------------------------------------------------- incremental refresh
@pytest.mark.parametrize("num_xpus,cube_n", [(4096, 2), (4096, 4)])
def test_dirty_cube_partial_refresh_matches_full(num_xpus, cube_n):
    """A commit touching few cubes takes the partial-refresh path (only
    dirty rows recomputed); derived state must equal a from-scratch
    rebuild."""
    rng = np.random.default_rng(7)
    rt = ReconfigTorus(num_xpus, cube_n)
    _random_fill(rt, rng, steps=10)
    shape = (2, 2, cube_n)
    rt._shape_fit_mask(shape)          # warm caches at this epoch
    fold = enumerate_folds(JobShape((2, 2, 2)), max_dim=rt.max_extent)[0]
    plan = rt.place_fold(fold)
    assert plan is not None
    rt.commit(12345, plan)             # marks only the touched cubes dirty
    assert rt._dirty                   # partial path is armed
    mask_after = rt._shape_fit_mask(shape).copy()
    cnt_after = rt._free_cnt.copy()

    fresh = ReconfigTorus(num_xpus, cube_n)
    fresh.occ[:] = rt.occ
    fresh.dedicated[:] = rt.dedicated
    fresh.bump_epoch()                 # full rebuild
    assert np.array_equal(mask_after, fresh._shape_fit_mask(shape))
    assert np.array_equal(cnt_after, fresh._free_cnt)

    rt.release(12345)                  # partial again, the other way
    fresh2 = ReconfigTorus(num_xpus, cube_n)
    fresh2.occ[:] = rt.occ
    fresh2.bump_epoch()
    assert np.array_equal(rt._shape_fit_mask(shape),
                          fresh2._shape_fit_mask(shape))
    assert np.array_equal(rt._free_cnt, fresh2._free_cnt)
    rt.check_invariants()


def test_plan_table_is_prefix_sorted():
    """Fold tables visit offsets best-prefix-first with the offset
    product index as the stable tiebreak."""
    for dims in [(8, 4, 4), (18, 1, 1), (4, 8, 2), (3, 3, 3)]:
        for f in enumerate_folds(JobShape(dims), max_dim=64):
            tab = fold_plan_table(f, 4, 64)
            if tab is None:
                continue
            keys = list(zip(tab.nbroken.tolist(), tab.ncubes.tolist(),
                            tab.links.tolist()))
            assert keys == sorted(keys)


# ------------------------------------------------- fitmask multi-query
def test_block_sums_from_ii_multi_matches_single():
    rng = np.random.default_rng(5)
    occ = rng.uniform(size=(9, 4, 4, 4)) < 0.4
    ii = fitmask.batched_integral_image(occ)
    locals_ = []
    for _ in range(20):
        lo = rng.integers(0, 4, size=3)
        hi = [int(rng.integers(int(loc) + 1, 5)) for loc in lo]
        locals_.append(tuple((int(loc), h) for loc, h in zip(lo, hi)))
    multi = fitmask.block_sums_from_ii_multi(ii, locals_)
    assert multi.shape == (len(locals_), occ.shape[0])
    for k, loc in enumerate(locals_):
        assert np.array_equal(multi[k], fitmask.block_sums_from_ii(ii, loc))
    free = fitmask.block_free_from_ii_multi(ii, locals_)
    assert np.array_equal(free, multi == 0)


def test_host_free_counts_helper():
    rng = np.random.default_rng(6)
    occ = rng.uniform(size=(5, 3, 3, 3)) < 0.5
    ref = np.array([(~occ[i]).sum() for i in range(5)])
    assert np.array_equal(fitmask.free_counts(occ), ref)


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
