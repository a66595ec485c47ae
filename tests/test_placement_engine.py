"""Parity + cache-invalidation tests for the incremental placement
engine (vectorized place_fold, epoch caches, gated simulator).

The engine must be behavior-preserving: identical placement decisions
and SimResults on fixed seeds versus the retained naive path."""
import numpy as np
import pytest

from repro.core import fitmask
from repro.core.allocator import make_policy
from repro.core.folding import _verify_fold_reference, enumerate_folds
from repro.core.geometry import JobShape
from repro.core.reconfig import ReconfigTorus
from repro.core.torus import StaticTorus
from repro.sim.simulator import Simulator
from repro.traces.generator import TraceConfig, generate_trace

POLICY_MATRIX = [
    ("firstfit", dict(dims=(8, 8, 8))),
    ("folding", dict(dims=(8, 8, 8))),
    ("reconfig", dict(num_xpus=512, cube_n=4)),
    ("rfold", dict(num_xpus=512, cube_n=4)),
    ("rfold_be", dict(num_xpus=512, cube_n=4)),
]


def _job_sig(res):
    return [(j.job_id, j.start, j.finish, j.dropped, j.slowdown,
             j.placement_meta) for j in res.jobs]


# ------------------------------------------------------------- sim parity
@pytest.mark.parametrize("name,kw", POLICY_MATRIX)
def test_backfill_watermark_parity(name, kw):
    """Backfill + per-shape feasibility watermark == backfill with the
    naive retry-every-job drain: byte-identical job records, utilization
    samples and JCR on seeded traces (a shape that failed to place can
    only be unblocked by a completion, so skipping its retries until
    then must not change any scheduling decision)."""
    for seed, load in [(7, 1.5), (11, 2.5)]:
        cfg = TraceConfig(num_jobs=50, seed=seed, target_load=load)
        gated = Simulator(make_policy(name, **kw), generate_trace(cfg),
                          backfill=True, gated=True).run()
        naive = Simulator(make_policy(name, **kw), generate_trace(cfg),
                          backfill=True, gated=False).run()
        assert _job_sig(gated) == _job_sig(naive)
        assert gated.utilization_samples == naive.utilization_samples
        assert gated.jcr == naive.jcr


def test_backfill_watermark_clears_on_completion():
    """After a completion frees capacity, previously-infeasible shapes
    must be retried (the watermark resets): a big job blocked behind a
    long-running one starts as soon as the cluster drains."""
    from repro.sim.job import Job
    from repro.core.geometry import JobShape
    jobs = [Job(0, 0.0, duration=10.0, shape=JobShape((8, 8, 4))),
            Job(1, 1.0, duration=5.0, shape=JobShape((8, 8, 8))),
            Job(2, 2.0, duration=1.0, shape=JobShape((2, 2, 2)))]
    res = Simulator(make_policy("rfold", num_xpus=512, cube_n=4), jobs,
                    backfill=True, gated=True).run()
    by_id = {j.job_id: j for j in res.jobs}
    assert by_id[2].start == pytest.approx(2.0)    # backfilled past job 1
    assert by_id[1].start == pytest.approx(10.0)   # retried at completion
    assert res.jcr == 1.0


@pytest.mark.parametrize("name,kw", POLICY_MATRIX)
def test_simulator_parity_fast_vs_naive(name, kw):
    """Fast engine + gated drain == naive engine + ungated drain:
    byte-identical job records, utilization samples and JCR."""
    cfg = TraceConfig(num_jobs=40, seed=7, target_load=1.5)

    fast = make_policy(name, **kw)
    res_fast = Simulator(fast, generate_trace(cfg), gated=True).run()

    naive = make_policy(name, **kw)
    naive.use_naive = True  # no-op for static policies
    res_naive = Simulator(naive, generate_trace(cfg), gated=False).run()

    assert _job_sig(res_fast) == _job_sig(res_naive)
    assert res_fast.utilization_samples == res_naive.utilization_samples
    assert res_fast.jcr == res_naive.jcr


def _random_fill(rt: ReconfigTorus, rng, steps=18):
    """Drive the torus into a random occupancy via real commit/release."""
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


# (seed, num_xpus, cube_n, variant): the first four are 4^3 cubes as
# they always were; the rest add cube sizes 2 and 8, the chained-cube
# ablation and failed OCS ports.
PARITY_CASES = [pytest.param(s, 512, 4, "plain", id=str(s)) for s in range(4)] + [
    pytest.param(4, 512, 2, "plain", id="4-n2"),
    pytest.param(5, 4096, 8, "plain", id="5-n8"),
    pytest.param(6, 512, 4, "chained", id="6-chained"),
    pytest.param(7, 512, 2, "chained", id="7-n2-chained"),
    pytest.param(8, 512, 4, "ocs", id="8-ocs"),
    pytest.param(9, 4096, 8, "ocs", id="9-n8-ocs"),
]


@pytest.mark.parametrize("seed,num_xpus,cube_n,variant", PARITY_CASES)
@pytest.mark.parametrize("offset_search", [True, False])
def test_place_fold_parity_random_occupancy(seed, num_xpus, cube_n, variant,
                                            offset_search):
    """place_fold == the naive oracle, with no bound and against an
    incumbent's score (then only a strictly better plan comes back)."""
    rng = np.random.default_rng(seed)
    rt = ReconfigTorus(num_xpus, cube_n, dedicate_chained=variant == "chained")
    if variant == "ocs":
        rt.fail_ocs_port(rng.choice(rt.num_cubes, size=rt.num_cubes // 4,
                                    replace=False))
    _random_fill(rt, rng)
    for dims in [(8, 4, 4), (18, 1, 1), (4, 8, 2), (6, 6, 1), (3, 3, 3),
                 (16, 2, 2), (12, 8, 4)]:
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


@pytest.mark.parametrize("name,kw", [("reconfig", dict(num_xpus=512, cube_n=4)),
                                     ("rfold", dict(num_xpus=512, cube_n=4)),
                                     ("rfold_be", dict(num_xpus=512, cube_n=4)),
                                     ("rfold", dict(num_xpus=512, cube_n=2))])
def test_can_ever_place_analytic_matches_naive(name, kw):
    rng = np.random.default_rng(42)
    fast = make_policy(name, **kw)
    naive = make_policy(name, **kw)
    naive.use_naive = True
    shapes = [tuple(int(rng.integers(1, 12)) for _ in range(3))
              for _ in range(40)] + [(8, 8, 8), (64, 1, 1), (9, 9, 9)]
    for dims in shapes:
        s = JobShape(dims)
        assert fast.can_ever_place(s) == naive.can_ever_place(s), dims


# ----------------------------------------------------- epoch invalidation
@pytest.mark.parametrize("seed", [0, 5, 9])
def test_epoch_cache_commit_release_roundtrip(seed):
    """commit -> release returns every cached query to its pre-commit
    answer (the epoch counter must invalidate correctly both ways)."""
    rng = np.random.default_rng(seed)
    rt = ReconfigTorus(512, 4)
    _random_fill(rt, rng, steps=6)
    probe = [f for s in [(8, 4, 4), (2, 2, 2), (4, 1, 1)]
             for f in enumerate_folds(JobShape(s), max_dim=rt.max_extent)]
    local = ((0, 2), (0, 4), (0, 4))
    before_mask = rt._block_free_mask(local).copy()
    before_plans = [rt.place_fold(f) for f in probe]
    victim = next(p for p in before_plans if p is not None)

    rt.commit(999, victim)
    during_mask = rt._block_free_mask(local)
    during_plans = [rt.place_fold(f) for f in probe]
    # the commit must be visible through the cache
    assert rt.busy_xpus == int(rt.occ.sum())
    assert during_plans != before_plans or not np.array_equal(
        before_mask, during_mask)

    rt.release(999)
    assert np.array_equal(rt._block_free_mask(local), before_mask)
    assert [rt.place_fold(f) for f in probe] == before_plans
    assert rt.busy_xpus == int(rt.occ.sum())
    rt.check_invariants()


def test_static_torus_epoch_cache_roundtrip():
    t = StaticTorus((8, 8, 8))
    before = {b: t.find_free_box(b) for b in [(8, 8, 8), (2, 2, 2), (4, 4, 1)]}
    t.commit_box(1, (0, 0, 0), (4, 4, 4))
    assert t.find_free_box((8, 8, 8)) is None   # cache saw the commit
    assert t.busy_xpus == 64
    t.release(1)
    for b, origin in before.items():
        assert t.find_free_box(b) == origin
    assert t.busy_xpus == 0
    t.check_invariants()


def test_bump_epoch_after_direct_mutation():
    rt = ReconfigTorus(128, 4)
    fold = enumerate_folds(JobShape((4, 4, 4)), max_dim=8)[0]
    assert rt.place_fold(fold) is not None      # caches built while empty
    rt.occ[:, :, :, :] = True                   # direct mutation...
    rt.bump_epoch()                             # ...must be announced
    assert rt.place_fold(fold) is None
    assert rt.busy_xpus == 128


# -------------------------------------------------------- verify / fitmask
def test_vectorized_verify_matches_reference():
    wraps = [(False, False, False), (True, True, True), (True, False, False),
             (False, True, True)]
    for dims in [(18, 1, 1), (4, 8, 2), (6, 4, 1), (3, 3, 3), (12, 2, 2),
                 (2, 2, 2), (5, 1, 1)]:
        for f in enumerate_folds(JobShape(dims), max_dim=64):
            for w in wraps:
                assert _verify_fold_impl_fresh(f, w) == \
                    _verify_fold_reference(f, w), (f, w)


def _verify_fold_impl_fresh(fold, wrap):
    from repro.core.folding import _verify_fold_impl
    return _verify_fold_impl(fold, wrap)


def test_fit_mask_batched_matches_single():
    rng = np.random.default_rng(3)
    occ = rng.uniform(size=(5, 6, 6, 6)) < 0.3
    for box in [(1, 1, 1), (2, 3, 1), (4, 4, 4), (6, 6, 6), (7, 1, 1)]:
        batched = fitmask.fit_mask_batched(occ, box)
        for i in range(occ.shape[0]):
            assert np.array_equal(batched[i], fitmask.fit_mask(occ[i], box))


def test_integral_image_block_queries():
    rng = np.random.default_rng(4)
    occ = rng.uniform(size=(7, 4, 4, 4)) < 0.4
    ii = fitmask.batched_integral_image(occ)
    for _ in range(30):
        lo = rng.integers(0, 4, size=3)
        hi = [int(rng.integers(l + 1, 5)) for l in lo]
        local = tuple((int(l), h) for l, h in zip(lo, hi))
        ref = np.array([occ[i][tuple(slice(l, h) for l, h in local)].sum()
                        for i in range(occ.shape[0])])
        assert np.array_equal(fitmask.block_sums_from_ii(ii, local), ref)
