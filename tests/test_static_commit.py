"""Committing a placed fold on a static torus.

``_StaticBase._commit_fold`` builds an allocation from the fold's
origin-free template and adds only the origin. These tests hold it to
the per-XPU construction it replaced, written out here as
:func:`_per_xpu_commit`: for every ring edge of the placed job, the
physical pair, the torus-neighbour test, the direct-or-full-span-wrap
test and the cut-link test. They also hold ``StaticTorus.commit`` and
``release`` to their refusals and their state, the template to being
built once per (fold, torus) and the ``plan.search`` tags to recording
only under a profiler session.
"""
import time

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.allocator import (FirstFitPolicy, FoldingPolicy,
                                  _commit_template)
from repro.core.folding import enumerate_folds, ring_edges, verify_fold
from repro.core.geometry import JobShape, is_torus_neighbor
from repro.core.torus import StaticTorus, canon_link


def _per_xpu_commit(torus, fold, origin, broken):
    """The allocation a placed fold took before templates: coords in
    C order over the job's logical grid, and each ring edge's physical
    link where it is a torus link, direct or a wrap link of an axis the
    box spans in full; a cut link is left out and breaks its axis."""
    dims = torus.dims
    d0, d1, d2 = fold.job_dims
    coords = []
    for i in range(d0):
        for j in range(d1):
            for k in range(d2):
                e = fold.embed((i, j, k))
                coords.append(tuple(o + v for o, v in zip(origin, e)))
    wrap = tuple(b == d for b, d in zip(fold.box, dims))
    links, extra = [], set()
    for (u, v, _ax) in ring_edges(fold.job_dims):
        pu = tuple(o + e for o, e in zip(origin, fold.embed(u)))
        pv = tuple(o + e for o, e in zip(origin, fold.embed(v)))
        if not is_torus_neighbor(pu, pv, dims, torus.wrap_flags()):
            continue
        direct = all(abs(a - b) <= 1 for a, b in zip(pu, pv))
        if direct or any(wrap[ax] and abs(pu[ax] - pv[ax]) == dims[ax] - 1
                         for ax in range(3)):
            link = canon_link(pu, pv)
            if link in torus.cut_links:
                extra.add(next(ax for ax in range(3) if pu[ax] != pv[ax]))
            else:
                links.append(link)
    if extra:
        broken = tuple(sorted(set(broken) | extra))
    meta = {"fold": str(fold), "kind": fold.kind, "box": fold.box,
            "origin": origin, "broken_rings": broken}
    return tuple(coords), frozenset(links), meta, broken


def _fold(dims, job, kind, box):
    folds = [f for f in enumerate_folds(JobShape(job), max_dim=max(dims))
             if f.kind == kind and f.box == box]
    assert folds, (job, kind, box)
    return folds[0]


# (torus, job, fold kind, box): identity, 1-D snake, 2-D and 3-D
# halving folds; boxes that span a torus axis in full and boxes that do
# not, on 16^3 and on a 4x4x8 torus.
FOLDS = [
    ((16, 16, 16), (4, 4, 2), "identity", (4, 4, 2)),
    ((16, 16, 16), (16, 2, 2), "identity", (16, 2, 2)),
    ((16, 16, 16), (16, 16, 16), "identity", (16, 16, 16)),
    ((16, 16, 16), (32, 1, 1), "cycle1d", (2, 4, 4)),
    ((16, 16, 16), (64, 1, 1), "cycle1d", (16, 2, 2)),
    ((16, 16, 16), (8, 4, 1), "ring_x_ham", (8, 2, 2)),
    ((16, 16, 16), (16, 8, 1), "ring_x_ham", (16, 2, 4)),
    ((16, 16, 16), (8, 4, 2), "halving3d", (8, 2, 4)),
    ((16, 16, 16), (16, 16, 2), "halving3d", (16, 8, 4)),
    ((4, 4, 8), (4, 2, 1), "identity", (4, 2, 1)),
    ((4, 4, 8), (8, 1, 1), "cycle1d", (2, 2, 2)),
    ((4, 4, 8), (8, 4, 2), "halving3d", (4, 4, 4)),
    ((4, 4, 8), (4, 8, 1), "ring_x_ham", (4, 4, 2)),
]
# Origins at 0 and against the far faces; no cut link, a cut link the
# placement does not use (there is none where the box is the whole
# torus), and a cut link of the placement's own ring, which breaks that
# ring's axis.
CASES = [(*f, where, cut) for f in FOLDS for where in ("zero", "far")
         for cut in ("none", "elsewhere", "own")
         if not (cut == "elsewhere" and f[0] == f[3])]


def _origin(where, dims, box):
    if where == "zero":
        return (0, 0, 0)
    return tuple(d - b for d, b in zip(dims, box))


def _axis(link):
    u, v = link
    return next(ax for ax in range(3) if u[ax] != v[ax])


def _unused_link(dims, own):
    for u in np.ndindex(*dims):
        for ax in range(3):
            v = list(u)
            v[ax] = (v[ax] + 1) % dims[ax]
            link = canon_link(u, tuple(v))
            if link not in own:
                return link
    raise AssertionError("every link is the placement's")


@pytest.mark.parametrize("policy", [FoldingPolicy, FirstFitPolicy])
@pytest.mark.parametrize(
    "dims,job,kind,box,where,cut", CASES,
    ids=[f"{'x'.join(map(str, c[0]))}-{c[2]}-{'x'.join(map(str, c[3]))}"
         f"-{c[4]}-{c[5]}" for c in CASES])
def test_commit_equals_the_per_xpu_construction(policy, dims, job, kind, box,
                                                where, cut):
    pol = policy(dims)
    torus = pol.torus
    fold = _fold(dims, job, kind, box)
    origin = _origin(where, dims, box)
    ok, broken = verify_fold(fold, pol._wrap_for_box(fold.box, origin))
    assert ok
    broken = tuple(broken)
    own = _per_xpu_commit(torus, fold, origin, broken)[1]
    if cut == "own":
        # a link of a ring that is intact until the cut, where there is one
        intact = sorted(l for l in own if _axis(l) not in broken)
        severed = (intact or sorted(own))[len(intact or own) // 2]
        torus.cut_link(*severed)
    elif cut == "elsewhere":
        torus.cut_link(*_unused_link(dims, own))
    coords, links, meta, want_broken = _per_xpu_commit(torus, fold, origin,
                                                       broken)
    if cut == "own":
        assert _axis(severed) in want_broken and severed not in links
    else:
        assert want_broken == broken
    placement = pol._commit_fold(7, fold, origin, broken,
                                 obs.span("plan.search"))
    alloc = torus.allocations[7]
    assert alloc.coords == coords
    assert alloc.links == links
    assert alloc.meta == meta and placement.meta == meta
    assert placement.broken_rings == want_broken
    assert torus.busy_xpus == len(coords)
    torus.check_invariants()
    # The template is origin-free: the same fold again, at the other
    # origin, matches too (and the first commit left it unchanged).
    pol.release(7)
    other = _origin("far" if where == "zero" else "zero", dims, box)
    coords, links, meta, want_broken = _per_xpu_commit(torus, fold, other,
                                                       broken)
    placement = pol._commit_fold(8, fold, other, broken,
                                 obs.span("plan.search"))
    alloc = torus.allocations[8]
    assert (alloc.coords, alloc.links, alloc.meta) == (coords, links, meta)
    assert placement.broken_rings == want_broken
    torus.check_invariants()


@pytest.mark.parametrize("policy", ["folding", "firstfit"])
def test_try_place_stream_equals_the_per_xpu_construction(policy):
    """Placements the policies choose themselves, on a filling 8^3
    torus: every allocation is the per-XPU construction of its fold at
    its origin."""
    pol = (FoldingPolicy if policy == "folding" else FirstFitPolicy)((8, 8, 8))
    shapes = [(4, 4, 2), (8, 1, 1), (16, 1, 1), (4, 2, 2), (8, 4, 1),
              (2, 2, 2), (8, 8, 2), (4, 4, 4), (6, 1, 1), (2, 1, 1)]
    placed = 0
    for jid, shape in enumerate(shapes * 3):
        if jid == 12:
            pol.release(1)
            pol.release(3)
        p = pol.try_place(jid, JobShape(shape))
        if p is None:
            continue
        placed += 1
        alloc = pol.torus.allocations[jid]
        fold = next(f for f in enumerate_folds(JobShape(shape), max_dim=8)
                    if str(f) == alloc.meta["fold"]
                    and f.box == alloc.meta["box"])
        want = _per_xpu_commit(pol.torus, fold, alloc.meta["origin"],
                               alloc.meta["broken_rings"])
        assert (alloc.coords, alloc.links, alloc.meta) == want[:3]
    assert placed > 10
    pol.torus.check_invariants()


# -- StaticTorus.commit / release --------------------------------------

def _state(t):
    return (t.occ.copy(), t.owner.copy(), dict(t.link_owner), t.busy_xpus,
            sorted(t.allocations))


def _line(x, y, n):
    coords = [(x, y, z) for z in range(n)]
    links = [canon_link(coords[i], coords[i + 1]) for i in range(n - 1)]
    return coords, links


def _assert_same(a, b):
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()
    assert a[2:] == b[2:]


@pytest.mark.parametrize("refusal", ["duplicate", "owned_xpu", "owned_link",
                                     "cut_link"])
@pytest.mark.parametrize("with_cells", [False, True])
def test_commit_refusals_change_nothing(refusal, with_cells):
    t = StaticTorus((4, 4, 4))
    coords, links = _line(0, 0, 4)
    t.commit(1, coords, links)
    if refusal == "duplicate":
        bad, bad_links = [(1, 0, 0), (1, 0, 1), (1, 0, 0)], []
        match = "duplicate XPUs"
    elif refusal == "owned_xpu":
        # the second and third XPUs are job 1's: the message names the
        # first of them
        bad = [(1, 0, 0), (0, 0, 1), (0, 0, 2)]
        bad_links = []
        match = r"XPU \(0, 0, 1\) already owned by 1"
    elif refusal == "owned_link":
        bad = [(1, 1, 0), (1, 1, 1)]
        bad_links = [canon_link((1, 1, 0), (1, 1, 1)), links[0]]
        match = r"link .* already owned by job 1"
    else:
        t.cut_link((2, 2, 0), (2, 2, 1))
        bad = [(2, 2, 0), (2, 2, 1)]
        bad_links = [canon_link((2, 2, 0), (2, 2, 1))]
        match = "is cut"
    before = _state(t)
    cells = (np.ravel_multi_index(np.array(bad).T, t.dims)
             if with_cells else None)
    with pytest.raises(ValueError, match=match):
        t.commit(2, bad, bad_links, cells=cells)
    _assert_same(_state(t), before)
    t.check_invariants()


def test_commit_and_release_write_the_grid_in_one_step():
    t = StaticTorus((4, 4, 8))
    empty = _state(t)
    coords, links = _line(3, 2, 8)
    alloc = t.commit(5, coords, links, {"kind": "line"})
    assert alloc.coords == tuple(coords) and alloc.links == frozenset(links)
    assert list(alloc.cells) == [np.ravel_multi_index(c, t.dims)
                                 for c in coords]
    for c in coords:
        assert t.occ[c] and t.owner[c] == 5
    assert t.occ.sum() == 8 and (t.owner == 5).sum() == 8
    assert t.busy_xpus == 8 and all(t.link_owner[l] == 5 for l in links)
    t.check_invariants()
    # Given its cells, the commit is the same.
    t2 = StaticTorus((4, 4, 8))
    t2.commit(5, coords, links, {"kind": "line"}, cells=alloc.cells)
    _assert_same(_state(t2), _state(t))
    t.release(5)
    _assert_same(_state(t), empty)
    t.check_invariants()


# -- the template --------------------------------------------------------

def test_template_is_built_once_per_fold_and_torus():
    # A torus no other test commits on, so that the first call builds.
    dims, wrap = (16, 12, 12), (True, True, True)
    fold = _fold(dims, (16, 8, 1), "ring_x_ham", (16, 2, 4))
    first, memo = _commit_template(fold, dims, wrap, (True, False, False))
    assert not memo
    again, memo = _commit_template(fold, dims, wrap, (True, False, False))
    assert memo and again is first
    # A fold from the shared enumeration cache is the same instance.
    same = _fold(dims, (16, 8, 1), "ring_x_ham", (16, 2, 4))
    assert same is fold
    assert _commit_template(same, dims, wrap, (True, False, False))[1]
    # Another box wrap or another torus builds its own.
    for other_dims, box_wrap in ((dims, (False, False, False)),
                                 ((32, 12, 12), (False, False, False))):
        other, memo = _commit_template(fold, other_dims, wrap, box_wrap)
        assert not memo and other is not first
        assert _commit_template(fold, other_dims, wrap, box_wrap)[1]
    # Without the span wrap, the x ring's closing edges take no link.
    links = _commit_template(fold, dims, wrap, (False, False, False))[0][0]
    assert len(links) < len(first[0])


# -- the plan.search tags -------------------------------------------------

def _profiled(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(str(tmp_path / "trace"),
                              profiler_options=opts)


# Tori no other test commits on, so that each first commit here builds
# its template.
@pytest.mark.parametrize("policy,dims", [(FoldingPolicy, (10, 6, 2)),
                                         (FirstFitPolicy, (12, 6, 2))])
def test_commit_tags_only_while_recording(tmp_path, policy, dims):
    pol = policy(dims)
    t0 = time.perf_counter()
    assert pol.try_place(0, JobShape((2, 2, 2))) is not None
    assert not [r for r in obs.records() if r.t0 >= t0]
    pol.release(0)
    whole = JobShape(dims)
    t0 = time.perf_counter()
    with _profiled(tmp_path):
        assert pol.try_place(1, whole) is not None     # builds
        pol.release(1)
        assert pol.try_place(2, whole) is not None     # reuses
        assert pol.try_place(3, whole) is None         # no room: no commit
    searches = [r.tags for r in obs.records()
                if r.t0 >= t0 and r.name == "plan.search"]
    assert [(s.get("commits"), s.get("commit_memo")) for s in searches] \
        == [(1, 0), (1, 1), (None, None)]
    assert [s["placed"] for s in searches] == [True, True, False]
