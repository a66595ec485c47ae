"""One device call per occupancy refresh on the ``pallas`` engine.

The multibox program answers each grid's free count beside its planes;
the engine keeps those counts per thread, so a ``free_counts`` on the
occupancy it has just answered needs no device call; and the
reconfigurable torus asks for the planes before the counts whenever a
refresh needs planes. Dispatches are counted at ``ops._on_device``,
the one place the device engines launch a program. Interpret mode on
the CPU."""
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.reconfig import ReconfigTorus
from repro.kernels.fitmask import ops
from repro.sim.fleet import QueryBroker

BOXES = ((1, 1, 1), (2, 2, 2), (4, 2, 1), (3, 3, 3))
LOCALS = (((0, 2), (0, 2), (0, 2)), ((1, 4), (0, 4), (2, 3)),
          ((0, 4), (0, 1), (0, 3)))
HOST = ops.NumpyEngine()


def _occ(seed, b=8, p=0.3):
    return np.random.default_rng(seed).uniform(size=(b, 4, 4, 4)) < p


@pytest.fixture
def calls(monkeypatch):
    """Every device call the engines make, as its ``kind``."""
    made = []
    inner = ops._on_device

    def counting(engine, kind, *args, **kw):
        made.append(kind)
        return inner(engine, kind, *args, **kw)

    monkeypatch.setattr(ops, "_on_device", counting)
    return made


def _torus(client=None, seed=0):
    """A pallas torus of 8 cubes with three sub-block shapes seen and
    their masks cached (one full refresh done)."""
    rt = ReconfigTorus(512, 4, engine="pallas", mask_client=client)
    rt.occ[:] = _occ(seed)
    rt.bump_epoch()
    for local in LOCALS:
        rt._block_free_mask(local)
    return rt


def _assert_matches_host(rt):
    np.testing.assert_array_equal(rt._free_cnt, HOST.free_counts(rt.occ))
    for shape, mask in rt._shape_masks.items():
        want = HOST.multibox(rt.occ, [shape])[:, 0] != 0
        np.testing.assert_array_equal(mask, want)


def test_multibox_answers_the_free_counts(calls):
    eng = ops.PallasEngine()
    occ = _occ(1, b=5)
    planes = eng.multibox(occ, BOXES)
    free = eng.free_counts(occ)
    assert calls == ["multibox"]
    np.testing.assert_array_equal(planes, HOST.multibox(occ, BOXES))
    np.testing.assert_array_equal(free, HOST.free_counts(occ))


def test_partial_refresh_is_one_device_call(calls):
    rt = _torus()
    rt.occ[3, 0, 0, 0] = ~rt.occ[3, 0, 0, 0]
    rt.occ[5, 1:3, 2, 2] = True
    rt._mark_dirty({3, 5})
    calls.clear()
    rt._derived()
    assert calls == ["multibox"]
    _assert_matches_host(rt)


def test_full_refresh_with_cached_shapes_is_one_device_call(calls):
    rt = _torus()
    rt.occ[:] = _occ(2)
    rt.bump_epoch()
    calls.clear()
    rt._derived()
    for local in LOCALS:
        rt._block_free_mask(local)
    assert calls == ["multibox"]
    assert set(rt._shape_masks) == rt._seen_shapes
    _assert_matches_host(rt)


def test_first_refresh_with_no_shapes_asks_only_the_counts(calls):
    rt = ReconfigTorus(512, 4, engine="pallas")
    rt.occ[:] = _occ(3)
    rt.bump_epoch()
    rt._derived()
    assert calls == ["free_counts"]
    _assert_matches_host(rt)


def test_free_counts_on_another_occupancy_dispatches(calls):
    eng = ops.PallasEngine()
    occ, other = _occ(4), _occ(5)
    eng.multibox(occ, BOXES)
    np.testing.assert_array_equal(eng.free_counts(other),
                                  HOST.free_counts(other))
    assert calls == ["multibox", "free_counts"]
    # Another shape of the same cells is another occupancy too.
    eng.multibox(occ, BOXES)
    np.testing.assert_array_equal(eng.free_counts(occ[:4]),
                                  HOST.free_counts(occ[:4]))
    assert calls[2:] == ["multibox", "free_counts"]


def test_kept_counts_follow_the_occupancy_not_the_array(calls):
    """The caller may change its array after the planes came back: the
    kept counts are for the occupancy answered, not for the object."""
    eng = ops.PallasEngine()
    occ = _occ(6)
    eng.multibox(occ, BOXES)
    occ[0] = True
    np.testing.assert_array_equal(eng.free_counts(occ),
                                  HOST.free_counts(occ))
    assert calls == ["multibox", "free_counts"]


def test_kept_counts_serve_one_free_counts_call(calls):
    eng = ops.PallasEngine()
    occ = _occ(7)
    eng.multibox(occ, BOXES)
    first, second = eng.free_counts(occ), eng.free_counts(occ)
    assert calls == ["multibox", "free_counts"]
    np.testing.assert_array_equal(first, HOST.free_counts(occ))
    np.testing.assert_array_equal(second, HOST.free_counts(occ))


def test_threads_never_get_each_others_counts(calls):
    """Two threads interleave multibox and free_counts on one engine:
    each gets its own counts, and neither dispatches for them."""
    eng = ops.PallasEngine()
    occs = [_occ(8, p=0.1), _occ(9, p=0.6)]
    got = [None, None]
    step = threading.Barrier(2, timeout=60)

    def sim(i):
        if i == 1:
            step.wait()           # thread 0's planes come first
        eng.multibox(occs[i], BOXES)
        if i == 0:
            step.wait()
        step.wait()               # both have asked for planes
        got[i] = eng.free_counts(occs[i])

    threads = [threading.Thread(target=sim, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    assert calls == ["multibox", "multibox"]
    for occ, free in zip(occs, got):
        np.testing.assert_array_equal(free, HOST.free_counts(occ))


def test_many_threads_keep_their_own_counts(calls):
    """More threads than cores, switching as often as the interpreter
    allows: every thread's counts are its own and none dispatches."""
    eng = ops.PallasEngine()
    n_threads, rounds = 12, 3
    wrong = []

    def sim(i):
        for r in range(rounds):
            occ = _occ(100 + rounds * i + r, b=2, p=0.1 + 0.05 * i)
            eng.multibox(occ, BOXES[:2])
            if not np.array_equal(eng.free_counts(occ),
                                  HOST.free_counts(occ)):
                wrong.append((i, r))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=sim, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert wrong == []
    assert calls == ["multibox"] * (n_threads * rounds)


def test_reuse_opens_an_engine_reuse_span(tmp_path):
    eng = ops.PallasEngine()
    occ = _occ(10, b=6)
    t0 = time.perf_counter()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path / "trace"), profiler_options=opts):
        eng.multibox(occ, BOXES)
        eng.free_counts(occ)
    recs = [r for r in obs.records() if r.t0 >= t0]
    assert [r.tags["kind"] for r in recs if r.name == "engine.call"] == \
        ["multibox"]
    reuse = [r for r in recs if r.name == "engine.reuse"]
    assert len(reuse) == 1
    assert reuse[0].tags == {"kind": "free_counts", "b": 6}


def test_torus_refresh_through_the_broker_is_one_flush(calls):
    broker = QueryBroker("pallas")
    rt = _torus(client=broker)
    rt.occ[:] = _occ(11)
    rt.bump_epoch()
    flushes, hits = broker.stats.flushes, broker.stats.fc_cache_hits
    calls.clear()
    rt._derived()
    assert broker.stats.flushes == flushes + 1
    assert broker.stats.fc_cache_hits == hits + 1
    assert calls == ["multibox"]
    _assert_matches_host(rt)
