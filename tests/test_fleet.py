"""Fleet simulation layer tests (repro.sim.fleet + the eval runner's
two-level pool): broker coalescing and bit-exactness, continuous
(quorum/timeout) flush scheduling, byte-identical fleet records vs the
sequential single-sim path, worker-side checkpointing, and
chunking/auto-sizing."""
import threading
import time

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.eval import EvalRunner, make_tasks
from repro.eval.runner import (iter_checkpoints, make_fleet_chunks,
                               run_fleet_tasks, task_grid_bucket)
from repro.kernels.fitmask import ops
from repro.sim.fleet import Fleet, QueryBroker

# Small matrix covering both cluster models and two grid cell shapes.
CONFIGS = [
    ("RFold (4^3)", "rfold", dict(num_xpus=512, cube_n=4)),
    ("Reconfig (4^3)", "reconfig", dict(num_xpus=512, cube_n=4)),
    ("Folding (8^3)", "folding", dict(dims=(8, 8, 8))),
    ("FirstFit (8^3)", "firstfit", dict(dims=(8, 8, 8))),
]


def _tasks(runs=2, num_jobs=25):
    return make_tasks(CONFIGS, runs=runs, num_jobs=num_jobs, load=1.5,
                      seed0=100)


def _strip(records):
    return [{k: v for k, v in r.items() if k != "sim_s"} for r in records]


def _occ(rng, b, cell):
    return rng.random((b,) + cell) < 0.4


# ------------------------------------------------------------- broker
def test_solo_broker_matches_inline_engine():
    """An unregistered broker answers immediately and bit-exactly."""
    rng = np.random.default_rng(0)
    occ = _occ(rng, 3, (6, 6, 6))
    boxes = ((2, 2, 1), (3, 1, 2), (6, 6, 6))
    broker = QueryBroker("numpy")
    ref = np.asarray(ops.get_engine("numpy").multibox(occ, boxes))
    np.testing.assert_array_equal(broker.multibox(occ, boxes), ref)
    np.testing.assert_array_equal(
        broker.free_counts(occ),
        np.asarray(ops.get_engine("numpy").free_counts(occ)))
    assert broker.stats.engine_calls == 2
    assert broker.stats.batched_calls == 0


def test_broker_coalesces_and_splits_exactly():
    """Three concurrent requests over the same cell shape: one engine
    call, every requester gets its own grids and its own boxes back,
    in its own order."""
    rng = np.random.default_rng(1)
    cell = (5, 5, 5)
    reqs = [(_occ(rng, b, cell), boxes) for b, boxes in
            [(1, ((2, 2, 2), (1, 1, 4))),
             (4, ((1, 1, 4), (3, 3, 1))),
             (2, ((5, 5, 5),))]]
    broker = QueryBroker("numpy")
    results = [None] * len(reqs)

    def worker(i):
        occ, boxes = reqs[i]
        results[i] = broker.multibox(occ, boxes)

    for _ in reqs:
        broker.register()
    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for (occ, boxes), out in zip(reqs, results):
        ref = np.asarray(ops.get_engine("numpy").multibox(occ, boxes))
        np.testing.assert_array_equal(out, ref)
    assert broker.stats.engine_calls == 1          # one coalesced call
    assert broker.stats.batched_calls == 1
    assert broker.stats.max_coalesced == 3
    assert broker.stats.max_grids == 7             # 1 + 4 + 2 stacked


def test_broker_buckets_by_cell_shape():
    """Different grid cell shapes cannot share a pass: two engine
    calls, both answered correctly."""
    rng = np.random.default_rng(2)
    a, b = _occ(rng, 2, (4, 4, 4)), _occ(rng, 1, (8, 8, 8))
    broker = QueryBroker("numpy")
    results = {}

    def worker(key, occ, boxes):
        results[key] = broker.multibox(occ, boxes)

    broker.register()
    broker.register()
    ts = [threading.Thread(target=worker, args=("a", a, ((2, 2, 2),))),
          threading.Thread(target=worker, args=("b", b, ((3, 3, 3),)))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    np.testing.assert_array_equal(
        results["a"],
        np.asarray(ops.get_engine("numpy").multibox(a, ((2, 2, 2),))))
    np.testing.assert_array_equal(
        results["b"],
        np.asarray(ops.get_engine("numpy").multibox(b, ((3, 3, 3),))))
    assert broker.stats.engine_calls == 2
    assert broker.stats.batched_calls == 0


def test_deactivate_triggers_pending_flush():
    """A simulator finishing while its peer waits must flush the
    peer's round — nobody else will (no quorum possible, no deadline
    set)."""
    broker = QueryBroker("numpy")
    broker.register()
    broker.register()
    occ = np.zeros((1, 4, 4, 4), dtype=bool)
    out = {}

    def waiter():
        out["res"] = broker.multibox(occ, ((2, 2, 2),))

    t = threading.Thread(target=waiter)
    t.start()
    while not broker.stats.requests:   # parked, waiting for peer
        pass
    broker.deactivate()                # peer finishes without querying
    t.join(timeout=5)
    assert not t.is_alive()
    assert int(np.count_nonzero(out["res"])) == 27   # 3^3 origins fit
    assert broker.stats.flush_all_parked == 1


def test_host_free_counts_answered_inline():
    """On the host engine a free-count query never parks: it is a
    cheap reduction, answered on the calling thread even while peers
    are live."""
    broker = QueryBroker("numpy")
    broker.register()
    broker.register()      # a peer that never queries
    occ = np.zeros((2, 4, 4, 4), dtype=bool)
    assert broker.free_counts(occ).tolist() == [64, 64]
    assert broker.stats.fc_inline == 1
    assert broker.stats.flushes == 0
    broker.deactivate()
    broker.deactivate()


def test_quorum_flush_does_not_wait_for_stragglers():
    """With a half-fleet quorum, two parked steppers out of four are
    answered without the other two ever querying."""
    broker = QueryBroker("numpy", quorum=0.5)
    for _ in range(4):
        broker.register()
    occ = np.zeros((1, 4, 4, 4), dtype=bool)
    outs = [None, None]

    def worker(i):
        outs[i] = broker.multibox(occ, ((1, 1, 1),))

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=5)
    assert not any(t.is_alive() for t in ts)
    for out in outs:
        assert int(np.count_nonzero(out)) == 64
    assert broker.stats.flush_quorum >= 1
    for _ in range(4):
        broker.deactivate()


def test_timeout_flush_bounds_the_wait():
    """A lone parked query in a live fleet is answered once the
    deadline elapses, not when the fleet drains."""
    broker = QueryBroker("numpy", timeout=0.005)
    broker.register()
    broker.register()      # peer that never queries
    occ = np.zeros((1, 4, 4, 4), dtype=bool)
    t0 = time.monotonic()
    out = broker.multibox(occ, ((4, 4, 4),))
    assert time.monotonic() - t0 < 2.0
    assert int(np.count_nonzero(out)) == 1
    assert broker.stats.flush_timeout == 1
    broker.deactivate()
    broker.deactivate()


def test_stale_pad_hint_recomputed_as_population_shrinks():
    """Satellite: the fleet-size pad hint is capped by the *live*
    population — a fleet of 8 down to 2 survivors pads flushes to 2,
    not 8."""
    broker = QueryBroker("jax", pad_b=True)
    broker.pad_hint = 8
    broker.register()
    broker.register()
    occ = np.zeros((1, 4, 4, 4), dtype=bool)
    outs = [None, None]

    def worker(i):
        outs[i] = broker.multibox(occ, ((2, 2, 2),))

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in ts)
    # 2 real grids padded to the effective hint min(8, live=2) == 2:
    # no pad rows at all, where the stale hint would have added 6.
    assert broker.stats.grids == 2
    assert broker.stats.padded_grids == 0
    broker.deactivate()
    broker.deactivate()


def test_fc_content_cache_serves_free_counts_after_multibox():
    """Compiled engines: a multibox flush's fused free counts are
    remembered, so free_counts on the same occupancy never parks."""
    broker = QueryBroker("jax")
    rng = np.random.default_rng(8)
    occ = rng.random((2, 5, 5, 5)) < 0.4
    broker.multibox(occ, ((2, 2, 2),))
    flushes = broker.stats.flushes
    fc = broker.free_counts(occ)
    np.testing.assert_array_equal(
        fc, np.asarray(ops.get_engine("numpy").free_counts(occ)))
    assert broker.stats.fc_cache_hits == 1
    assert broker.stats.flushes == flushes   # answered without a round


def test_bucketed_k_padding_serves_exact_answers():
    """Compiled engines run per-bucket box tables padded to pow2
    capacity; answers are sliced back to each request's own boxes, in
    its own order."""
    broker = QueryBroker("jax", pad_b=True)
    rng = np.random.default_rng(9)
    occ = rng.random((1, 5, 5, 5)) < 0.4
    eng = ops.get_engine("numpy")
    b1 = ((3, 1, 2), (1, 1, 1), (2, 2, 2))
    out1 = broker.multibox(occ, b1)
    np.testing.assert_array_equal(np.asarray(out1) != 0,
                                  eng.multibox(occ, b1) != 0)
    # Second query re-uses the bucket's table; one new box appended.
    b2 = ((2, 2, 2), (4, 4, 4))
    out2 = broker.multibox(occ, b2)
    np.testing.assert_array_equal(np.asarray(out2) != 0,
                                  eng.multibox(occ, b2) != 0)
    assert broker.stats.k_slots >= broker.stats.k_needed > 0


def test_broker_propagates_engine_errors():
    class Boom:
        def multibox(self, occ, boxes):
            raise RuntimeError("engine down")

        def free_counts(self, occ):
            raise RuntimeError("engine down")

    broker = QueryBroker(Boom())
    with pytest.raises(RuntimeError, match="engine down"):
        broker.multibox(np.zeros((1, 4, 4, 4), dtype=bool), ((1, 1, 1),))


def test_broker_rejects_unbatched_grids():
    with pytest.raises(ValueError, match=r"\(B, X, Y, Z\)"):
        QueryBroker("numpy").free_counts(np.zeros((4, 4, 4), dtype=bool))


def test_fleet_surfaces_unit_exception():
    def bad(broker):
        raise ValueError("sim exploded")

    def good(broker):
        return int(broker.free_counts(
            np.zeros((1, 2, 2, 2), dtype=bool))[0])

    with pytest.raises(ValueError, match="sim exploded"):
        Fleet("numpy").run([bad, good])


# ---------------------------------------------------- fleet-of-sims
def test_fleet_records_byte_identical_to_sequential():
    """The tentpole parity contract: fleets produce the same records
    (minus timing) as the per-task oracle path, for both cluster
    models, while genuinely batching engine calls."""
    tasks = _tasks()
    seq = EvalRunner(workers=0, fleet_size=0).run(tasks)
    runner = EvalRunner(workers=0, fleet_size=4)
    fl = runner.run(tasks)
    assert _strip(seq) == _strip(fl)
    broker = runner.last_stats["fleet"]["broker"]
    assert broker["batched_calls"] > 0
    assert broker["mean_grids_per_call"] > 1
    # the new scheduling/padding telemetry is aggregated too
    for key in ("flush_all_parked", "flush_quorum", "flush_timeout",
                "requeued", "b_pad_waste", "k_pad_waste", "fc_inline"):
        assert key in broker


def test_fleet_pool_records_identical(tmp_path):
    """Two-level pool (processes x fleets) returns the same records
    and writes every checkpoint worker-side."""
    tasks = _tasks(runs=2)
    seq = EvalRunner(workers=0, fleet_size=0).run(tasks)
    ckpt = str(tmp_path / "ckpt")
    runner = EvalRunner(checkpoint_dir=ckpt, workers=2, fleet_size=2)
    fl = runner.run(tasks)
    assert _strip(seq) == _strip(fl)
    assert len(list(iter_checkpoints(ckpt))) == len(tasks)
    # resume reuses everything the fleet workers checkpointed
    resumed = EvalRunner(checkpoint_dir=ckpt, workers=2, fleet_size=2)
    resumed.run(tasks)
    assert resumed.last_stats["reused_from_checkpoint"] == len(tasks)


def test_run_fleet_tasks_engine_override_is_bit_exact():
    """The broker's engine choice cannot change records (engines are
    parity-tested); only where masks get computed differs."""
    tasks = _tasks(runs=1, num_jobs=15)
    base, _ = run_fleet_tasks(tasks)
    ref, stats = run_fleet_tasks(tasks, engine="ref")
    assert _strip(base) == _strip(ref)
    assert stats["engine_calls"] > 0


# ------------------------------------- continuous-scheduling parity
def _random_query_plan(rng, cell, n_steppers):
    """Per-stepper deterministic query sequences over one cell shape:
    a mix of multibox (random B, random boxes) and free_counts."""
    plans = []
    for _ in range(n_steppers):
        steps = []
        for _s in range(int(rng.integers(1, 5))):
            occ = rng.random((int(rng.integers(1, 4)),) + cell) < 0.5
            if rng.random() < 0.75:
                boxes = tuple(
                    tuple(int(v) for v in rng.integers(1, 5, size=3))
                    for _ in range(int(rng.integers(1, 4))))
                steps.append(("multibox", occ, boxes))
            else:
                steps.append(("free_counts", occ, None))
        plans.append(steps)
    return plans


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 2**31 - 1),
       st.sampled_from([0.25, 0.5, 0.75, 1.0]),
       st.sampled_from([-1, 0, 1, 3]))   # -1: no deadline; ms otherwise
def test_schedules_byte_identical_under_random_interleaving(
        seed, quorum, timeout_ms):
    """The tentpole parity proof, extended to continuous scheduling:
    across randomized stepper interleavings, quorum fractions, and
    timeout firings (0 ms forces a deadline flush on every tick), every
    query's answer is byte-identical to the sequential per-task oracle
    (the inline engine call on the same inputs) — which round answered
    it cannot leak into the result."""
    timeout = None if timeout_ms < 0 else timeout_ms / 1000.0
    rng = np.random.default_rng(seed)
    cell = tuple(int(v) for v in rng.integers(3, 7, size=3))
    n = int(rng.integers(2, 5))
    plans = _random_query_plan(rng, cell, n)
    eng = ops.get_engine("numpy")
    broker = QueryBroker(eng, quorum=quorum, timeout=timeout)
    outs = [[] for _ in range(n)]
    errs = []

    def stepper(i):
        r = np.random.default_rng(seed ^ (i + 1))
        try:
            for kind, occ, boxes in plans[i]:
                time.sleep(float(r.random()) * 0.002)  # interleave
                if kind == "multibox":
                    outs[i].append(broker.multibox(occ, boxes))
                else:
                    outs[i].append(broker.free_counts(occ))
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errs.append(e)
        finally:
            broker.deactivate()

    for _ in range(n):
        broker.register()
    threads = [threading.Thread(target=stepper, args=(i,), daemon=True)
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errs and not any(t.is_alive() for t in threads)
    for i, steps in enumerate(plans):
        for (kind, occ, boxes), got in zip(steps, outs[i]):
            if kind == "multibox":
                ref = np.asarray(eng.multibox(occ, boxes))
                np.testing.assert_array_equal(np.asarray(got) != 0,
                                              ref != 0)
            else:
                np.testing.assert_array_equal(
                    got, np.asarray(eng.free_counts(occ)))
    assert broker.stats.requests == sum(len(p) for p in plans)


def test_interleaving_parity_on_compiled_engine_with_padding():
    """Same contract through the jax path: bucketed box tables, padded
    B, fused free counts and the content cache all stay invisible in
    the answers."""
    seed = 1234
    rng = np.random.default_rng(seed)
    cell = (5, 5, 5)
    n = 3
    plans = _random_query_plan(rng, cell, n)
    oracle = ops.get_engine("numpy")
    broker = QueryBroker("jax", quorum=0.5, timeout=0.003)
    outs = [[] for _ in range(n)]

    def stepper(i):
        r = np.random.default_rng(seed ^ (i + 1))
        try:
            for kind, occ, boxes in plans[i]:
                time.sleep(float(r.random()) * 0.002)
                if kind == "multibox":
                    outs[i].append(broker.multibox(occ, boxes))
                else:
                    outs[i].append(broker.free_counts(occ))
        finally:
            broker.deactivate()

    for _ in range(n):
        broker.register()
    threads = [threading.Thread(target=stepper, args=(i,), daemon=True)
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    for i, steps in enumerate(plans):
        for (kind, occ, boxes), got in zip(steps, outs[i]):
            if kind == "multibox":
                ref = oracle.multibox(occ, boxes)
                np.testing.assert_array_equal(np.asarray(got) != 0,
                                              ref != 0)
            else:
                np.testing.assert_array_equal(
                    np.asarray(got),
                    np.asarray(oracle.free_counts(occ)))


# ------------------------------------------------ the K rules
class _PaddedEngine(ops.NumpyEngine):
    """The numpy engine, declared compiled (padded shapes, no host free
    counts), reading its boxes as data or compiling them in; records
    each call's K."""

    name = "padded"
    pads_shapes = True
    host_free = False

    def __init__(self, compiles_boxes):
        super().__init__()
        self.compiles_boxes = compiles_boxes
        self.ks = []

    def multibox_bucketed(self, occ, boxes):
        self.ks.append(len(boxes))
        return super().multibox_bucketed(occ, boxes)


def test_union_rule_for_an_engine_that_reads_boxes_as_data():
    """Each flush is served its own union padded to a power of two,
    whatever boxes earlier flushes asked for, and every answer is the
    numpy engine's."""
    eng = _PaddedEngine(compiles_boxes=False)
    broker = QueryBroker(eng)
    oracle = ops.get_engine("numpy")
    rng = np.random.default_rng(21)
    queries = [((1, 1, 1), (2, 1, 1), (1, 2, 1)),   # union of 3 -> K 4
               ((2, 1, 1),),                        # union of 1
               ((3, 1, 1), (1, 1, 3)),              # union of 2
               ((1, 1, 1), (2, 2, 2), (3, 3, 1),
                (1, 3, 1), (4, 1, 1)),              # union of 5 -> K 8
               ((1, 1, 1),)]                        # union of 1
    for boxes in queries:
        occ = _occ(rng, 1, (5, 5, 5))
        got = broker.multibox(occ, boxes)
        np.testing.assert_array_equal(np.asarray(got) != 0,
                                      oracle.multibox(occ, boxes) != 0)
    assert eng.ks == [4, 1, 2, 8, 1]
    assert broker.stats.k_slots == sum(eng.ks)
    assert broker.stats.k_needed == 3 + 1 + 2 + 5 + 1


def test_engine_that_compiles_boxes_keeps_the_table_rule():
    """An engine that compiles its boxes into the program keeps growing
    its bucket's table."""
    eng = _PaddedEngine(compiles_boxes=True)
    broker = QueryBroker(eng)
    occ = np.zeros((1, 5, 5, 5), bool)
    for box in [(1, 1, 1), (2, 1, 1), (3, 1, 1), (4, 1, 1), (5, 1, 1)]:
        broker.multibox(occ, (box,))
    assert eng.ks == [1, 2, 4, 4, 8]


def test_engine_k_rules():
    """``pallas`` reads its boxes as data; ``jax`` compiles them in."""
    assert not ops.PallasEngine.compiles_boxes
    assert ops.JaxEngine.compiles_boxes


@pytest.mark.parametrize("compiles_boxes,want", [
    (False, [("union", 2, 2), ("union", 4, 3)]),
    (True, [("table", 2, 2), ("table", 4, 3)]),
])
def test_flush_span_tags_the_k_rule(tmp_path, compiles_boxes, want):
    """While recording, ``broker.flush`` says which rule served it and
    how many box slots it served and needed."""
    import jax
    from repro import obs
    eng = _PaddedEngine(compiles_boxes=compiles_boxes)
    broker = QueryBroker(eng)
    occ = np.zeros((1, 4, 4, 4), bool)
    t0 = time.perf_counter()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path / "trace"), profiler_options=opts):
        broker.multibox(occ, ((1, 1, 1), (2, 1, 1)))
        broker.multibox(occ, ((1, 1, 1), (3, 1, 1), (1, 1, 2)))
    flushes = [r.tags for r in obs.records()
               if r.t0 >= t0 and r.name == "broker.flush"]
    assert [(f["rule"], f["k_served"], f["k_needed"])
            for f in flushes] == want


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 2**31 - 1),
       st.booleans(),
       st.sampled_from([0.5, 1.0]),
       st.sampled_from([-1, 0, 1]))   # -1: no deadline; ms otherwise
def test_union_rule_byte_identical_under_random_interleaving(
        seed, compiles_boxes, quorum, timeout_ms):
    """Under either K rule, every answer across randomized stepper
    interleavings is the numpy engine's on the same inputs, and every
    K the union rule sends is a power of two."""
    timeout = None if timeout_ms < 0 else timeout_ms / 1000.0
    rng = np.random.default_rng(seed)
    cell = tuple(int(v) for v in rng.integers(3, 7, size=3))
    n = int(rng.integers(2, 5))
    plans = _random_query_plan(rng, cell, n)
    eng = _PaddedEngine(compiles_boxes=compiles_boxes)
    oracle = ops.get_engine("numpy")
    broker = QueryBroker(eng, quorum=quorum, timeout=timeout)
    outs = [[] for _ in range(n)]
    errs = []

    def stepper(i):
        r = np.random.default_rng(seed ^ (i + 1))
        try:
            for kind, occ, boxes in plans[i]:
                time.sleep(float(r.random()) * 0.002)  # interleave
                if kind == "multibox":
                    outs[i].append(broker.multibox(occ, boxes))
                else:
                    outs[i].append(broker.free_counts(occ))
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errs.append(e)
        finally:
            broker.deactivate()

    for _ in range(n):
        broker.register()
    threads = [threading.Thread(target=stepper, args=(i,), daemon=True)
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errs and not any(t.is_alive() for t in threads)
    for i, steps in enumerate(plans):
        for (kind, occ, boxes), got in zip(steps, outs[i]):
            if kind == "multibox":
                np.testing.assert_array_equal(
                    np.asarray(got) != 0, oracle.multibox(occ, boxes) != 0)
            else:
                np.testing.assert_array_equal(
                    np.asarray(got), oracle.free_counts(occ))
    if not compiles_boxes:
        assert all(k & (k - 1) == 0 for k in eng.ks)


# ------------------------------------------------- chunking / sizing
def test_task_grid_bucket_defaults_mirror_make_policy():
    tasks = _tasks(runs=1)
    buckets = {t.label: task_grid_bucket(t) for t in tasks}
    assert buckets["RFold (4^3)"] == ("cube", 4)
    assert buckets["Folding (8^3)"] == ("static", (8, 8, 8))
    t = make_tasks([("x", "folding", {})], runs=1, num_jobs=5, load=1.0,
                   seed0=0)[0]
    assert task_grid_bucket(t) == ("static", (16, 16, 16))


def test_make_fleet_chunks_groups_buckets_and_caps_size():
    tasks = _tasks(runs=3)             # 6 cube tasks + 6 static tasks
    chunks = make_fleet_chunks(tasks, list(range(len(tasks))), 4)
    assert sorted(i for c in chunks for i in c) == list(range(len(tasks)))
    for chunk in chunks:
        assert len(chunk) <= 4
        assert len({task_grid_bucket(tasks[i]) for i in chunk}) == 1


def test_auto_fleet_size_scales_with_pending_and_workers():
    r = EvalRunner(workers=2, fleet_size="auto", fleet_engine="jax")
    assert r._resolve_fleet_size(24) == 3     # ceil(24 / (4*2))
    assert r._resolve_fleet_size(800) == 8    # capped
    assert r._resolve_fleet_size(2) == 2      # floor
    assert EvalRunner(workers=2,
                      fleet_size=6)._resolve_fleet_size(24) == 6
    assert EvalRunner(workers=2,
                      fleet_size=0)._resolve_fleet_size(24) is None


def test_fleet_mode_is_the_unconditional_default():
    """Fleet batching is the default on every engine — the host numpy
    path included (its multibox is genuinely (B, K) vectorized; the
    parity section of BENCH_fleet.json tracks the margin). The
    per-task oracle path is an explicit opt-out (fleet_size=0/None)."""
    assert EvalRunner(workers=2)._resolve_fleet_size(24) == 3
    assert EvalRunner(workers=2, fleet_size="auto",
                      fleet_engine="numpy")._resolve_fleet_size(24) == 3
    assert EvalRunner(workers=2, fleet_size="auto",
                      fleet_engine="pallas")._resolve_fleet_size(24) == 3
    assert EvalRunner(workers=2,
                      fleet_size=None)._resolve_fleet_size(24) is None


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
