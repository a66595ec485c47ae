"""Spans inside the scheduler (``repro.obs``): nothing records without a
profiler session; under one, a served op records its spans from the
daemon's loop down to the fitmask engine's device call, under one
request id and nested in time; the state is the same either way; and
the fleet broker records its waits and the flushes it leads."""
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import jax
import numpy as np

from repro import obs
from repro.api import Scheduler, SchedulerConfig
from repro.core.allocator import make_policy
from repro.core.geometry import JobShape
from repro.kernels.fitmask import ops
from repro.sim.fleet import QueryBroker

MEDIUM = dict(num_xpus=512, cube_n=4)    # 8 cubes of 4^3
SHAPES = [(4, 4, 4), (2, 2, 2), (8, 4, 4), (4, 2, 1), (2, 2, 1),
          (4, 4, 2)]
ROOT = Path(__file__).resolve().parents[1]


def _config(tmp_path, name):
    return SchedulerConfig(policy="rfold", policy_kw=MEDIUM, engine="jax",
                           backfill=True, checkpoint_every=4,
                           checkpoint_dir=str(tmp_path / name))


def _serve(config):
    """Submit every shape, finish the first two jobs; the replies and
    the daemon's final state digest."""
    with Scheduler(config) as s:
        replies = [s.submit(shape) for shape in SHAPES]
        replies += [s.done(r["job_id"]) for r in replies[:2]]
        digest = s.status()["state_digest"]
    return replies, digest


def _profiled(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(str(tmp_path / "trace"),
                              profiler_options=opts)


def _since(t0):
    return [r for r in obs.records() if r.t0 >= t0]


def test_nothing_records_without_a_profiler(tmp_path):
    assert not obs.recording()
    assert obs.span("core.apply") is obs.span("plan.search")
    t0 = time.perf_counter()
    _serve(_config(tmp_path, "off"))
    assert _since(t0) == []


def test_served_op_records_its_spans_down_to_the_device(tmp_path):
    t0 = time.perf_counter()
    with _profiled(tmp_path):
        assert obs.recording()
        _serve(_config(tmp_path, "on"))
    recs = _since(t0)
    by_sid = {r.sid: r for r in recs}
    names = {r.name for r in recs}
    assert {"daemon.op", "core.apply", "plan.search", "engine.call",
            "engine.launch", "engine.wait", "engine.fetch", "wal.append",
            "wal.fsync", "wal.snapshot"} <= names

    def chain(rec):
        out = [rec.name]
        while rec.parent is not None:
            rec = by_sid[rec.parent]
            out.append(rec.name)
        return out[::-1]

    fetches = [r for r in recs if r.name == "engine.fetch"
               and r.rid is not None]
    assert fetches
    for fetch in fetches:
        assert chain(fetch) == ["daemon.op", "core.apply", "plan.search",
                                "engine.call", "engine.fetch"]
        call = by_sid[fetch.parent]
        kids = sorted((r for r in recs if r.parent == call.sid),
                      key=lambda r: r.t0)
        assert [r.name for r in kids] == ["engine.launch", "engine.wait",
                                          "engine.fetch"]
        assert fetch.tags["bytes"] > 0
        assert call.tags["kind"] in ("multibox", "free_counts")
        assert call.tags["b"] <= call.tags["b_pad"]
    # One request id from the daemon down; every span inside its parent.
    for rec in recs:
        if rec.parent is None:
            continue
        parent = by_sid[rec.parent]
        assert rec.rid == parent.rid
        assert parent.t0 <= rec.t0 <= rec.t1 <= parent.t1
    served = [r for r in recs if r.name == "daemon.op" and r.rid is not None]
    assert {r.tags["op"] for r in served} >= {"submit", "done"}
    searches = [r for r in recs if r.name == "plan.search"]
    for apply in (r for r in recs if r.name == "core.apply"):
        under = [r for r in searches if r.parent == apply.sid]
        assert apply.tags["searches"] == len(under)
    assert all({"folds", "pruned", "rows", "rows_cut", "placed"}
               <= set(r.tags) for r in searches)
    assert any(r.tags["placed"] for r in searches)
    snaps = [r for r in recs if r.name == "wal.snapshot"]
    assert all(r.tags["bytes"] > 0 and r.tags["records"] > 0 for r in snaps)


def test_plan_search_tags_rows_and_rows_cut(tmp_path):
    """A multi-cube search tags ``plan.search`` with the offset rows it
    handed to cube assignment and the rows the supply test ruled out. A
    failed corner cell leaves no cube whole: the first 8x4x4 search
    reads the whole-cube fit mask while assigning its one row, so the
    second, in the same occupancy epoch, rules that row out unassigned."""
    pol = make_policy("rfold", **MEDIUM)
    cl = pol.cluster
    cl.fail_cells([(c, 0, 0, 0) for c in range(cl.num_cubes)])
    assigned = []
    assign = cl._assign_plan
    cl._assign_plan = lambda *a: assigned.append(a) or assign(*a)
    t0 = time.perf_counter()
    with _profiled(tmp_path):
        for jid, dims in enumerate([(8, 4, 4), (8, 4, 4), (8, 8, 2)]):
            pol.try_place(jid, JobShape(dims))
    searches = [r.tags for r in _since(t0) if r.name == "plan.search"]
    assert [(t["rows"], t["rows_cut"], t["placed"]) for t in searches] == [
        (1, 0, False), (0, 1, False), (2, 1, True)]
    assert sum(t["rows"] for t in searches) == len(assigned)


def test_tracing_leaves_the_schedule_unchanged(tmp_path):
    plain = _serve(_config(tmp_path, "plain"))
    with _profiled(tmp_path):
        traced = _serve(_config(tmp_path, "traced"))
    strip = [{k: v for k, v in r.items() if k != "seq"} for r in plain[0]]
    assert strip == [{k: v for k, v in r.items() if k != "seq"}
                     for r in traced[0]]
    assert plain[1] == traced[1]


def test_broker_records_waits_and_flushes(tmp_path):
    broker = QueryBroker("jax", quorum=1.0)
    rng = np.random.default_rng(0)
    occs = [rng.uniform(size=(2, 4, 4, 4)) < 0.3 for _ in range(3)]
    answers = [None] * len(occs)

    def sim(i):
        answers[i] = broker.multibox(occs[i], [(1, 1, 1), (2, 2, 2)])
        broker.deactivate()

    t0 = time.perf_counter()
    with _profiled(tmp_path):
        threads = [threading.Thread(target=sim, args=(i,))
                   for i in range(len(occs))]
        for t in threads:
            broker.register(t)
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
    recs = _since(t0)
    waits = [r for r in recs if r.name == "broker.wait"]
    flushes = [r for r in recs if r.name == "broker.flush"]
    assert len(waits) == len(occs) and flushes
    assert {r.tags["trigger"] for r in waits} <= {"all_parked", "quorum",
                                                  "timeout"}
    assert all(r.tags["grids"] == 2 for r in waits)
    wait_ids = {r.sid for r in waits}
    assert any(f.parent in wait_ids for f in flushes)
    assert sum(f.tags["grids"] for f in flushes) == 2 * len(occs)
    flush_ids = {f.sid for f in flushes}
    assert any(r.name == "engine.call" and r.parent in flush_ids
               for r in recs)
    host = ops.get_engine("numpy")
    for got, occ in zip(answers, occs):
        want = host.multibox(occ, [(1, 1, 1), (2, 2, 2)])
        assert np.array_equal(got != 0, want != 0)


def test_spans_do_not_import_jax():
    code = ("import sys; from repro import obs\n"
            "with obs.span('core.apply', op='submit') as sp:\n"
            "    sp.tag(searches=0)\n"
            "assert not obs.recording() and obs.records() == []\n"
            "assert 'jax' not in sys.modules\n")
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src")}
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   env=env)
