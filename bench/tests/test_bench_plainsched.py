"""The plain reference of the scheduler agrees with the program where
the program is sound, at sizes a test run holds, on the CPU with the
program's numpy engine: the daemon's replies and state digest, the
simulator's schedule and summary, and the journal read back from disk."""
import heapq
import os

import pytest

import smallcell  # noqa: F401 -- puts bench/ and src/ on the path
from benchlib import philly, plainsched
from benchlib.reference import canonical

POLICIES = {"rfold": {"num_xpus": 512, "cube_n": 4},
            "folding": {"dims": (8, 8, 8)}}


def _traffic(load: float, xpus: int = 512) -> dict:
    from benchlib import registry
    return {**registry.load_mix("steady")["philly"], "load": load,
            "cluster_xpus": xpus, "size_max": xpus}


def _replay(jobs, apply):
    """Submit each job at its arrival and finish each placed one when its
    duration has passed, in simulated time."""
    by_id = {j.job_id: j for j in jobs}
    running = []

    def placed(reply):
        if reply.get("outcome") == "placed":
            return [reply["job_id"]]
        return [s["job_id"] for s in reply.get("started", [])
                if s.get("outcome") == "placed"]

    for job in jobs:
        while running and running[0][0] <= job.arrival:
            _, jid = heapq.heappop(running)
            for sid in placed(apply({"op": "done", "job_id": jid})):
                heapq.heappush(running, (job.arrival + by_id[sid].duration,
                                         sid))
        reply = apply({"op": "submit", "job_id": job.job_id,
                       "shape": list(job.shape)})
        for sid in placed(reply):
            heapq.heappush(running, (job.arrival + job.duration, sid))


@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_plain_daemon_agrees_with_the_allocator_core(policy):
    from repro.serve.scheduler import AllocatorCore, SchedulerConfig
    core = AllocatorCore(SchedulerConfig(policy=policy,
                                         policy_kw=dict(POLICIES[policy]),
                                         backfill=True, engine="numpy"))
    ref = plainsched.PlainServed(policy, POLICIES[policy], True)
    mismatched, outcomes = [], set()

    def apply(op):
        got, _ = core.apply(dict(op))
        want = ref.apply(dict(op))
        outcomes.add(got.get("outcome"))
        if canonical(got) != canonical(want):
            mismatched.append((op, got, want))
        return got

    _replay(philly.jobs(_traffic(1.2), 250, seed=2 ** 31 + 5), apply)
    assert not mismatched, mismatched[:2]
    assert {"placed", "queued"} <= outcomes
    assert ref.digest() == core.state_digest()


@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_plain_simulator_agrees_with_the_program(policy):
    from repro.core.allocator import make_policy
    from repro.core.geometry import JobShape
    from repro.sim.job import Job
    from repro.sim.metrics import summarize, utilization_cdf
    from repro.sim.simulator import Simulator
    jobs = philly.jobs(_traffic(1.5), 120, seed=3)
    res = Simulator(make_policy(policy, engine="numpy", **POLICIES[policy]),
                    [Job(j.job_id, j.arrival, j.duration, JobShape(j.shape))
                     for j in jobs], backfill=True).run()
    levels, cdf = utilization_cdf(res)
    want = plainsched.simulate(policy, POLICIES[policy], jobs, True)
    assert canonical([[j.job_id, j.start, j.finish, j.dropped,
                       j.placement_meta] for j in res.jobs]) == \
        canonical(want["schedule"])
    assert canonical(summarize(res)) == canonical(want["summary"])
    assert canonical([float(x) for x in cdf]) == canonical(want["cdf"])
    assert canonical([float(x) for x in levels]) == \
        canonical(want["cdf_levels"])


def test_journal_is_read_back_from_snapshot_and_log(tmp_path):
    from repro.serve.scheduler import AllocatorCore, SchedulerConfig
    conf = SchedulerConfig(policy="rfold", policy_kw=POLICIES["rfold"],
                           backfill=True, engine="numpy",
                           checkpoint_dir=str(tmp_path), checkpoint_every=7,
                           fsync=False)
    core = AllocatorCore(conf)
    for i, job in enumerate(philly.jobs(_traffic(0.8), 30, seed=1)):
        core.apply({"op": "submit", "job_id": job.job_id,
                    "shape": list(job.shape), "request_id": f"c:{i}"})
    wal = [os.path.join(r, n) for r, _, ns in os.walk(tmp_path)
           for n in ns if n.endswith(".wal")]
    assert len(wal) == 1
    with open(wal[0], "ab") as f:
        f.write(b"\x10\x00\x00\x00torn")      # a frame cut short
    got = plainsched.read_journal(str(tmp_path))
    assert canonical(got) == canonical(core.journal)
    assert len(got) == 30


def test_fold_rules_on_known_shapes():
    folds = plainsched.folds_of((4, 8, 2), max_dim=None)
    halving = [f for f in folds if f.kind == "halving3d"]
    assert {f.box for f in halving} == {(4, 4, 4), (8, 2, 4)}
    # The paper's 4x8x2 -> 4x4x4: its ring of 8 (axis 0 of the sorted
    # shape 8x4x2) closes through the wrap of box axis 2.
    f = next(f for f in halving if f.box == (4, 4, 4))
    assert f.broken((True, True, True)) == ()
    assert f.broken((True, True, False)) == (0,)
    # 4x8x3 has no halving fold (the paper's impossibility example).
    assert not [f for f in plainsched.folds_of((4, 8, 3), None)
                if f.kind == "halving3d"]
    # A 1D ring of 18 folds onto every even box with at most one unit
    # extent, 2x9 among them, and never breaks.
    cyc = [f for f in plainsched.folds_of((18, 1, 1), None)
           if f.kind == "cycle1d"]
    assert (2, 9, 1) in {f.box for f in cyc}
    assert all(f.broken((False,) * 3) == () for f in cyc)
