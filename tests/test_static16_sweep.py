"""The static baseline's what-if sweep at the real torus size, on the
CPU: Folding simulators for one 16^3 torus over one fleet broker on the
``pallas`` engine (interpreted here), whose broker serves each flush
its own box union. The schedules must equal the benchmark's plain reference
(``bench/benchlib/plainsched.py``, which shares no code with the
program)."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

from benchlib import philly, plainsched  # noqa: E402

from repro import obs  # noqa: E402
from repro.core.allocator import make_policy  # noqa: E402
from repro.core.geometry import JobShape  # noqa: E402
from repro.kernels.fitmask import ops  # noqa: E402
from repro.sim.fleet import Fleet  # noqa: E402
from repro.sim.job import Job  # noqa: E402
from repro.sim.simulator import Simulator  # noqa: E402

DIMS = [16, 16, 16]
SIMS, JOBS = 2, 10


def _jobs():
    mix = json.loads((ROOT / "bench/traffic/whatif.json").read_text())
    p = {**mix["philly"], "cluster_xpus": 4096, "size_max": 4096}
    drawn = philly.pool(p, JOBS)
    return [philly.arrange(drawn, [2 ** 33 + 5, 0, k]) for k in range(SIMS)]


def _unit(jobs):
    def go(broker):
        policy = make_policy("folding", mask_client=broker, dims=DIMS)
        res = Simulator(policy, [Job(j.job_id, j.arrival, j.duration,
                                     JobShape(j.shape)) for j in jobs],
                        backfill=True).run()
        return [[j.job_id, j.start, j.finish, j.dropped, j.placement_meta]
                for j in res.jobs]
    return go


def _canonical(obj):
    return json.dumps(json.loads(json.dumps(obj)), sort_keys=True)


def test_folding16_pallas_sweep_equals_plain_reference():
    jobs = _jobs()
    fleet = Fleet("pallas")
    got = fleet.run([_unit(j) for j in jobs])
    stats = fleet.broker.stats
    assert stats.engine_calls > 0 and stats.engine_failovers == 0
    assert not fleet.broker._buckets[tuple(DIMS)].table   # union rule
    for sim_jobs, schedule in zip(jobs, got):
        want = plainsched.simulate("folding", {"dims": DIMS}, sim_jobs, True)
        assert _canonical(schedule) == _canonical(want["schedule"])
        assert sum(not row[3] for row in schedule) > 0   # jobs were placed


@pytest.mark.parametrize("k,tiles", [(8, 1), (32, 2)])
def test_pallas_engine_call_tags_k_tiles(tmp_path, k, tiles):
    """While recording, the ``pallas`` engine's ``engine.call`` says how
    many K tiles the kernel ran on a 16^3 grid."""
    import time

    import jax
    import numpy as np
    engine = ops.get_engine("pallas")
    occ = np.zeros((1, 16, 16, 16), bool)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path / "trace"), profiler_options=opts):
        t0 = time.perf_counter()
        engine.multibox(occ, [(1, 1, 1)] * k)
    calls = [r.tags for r in obs.records()
             if r.t0 >= t0 and r.name == "engine.call"]
    assert [(c["k_pad"], c["k_tiles"]) for c in calls] == [(k, tiles)]
