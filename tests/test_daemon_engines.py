"""The served path answers alike on every fitmask engine: a daemon
named with a device engine gives the numpy daemon's replies and state
digest for the same op stream (submits of mixed shapes that fill the
cluster, queue and backfill, and dones that start queued jobs)."""
import functools

import pytest

from repro.serve.scheduler import Scheduler, SchedulerConfig

# (policy, policy_kw, edge of the cluster's cube or torus)
CLUSTERS = {
    "rfold": ("rfold", {"num_xpus": 64, "cube_n": 4}, 4),
    "folding": ("folding", {"dims": (8, 8, 8)}, 8),
}


def _ops(n):
    """Three jobs fill the cluster, two queue behind them; the first
    done backfills the small one past the blocked head."""
    h = n // 2
    return [("submit", (n, n, h)), ("submit", (h, h, n)),
            ("submit", (h, n, h)), ("submit", (n, n, h)),
            ("submit", (1, 1, 2)), ("done", 1), ("submit", (2, 1, 1)),
            ("done", 0), ("done", 2), ("submit", (h, h, h))]


@functools.lru_cache(maxsize=None)
def _drive(cluster, engine):
    policy, kw, n = CLUSTERS[cluster]
    cfg = SchedulerConfig(policy=policy, policy_kw=kw, backfill=True,
                          engine=engine)
    replies, ids = [], []
    with Scheduler(cfg) as s:
        for op, arg in _ops(n):
            if op == "submit":
                r = s.submit(arg)
                ids.append(r["job_id"])
            else:
                r = s.done(ids[arg])
            replies.append(r)
        digest = s.status()["state_digest"]
    return replies, digest


@pytest.mark.parametrize("cluster", sorted(CLUSTERS))
@pytest.mark.parametrize("engine", ["jax", "pallas", "ref"])
def test_daemon_replies_and_digest_match_numpy(cluster, engine):
    ref_replies, ref_digest = _drive(cluster, "numpy")
    outcomes = [r.get("outcome") for r in ref_replies]
    assert "queued" in outcomes and any(r.get("started")
                                        for r in ref_replies)
    replies, digest = _drive(cluster, engine)
    assert replies == ref_replies
    assert digest == ref_digest
