"""The replication stream's id: a standby follows any primary whose
placement rules equal its own, whatever fitmask engine either runs; a
standby with other rules is refused; the checkpoint names do not move;
and the replication spans record under a profiler session only."""
import time

import jax
import pytest

from repro import obs
from repro.api import Scheduler, SchedulerClient, SchedulerConfig

MEDIUM = dict(num_xpus=512, cube_n=4)    # 8 cubes of 4^3
SHAPES = [(4, 4, 4), (2, 2, 2), (8, 4, 4), (4, 2, 1), (2, 2, 1),
          (4, 4, 2), (8, 8, 4), (4, 4, 4)]
POD = {"policy": "rfold", "policy_kw": {"num_xpus": 4096, "cube_n": 4},
       "backfill": True}


def _pair(tmp_path, primary_kw=None, standby_kw=None):
    """A primary on the ``jax`` engine and a ``numpy`` standby, each on
    its own checkpoint store, with sync acks."""
    base = dict(policy="rfold", policy_kw=MEDIUM, backfill=True,
                checkpoint_every=3, repl_poll=0.1, ack_mode="sync",
                sync_timeout=5.0)
    pri = Scheduler(SchedulerConfig(**{
        **base, "engine": "jax", "checkpoint_dir": str(tmp_path / "pri"),
        **(primary_kw or {})})).start()
    sby = Scheduler(SchedulerConfig(**{
        **base, "engine": "numpy", "checkpoint_dir": str(tmp_path / "sby"),
        "role": "standby", "replicate_from": pri.address,
        **(standby_kw or {})})).start()
    return pri, sby


def _await(predicate, what, deadline=10.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline:
        if predicate():
            return
        time.sleep(0.02)
    raise AssertionError(what)


def _follow(pri, sby):
    _await(lambda: pri.status()["repl"]["follower_live"],
           "the standby never pulled from the primary")


def _drive(client):
    """Submit every shape and finish three jobs, each op with its own
    request id; the replies without their transport fields."""
    replies = {}
    for i, dims in enumerate(SHAPES):
        replies[f"s{i}"] = client._request("submit", request_id=f"s{i}",
                                           shape=list(dims))
    for i, job in enumerate((0, 2, 5)):
        replies[f"d{i}"] = client._request("done", request_id=f"d{i}",
                                           job_id=job)
    return replies


def _bare(reply):
    return {k: v for k, v in reply.items()
            if k not in ("seq", "epoch", "replicated")}


@pytest.mark.parametrize("a,b", [({}, {"engine": "numpy"}),
                                 ({"engine": "numpy"}, {"engine": "jax"}),
                                 ({"engine": "jax"}, {"engine": "pallas"}),
                                 (POD, {**POD, "engine": "pallas"})])
def test_engines_share_one_stream_id(a, b):
    assert SchedulerConfig(**a).stream_id() == SchedulerConfig(**b).stream_id()


@pytest.mark.parametrize("other", [{"policy_kw": {"num_xpus": 4096,
                                                  "cube_n": 8}},
                                   {"backfill": False},
                                   {"max_queue": 16},
                                   {"policy": "folding",
                                    "policy_kw": {"dims": [16, 16, 16]}}])
def test_other_placement_rules_are_another_stream(other):
    assert SchedulerConfig(**POD).stream_id() \
        != SchedulerConfig(**{**POD, **other}).stream_id()


@pytest.mark.parametrize("cfg,fingerprint", [
    ({}, "6a37962768e6e78d"),
    ({**POD, "engine": "pallas"}, "22598110423eebf3")])
def test_checkpoint_names_keep_their_fingerprint(cfg, fingerprint):
    """The stream id is not the checkpoint's name: that still hashes
    the engine, as the journals already on disk were named."""
    config = SchedulerConfig(**cfg)
    assert config.fingerprint() == fingerprint
    assert config.checkpoint_name().endswith(f"__{fingerprint}.json")
    assert config.stream_id() != fingerprint
    assert config.stream_id() == SchedulerConfig(
        **{**cfg, "engine": "numpy"}).stream_id()


def test_numpy_standby_follows_a_jax_primary(tmp_path):
    """Every sync ack is replicated; after the primary is killed the
    promoted standby holds the primary's state and answers every
    request id with the primary's reply, byte for byte."""
    pri, sby = _pair(tmp_path)
    c = SchedulerClient(pri.address, client_id="ha", backoff=0.01)
    try:
        _follow(pri, sby)
        replies = _drive(c)
        assert all(r["ok"] and r["replicated"] is True
                   for r in replies.values()), replies
        want = pri.status()
        assert want["repl"]["sync_timeouts"] == 0
        assert sby.status()["journal_ops"] == want["journal_ops"]
        pri.kill()
        assert sby.promote()["epoch"] == 2
        promoted = SchedulerClient(sby.address, client_id="ha",
                                   backoff=0.01)
        try:
            got = sby.status()
            assert got["state_digest"] == want["state_digest"]
            assert got["resilience"]["repl_applied"] == want["journal_ops"]
            for rid, reply in replies.items():
                op = "submit" if rid[0] == "s" else "done"
                fields = ({"shape": list(SHAPES[int(rid[1:])])}
                          if op == "submit" else {"job_id": reply["job_id"]})
                again = promoted._request(op, request_id=rid, **fields)
                assert _bare(again) == _bare(reply), rid
            assert sby.status()["resilience"]["dedup_hits"] == len(replies)
        finally:
            promoted.close()
    finally:
        c.close()
        sby.kill()
        pri.kill()


@pytest.mark.parametrize("other", [{"policy_kw": {"num_xpus": 512,
                                                  "cube_n": 2}},
                                   {"backfill": False}])
def test_standby_with_other_rules_is_refused(tmp_path, other):
    pri, sby = _pair(tmp_path, standby_kw=other)
    try:
        _await(lambda: sby.status()["repl"]["last_error"] is not None,
               "the standby's pulls were never refused")
        assert sby.status()["repl"]["last_error"] == "stream mismatch"
        assert not pri.status()["repl"]["follower_live"]
        r = pri.submit((4, 4, 4))
        assert r["ok"] and r["replicated"] is False   # degraded, at once
        assert sby.status()["journal_ops"] == 0
    finally:
        sby.kill()
        pri.kill()


def _profiled(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(str(tmp_path / "trace"),
                              profiler_options=opts)


def _repl_spans(t0):
    return [r for r in obs.records() if r.t0 >= t0
            and r.name in ("daemon.sync_wait", "repl.serve")]


def test_replication_spans_record_under_a_profiler_only(tmp_path):
    pri, sby = _pair(tmp_path)
    c = SchedulerClient(pri.address, client_id="tr", backoff=0.01)
    try:
        _follow(pri, sby)
        t0 = time.perf_counter()
        c.submit((4, 4, 4))
        assert not obs.recording()
        assert _repl_spans(t0) == []
        t0 = time.perf_counter()
        with _profiled(tmp_path):
            replies = [c.submit(dims) for dims in SHAPES[1:6]]
        recs = _repl_spans(t0)
    finally:
        c.close()
        sby.kill()
        pri.kill()
    assert all(r["replicated"] for r in replies)
    by_sid = {r.sid: r for r in obs.records() if r.t0 >= t0}
    waits = [r for r in recs if r.name == "daemon.sync_wait"]
    assert len(waits) == len(replies)
    for w in waits:
        assert w.tags["ok"] is True and w.tags["target"] >= 1
        assert by_sid[w.parent].name == "daemon.op"
        assert w.rid == by_sid[w.parent].rid is not None
    serves = [r for r in recs if r.name == "repl.serve"]
    assert sum(r.tags["records"] for r in serves) >= len(replies)
    assert sum(r.tags["acked_delta"] for r in serves) >= len(replies) - 1
    assert all(r.tags["bytes"] >= 0 for r in serves)
    applied = [r.tags["standby_apply_s"] for r in serves
               if "standby_apply_s" in r.tags]
    assert applied and all(s >= 0.0 for s in applied)
    assert sum(applied) > 0.0
