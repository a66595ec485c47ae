"""The replicated and bursty served cells (``served_plus.py``) at a small
size on the CPU: a sound run is correct; a standby stopped through the
window, or one that acknowledges records it has not written, makes
``correct`` false; a standby whose pulls are refused stops the run in
set-up; and the burst mix keeps the steady mix's jobs and mean gap."""
import copy
import dataclasses
import statistics
import time

import pytest

import smallcell
from benchlib import harness, philly, registry

BENCH = registry.load_benchmark()
REPLICATED = ("acked_ops_lost", "reply_mismatches", "digest_mismatch",
              "error_replies", "degraded_acks", "acked_ops_lost_standby",
              "standby_digest_mismatch")
NEW = ("sync_wait_ms_per_op.repl", "standby_apply_ms_per_op.repl",
       "repl_serve_ms_per_op.repl", "repl_records_per_pull.repl")
# Send-to-reply less apply: in a sync-ack cell mostly the standby's wait,
# which the replication metrics already count, so the cell leaves it out.
NOT_REPLICATED = ("wire_queue_ms_per_op",)


def small_cell(mix: str, seed: int, ha: bool = True, ack_mode: str = "sync",
               trace: bool = False) -> harness.Cell:
    config = smallcell.small_config()
    if ha:
        served = registry.load_config(BENCH, "rfold4096_ha")["served"]
        config["served"] = dict(copy.deepcopy(served), ack_mode=ack_mode)
    return harness.Cell(name=f"small.{mix}", config=config,
                        mix=dict(registry.load_mix(mix), prefill_jobs=60,
                                 connections=4, grace_s=20.0),
                        seed=seed, seconds=1.5, trace=trace, chips=1,
                        t_start=time.perf_counter(), engine="jax", rate=25.0,
                        peaks=registry.peaks_for("TPU v5 lite"))


@pytest.fixture(scope="module")
def driver():
    return registry.load_driver("served_plus")


def checks(run):
    return {name: value for name, value, _ in run.checks}


def test_configuration_is_the_pod_with_a_sync_standby():
    ha = registry.load_config(BENCH, "rfold4096_ha")
    pod = registry.load_config(BENCH, "rfold4096")
    for key in ("policy", "policy_kw", "num_xpus", "engine", "reduced"):
        assert ha[key] == pod[key]
    assert ha["warm"]["served"] == pod["warm"]["served"]
    assert ha["served"]["backfill"] is pod["served"]["backfill"] is True
    assert ha["served"]["ack_mode"] == "sync" and ha["served"]["fsync"]
    assert ha["served"]["standby"] == {"count": 1, "engine": "numpy",
                                       "fsync": True, "process": "separate"}
    for name, config, mix in (("rfold4096_ha.replicated", "rfold4096_ha",
                               "replicated"),
                              ("rfold4096.burst", "rfold4096", "burst")):
        cell = registry.find_cell(BENCH, name)
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            config, mix, 1)
    steady = registry.load_mix("steady")
    for mix in ("replicated", "burst"):
        m = registry.load_mix(mix)
        assert m["driver"] == "served_plus"
        assert {k: v for k, v in m.items() if k in steady
                and k != "driver"} == {k: v for k, v in steady.items()
                                       if k != "driver"}


def test_sound_replicated_run_is_correct(driver):
    run = driver.run(small_cell("replicated", seed=2 ** 31 + 19))
    assert run.correct, run.checks
    assert tuple(checks(run)) == REPLICATED
    assert run.attempted > 0 and run.failed == 0
    assert 0.0 < run.extra["failover_s"] < 5.0


@pytest.mark.parametrize("plant,ack_mode,caught_by", [
    ("pause_standby", "async", "acked_ops_lost_standby"),
    ("pause_standby", "sync", "degraded_acks"),
    ("standby_lost_wal", "sync", "acked_ops_lost_standby")])
def test_planted_standby_fault_is_caught(driver, plant, ack_mode, caught_by):
    run = driver.run(small_cell("replicated", seed=13, ack_mode=ack_mode),
                     plant=plant)
    assert not run.correct, (plant, run.checks)
    assert checks(run)[caught_by] > 0, run.checks


def test_refused_standby_stops_the_run_in_set_up(driver, monkeypatch):
    """A primary whose placement rules differ from the standby's (here:
    no backfill) refuses its pulls; the run stops before the warm-up."""
    primary_config = driver.served._scheduler_config

    def no_backfill(cell, engine, ckpt):
        return dataclasses.replace(primary_config(cell, engine, ckpt),
                                   backfill=False)
    monkeypatch.setattr(driver.served, "_scheduler_config", no_backfill)
    cell = small_cell("replicated", seed=3)
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="stream mismatch"):
        driver.run(cell)
    assert time.perf_counter() - t0 < driver.FOLLOW_S
    assert "standby" not in cell.marks and "warm" not in cell.marks


def test_sound_burst_run_is_correct(driver):
    run = driver.run(small_cell("burst", seed=2 ** 32 + 7, ha=False))
    assert run.correct, run.checks
    assert tuple(checks(run)) == REPLICATED[:4]
    assert run.attempted > 0 and run.failed == 0


def test_burst_gaps_keep_the_steady_pool_and_mean_gap(driver):
    """At the cell's own size: the same jobs, durations and shapes in
    the same order, the same mean gap, and a CV^2 near 1 + 6 b^2."""
    cell = harness.make_cell(BENCH, "rfold4096.burst", 2 ** 31 + 5, 30.0,
                             False, 0.0)
    params = driver.loadgen_params(cell, ("127.0.0.1", 1))
    steady = harness.make_cell(BENCH, "rfold4096.steady", 2 ** 31 + 5,
                               30.0, False, 0.0)
    assert {k: v for k, v in params.items() if k != "arrival_burstiness"} \
        == registry.load_driver("served").loadgen_params(
            steady, ("127.0.0.1", 1))
    b = params["arrival_burstiness"]
    p = params["philly"]
    pool = philly.jobs(p, params["num_jobs"], params["seed"],
                       head=params["prefill_jobs"], order=params["order"])
    burst = registry.load_driver("loadgen_burst").bursty(pool, p["pool_seed"],
                                                         b)
    assert [(j.job_id, j.duration, j.shape) for j in burst] == \
        [(j.job_id, j.duration, j.shape) for j in pool]
    arrivals = [0.0] + [j.arrival for j in burst]
    gaps = [t1 - t0 for t0, t1 in zip(arrivals, arrivals[1:])]
    assert burst[-1].arrival == pytest.approx(pool[-1].arrival, rel=1e-9)
    cv2 = statistics.pvariance(gaps) / statistics.fmean(gaps) ** 2
    assert cv2 == pytest.approx(1 + 6 * b * b, rel=0.15)


@pytest.fixture(scope="module")
def traced(driver):
    run = driver.run(small_cell("replicated", seed=2 ** 33 + 1, trace=True))
    assert run.correct, run.checks
    return run


@pytest.mark.parametrize("name", NEW)
def test_new_metric_reads_a_number(traced, name):
    value = registry.load_reader(name)(traced)
    assert isinstance(value, float) and value > 0.0


def test_cells_report_every_served_metric(traced):
    listed = {m["name"] for m in registry.metrics_for(
        BENCH, "rfold4096_ha.replicated", "per_layer")}
    steady = {m["name"] for m in registry.metrics_for(
        BENCH, "rfold4096.steady", "per_layer")}
    assert listed == (steady - set(NOT_REPLICATED)) | set(NEW)
    burst = {m["name"] for m in registry.metrics_for(
        BENCH, "rfold4096.burst", "per_layer")}
    assert burst == steady
    for name in listed:
        value = registry.load_reader(name)(traced)
        assert value is None or value >= 0.0, name
