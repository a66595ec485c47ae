"""The per-layer metrics read from the program's own spans
(``benchlib/progspans.py``), on traced small cells on the CPU: each
reads a number where its layer runs, each lies inside the benchmark's
own span of the same layer, and none reads anything where the program
recorded nothing."""
import dataclasses
import time

import pytest

import smallcell
from benchlib import readers, registry

SERVED = ("loop_wait_ms_per_op", "wal_fsync_ms_per_op", "snapshot_ms_per_op",
          "plan_searches_per_op", "engine_launch_ms_per_op.served",
          "engine_fetch_ms_per_op.served")
WHATIF = ("broker_wait_ms_per_job", "plan_ms_per_job.whatif",
          "engine_launch_ms_per_job.whatif", "engine_fetch_ms_per_job.whatif")


def _read(name, run):
    return registry.load_reader(name)(run)


@pytest.fixture(scope="module")
def served():
    run = smallcell.run(smallcell.served_cell(seed=2 ** 31 + 21, trace=True))
    assert run.correct, run.checks
    return run


@pytest.fixture(scope="module")
def whatif():
    # The jax engine, so that the broker's flushes make device calls.
    cell = dataclasses.replace(smallcell.whatif_cell(seed=2 ** 33 + 5),
                               engine="jax", trace=True)
    run = smallcell.run(cell)
    assert run.correct, run.checks
    return run


def test_every_new_metric_is_in_the_benchmark():
    bench = registry.load_benchmark()
    for names, cell in ((SERVED, "rfold4096.steady"),
                        (WHATIF, "rfold4096.whatif")):
        listed = {m["name"]: m for m in
                  registry.metrics_for(bench, cell, "per_layer")}
        for name in names:
            assert listed[name]["source"] == "program_span"


@pytest.mark.parametrize("name", SERVED)
def test_served_metric_reads_a_number(served, name):
    value = _read(name, served)
    assert isinstance(value, float) and value >= 0.0


@pytest.mark.parametrize("name", WHATIF)
def test_whatif_metric_reads_a_number(whatif, name):
    value = _read(name, whatif)
    assert isinstance(value, float) and value > 0.0


def test_served_ops_search_and_call_the_engine(served):
    assert _read("plan_searches_per_op", served) > 0
    assert _read("engine_launch_ms_per_op.served", served) > 0
    assert _read("engine_fetch_ms_per_op.served", served) > 0
    assert _read("wal_fsync_ms_per_op", served) > 0


def test_loop_wait_is_inside_wire_queue(served):
    assert 0 < _read("loop_wait_ms_per_op", served) \
        <= readers.wire_queue_ms_per_op(served)


def test_wal_split_is_inside_wal(served):
    split = _read("wal_fsync_ms_per_op", served) \
        + _read("snapshot_ms_per_op", served)
    assert split <= 1.05 * _read("wal_ms_per_op", served)


def test_engine_split_is_inside_engine(served):
    split = _read("engine_launch_ms_per_op.served", served) \
        + _read("engine_fetch_ms_per_op.served", served)
    assert split <= _read("engine_ms_per_op.served", served)


def test_plan_self_time_is_inside_plan_searches(whatif):
    from benchlib import progspans
    whole = progspans.whatif_ms_per_job(whatif, "plan.search")
    assert 0 < _read("plan_ms_per_job.whatif", whatif) <= whole


@pytest.mark.parametrize("name", SERVED + WHATIF)
def test_nothing_recorded_reads_nothing(served, name):
    later = time.perf_counter() + 3600.0
    run = dataclasses.replace(served, extra={**served.extra, "jobs": 10,
                                             "trace_t0": later,
                                             "trace_t1": later + 1.0})
    assert _read(name, run) is None
