"""The ``folding16`` configuration through the what-if driver at a
small size on the CPU: its own 16^3 torus, policy and ``pallas`` engine
(interpreted here), two simulators of a few jobs per sweep, nothing
warmed. A sound sweep is correct; the control and a planted fault make
``correct`` false."""
import copy
import dataclasses
import time

import pytest

import smallcell
from benchlib import harness, reference, registry


def folding16_cell(seed: int, **hooks) -> harness.Cell:
    bench = registry.load_benchmark()
    config = copy.deepcopy(registry.load_config(bench, "folding16"))
    config["warm"]["whatif"]["multibox"] = []
    mix = dict(registry.load_mix("whatif"), sims=2, num_jobs=10)
    return harness.Cell(name="folding16.whatif", config=config, mix=mix,
                        seed=seed, seconds=0.1, trace=False, chips=1,
                        t_start=time.perf_counter(),
                        peaks=registry.peaks_for("TPU v5 lite"), **hooks)


def test_config_is_the_static_16_cubed_torus_on_pallas():
    bench = registry.load_benchmark()
    config = registry.load_config(bench, "folding16")
    assert config["policy"] == "folding"
    assert config["policy_kw"] == {"dims": [16, 16, 16]}
    assert config["num_xpus"] == 4096 and config["engine"] == "pallas"
    cell = registry.find_cell(bench, "folding16.whatif")
    assert (cell["traffic"], cell["chips"]) == ("whatif", 1)
    warm = config["warm"]["whatif"]
    assert warm["grid"] == [16, 16, 16] and warm["free_counts"] == []
    pairs = {tuple(p) for p in warm["multibox"]}
    assert {(b, k) for b in (1, 2, 4, 8)
            for k in (1, 2, 4, 8, 16, 32, 64, 128)} <= pairs
    assert warm["multibox"][0] == max(warm["multibox"])   # largest first


def test_sound_folding16_sweep_is_correct():
    run = smallcell.run(folding16_cell(seed=3 * 2 ** 31 + 15))
    assert run.correct, run.checks
    assert run.extra["jobs"] == 20 * run.extra["sweeps"] > 0
    assert run.counters["broker"]["engine_calls"] > 0


@pytest.mark.parametrize("plant", ["control", "flip"])
def test_planted_fault_is_caught_in_folding16(plant):
    hook = (reference.use_control if plant == "control"
            else reference.FAULTS[plant])
    run = smallcell.run(folding16_cell(seed=9, engine_hook=hook))
    assert not run.correct, (plant, run.checks)


# The per-layer metrics of the cell: the what-if cells' readers, and
# two this cell brought, which read every what-if cell.
NEW = ("engine_fetch_mb_per_job.whatif", "k_served_per_needed.whatif")
CELL = ("broker_grids_per_call", "engine_ms_per_job.whatif",
        "window_compiles.whatif", "fitmask_roofline.whatif",
        "device_idle_share.whatif", "broker_wait_ms_per_job",
        "plan_ms_per_job.whatif", "engine_launch_ms_per_job.whatif",
        "engine_fetch_ms_per_job.whatif") + NEW
# Read from the program's counters and spans and the benchmark's own
# spans: a number on the CPU.
READS = ("broker_grids_per_call", "engine_ms_per_job.whatif",
         "broker_wait_ms_per_job", "plan_ms_per_job.whatif",
         "engine_launch_ms_per_job.whatif",
         "engine_fetch_ms_per_job.whatif") + NEW
# Read from the program's spans alone.
PROGRAM_SPANS = READS[2:]


@pytest.fixture(scope="module")
def traced():
    run = smallcell.run(dataclasses.replace(folding16_cell(seed=2 ** 32 + 3),
                                            trace=True))
    assert run.correct, run.checks
    return run


def test_new_metrics_are_the_cells_per_layer_metrics():
    bench = registry.load_benchmark()
    listed = {m["name"]: m for m in
              registry.metrics_for(bench, "folding16.whatif", "per_layer")}
    assert set(listed) == set(CELL)
    for name in CELL:
        assert listed[name]["moves"] == "sim_jobs_per_s"
        assert listed[name]["workloads"] == ["rfold4096.whatif",
                                             "folding16.whatif"]


@pytest.mark.parametrize("name", READS)
def test_traced_metric_reads_a_number(traced, name):
    value = registry.load_reader(name)(traced)
    assert isinstance(value, float) and value > 0.0


def test_served_slots_cover_the_needed_ones(traced):
    assert registry.load_reader("k_served_per_needed.whatif")(traced) >= 1


@pytest.mark.parametrize("name", CELL)
def test_every_new_metric_reads_without_raising(traced, name):
    value = registry.load_reader(name)(traced)
    assert value is None or value >= 0


@pytest.mark.parametrize("name", PROGRAM_SPANS)
def test_nothing_recorded_reads_nothing(traced, name):
    later = time.perf_counter() + 3600.0
    run = dataclasses.replace(traced, spans=None,
                              extra={**traced.extra, "trace_t0": later,
                                     "trace_t1": later + 1.0})
    assert registry.load_reader(name)(run) is None
