"""The what-if cell's comparison, at a small size on the CPU: a sound
sweep is correct, and the control and every fault a what-if cell can
have make ``correct`` false."""
import pytest

import smallcell
from benchlib import reference


def test_sound_sweep_is_correct():
    run = smallcell.run(smallcell.whatif_cell(seed=3 * 2 ** 31 + 1))
    assert run.correct, run.checks
    assert run.extra["sweeps"] >= 1 and run.extra["jobs"] == 80
    assert run.counters["broker"]["engine_calls"] > 0
    assert {n for n, _, _ in run.checks} == {
        "schedule_mismatches", "summary_mismatches", "broker_faults",
        "failed_sims"}


@pytest.mark.parametrize("plant", ["control", "half_batch", "flip",
                                   "unchanged"])
def test_planted_fault_is_caught(plant):
    hook = (reference.use_control if plant == "control"
            else reference.FAULTS[plant])
    run = smallcell.run(smallcell.whatif_cell(seed=9, engine_hook=hook))
    assert not run.correct, (plant, run.checks)


def test_sound_folding_sweep_is_correct():
    run = smallcell.run(smallcell.whatif_cell(seed=2 ** 31 + 7, folding=True))
    assert run.correct, run.checks
    assert run.extra["jobs"] == 40 * run.extra["sweeps"] > 0
    assert run.counters["broker"]["engine_calls"] > 0


@pytest.mark.parametrize("plant", ["control", "flip", "unchanged"])
def test_planted_fault_is_caught_in_a_folding_sweep(plant):
    hook = (reference.use_control if plant == "control"
            else reference.FAULTS[plant])
    run = smallcell.run(smallcell.whatif_cell(seed=9, folding=True,
                                              engine_hook=hook))
    assert not run.correct, (plant, run.checks)
