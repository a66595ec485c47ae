"""The served cells' comparison, at a small size on the CPU: a sound run
is correct, and the control and every fault a served cell can have make
``correct`` false (the chip runs of the same are in PERF.md)."""
import pytest

import smallcell
from benchlib import reference


def test_sound_run_is_correct():
    run = smallcell.run(smallcell.served_cell(seed=2 ** 31 + 11))
    assert run.correct, run.checks
    assert run.attempted > 0 and run.failed == 0
    names = {n for n, _, _ in run.checks}
    assert names == {"acked_ops_lost", "reply_mismatches",
                     "digest_mismatch", "error_replies"}


@pytest.mark.parametrize("plant", ["control", "half_batch", "flip",
                                   "unchanged", "lost_wal", "plan_skip"])
def test_planted_fault_is_caught(plant):
    if plant == "control":
        hooks = {"engine_hook": reference.use_control}
    elif plant in reference.FAULTS:
        hooks = {"engine_hook": reference.FAULTS[plant]}
    else:
        hooks = {"core_hook": reference.CORE_FAULTS[plant]}
    run = smallcell.run(smallcell.served_cell(seed=5, **hooks))
    assert not run.correct, (plant, run.checks)


def test_traced_run_reads_layer_spans():
    run = smallcell.run(smallcell.served_cell(seed=7, trace=True))
    from benchlib import readers
    assert run.correct
    assert readers.plan_ms_per_op(run) > 0
    assert readers.per_op_ms(run, ("bench.wal",)) > 0
    assert readers.per_op_ms(run, ("bench.engine",)) > 0
    assert readers.wire_queue_ms_per_op(run) > 0
    assert readers.gen_lag_p95_ms(run) >= 0
