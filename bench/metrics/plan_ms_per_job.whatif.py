"""``plan_ms_per_job.whatif``: Milliseconds per simulated job in placement
attempts, less their broker waits and engine calls (program span
``plan.search`` less ``broker.wait`` and ``engine.call``), summed over
simulator threads, what-if cells."""
from benchlib.progspans import self_ms_per_job


def read(run):
    return self_ms_per_job(run, "plan.search", ("broker.wait", "engine.call"))
