"""``engine_launch_ms_per_job.whatif``: Milliseconds per simulated job
launching fitmask device calls (program span ``engine.launch``),
what-if cells."""
from benchlib.progspans import whatif_ms_per_job


def read(run):
    return whatif_ms_per_job(run, "engine.launch")
