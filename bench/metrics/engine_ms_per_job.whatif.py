"""``engine_ms_per_job.whatif``: Milliseconds in fitmask engine calls per
simulated job, what-if cells."""
from benchlib.readers import engine_ms_per_job


def read(run):
    return engine_ms_per_job(run)
