"""``engine_fetch_ms_per_job.whatif``: Milliseconds per simulated job
copying fitmask answers from the device to the host (program span
``engine.fetch``), what-if cells."""
from benchlib.progspans import whatif_ms_per_job


def read(run):
    return whatif_ms_per_job(run, "engine.fetch")
