"""``engine_fetch_mb_per_job.whatif``: Megabytes (10^6 bytes) of fitmask
answers copied from the device to the host per simulated job (the
``bytes`` tag of program span ``engine.fetch``), what-if cells."""
from benchlib.progtags import tag_per_job


def read(run):
    per_job = tag_per_job(run, "engine.fetch", "bytes")
    return None if per_job is None else per_job / 1e6
