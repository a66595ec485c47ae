"""``sim_jobs_per_s``: Simulated jobs of every whole sweep over the span
from the first sweep's start to the last one's end (host clock)."""


def read(run):
    if "jobs" not in run.extra:
        return None
    return run.extra["jobs"] / run.extra["span_s"]
