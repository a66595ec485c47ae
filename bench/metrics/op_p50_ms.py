"""``op_p50_ms``: Median due-to-reply time over every submit and done of
the window (host clock)."""
from benchlib.readers import op_percentile


def read(run):
    return op_percentile(run, 50)
