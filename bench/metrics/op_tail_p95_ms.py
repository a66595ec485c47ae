"""``op_tail_p95_ms``: 95th percentile of due-to-reply time over every
submit and done of the window (host clock), read in the traced run. It
is a per-layer number, not an end-to-end one: a stall of the host for a
second or more in a 30-s window moves it many times over (see PERF.md)."""
from benchlib.readers import op_percentile


def read(run):
    return op_percentile(run, 95)
