"""``plan_ms_per_op``: Mean apply time per op less journal, snapshot and
engine: the allocator core and its plan search."""
from benchlib.readers import plan_ms_per_op


def read(run):
    return plan_ms_per_op(run)
