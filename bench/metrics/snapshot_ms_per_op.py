"""``snapshot_ms_per_op``: Mean time per op in the periodic journal
snapshot (program span ``wal.snapshot``), served cells."""
from benchlib.progspans import served_ms_per_op


def read(run):
    return served_ms_per_op(run, "wal.snapshot")
