"""``wal_fsync_ms_per_op``: Mean time per op in the journal's flush and
fsync of its appends (program span ``wal.fsync`` under ``wal.append``),
served cells."""
from benchlib.progspans import served_ms_per_op


def read(run):
    return served_ms_per_op(run, "wal.fsync", parent="wal.append")
