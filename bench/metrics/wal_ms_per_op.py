"""``wal_ms_per_op``: Mean time per op in the journal writer's append
(frame, write, fsync) and the periodic snapshot."""
from benchlib.readers import per_op_ms


def read(run):
    return per_op_ms(run, ("bench.wal", "bench.snapshot"))
