"""``engine_launch_ms_per_op.served``: Mean time per op launching fitmask
device calls: host-to-device copy and dispatch (program span
``engine.launch``), served cells."""
from benchlib.progspans import served_ms_per_op


def read(run):
    return served_ms_per_op(run, "engine.launch")
