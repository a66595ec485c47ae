"""``loop_wait_ms_per_op``: Mean time from the load generator sending an
op to the daemon's loop taking it up (program span ``daemon.op``): the
wire and the wait behind other ops on the loop, served cells."""
from benchlib.progspans import loop_wait_ms_per_op


def read(run):
    return loop_wait_ms_per_op(run)
