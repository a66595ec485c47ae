"""``sync_wait_ms_per_op.repl``: Mean time per op that the primary held
its reply for the standby's acknowledgement (program span
``daemon.sync_wait``), replicated served cells. None where the program
records no such span."""
from benchlib.progspans import served_count_per_op, served_ms_per_op


def read(run):
    if not served_count_per_op(run, "daemon.sync_wait"):
        return None
    return served_ms_per_op(run, "daemon.sync_wait")
