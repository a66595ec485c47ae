"""``counts_reused_per_op.served``: Mean number of free-count queries per
op answered from the fitmask engine's last multibox call, with no device
call of their own (program span ``engine.reuse``), served cells."""
from benchlib.progspans import served_count_per_op


def read(run):
    return served_count_per_op(run, "engine.reuse")
