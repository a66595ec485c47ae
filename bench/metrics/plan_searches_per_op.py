"""``plan_searches_per_op``: Mean number of placement attempts per op,
backfill included (program span ``plan.search``), served cells."""
from benchlib.progspans import served_count_per_op


def read(run):
    return served_count_per_op(run, "plan.search")
