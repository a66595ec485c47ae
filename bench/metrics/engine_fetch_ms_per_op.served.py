"""``engine_fetch_ms_per_op.served``: Mean time per op copying fitmask
answers from the device to the host (program span ``engine.fetch``),
served cells."""
from benchlib.progspans import served_ms_per_op


def read(run):
    return served_ms_per_op(run, "engine.fetch")
