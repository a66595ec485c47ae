"""``broker_wait_ms_per_job``: Milliseconds per simulated job that
simulators sit parked in the fleet broker, less the flushes they lead
(program span ``broker.wait`` less ``broker.flush``), summed over
simulator threads, what-if cells."""
from benchlib.progspans import self_ms_per_job


def read(run):
    return self_ms_per_job(run, "broker.wait", ("broker.flush",))
