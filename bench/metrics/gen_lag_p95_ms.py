"""``gen_lag_p95_ms``: 95th percentile of how late the load generator sent
an op after it was due (host clock, generator process)."""
from benchlib.readers import gen_lag_p95_ms


def read(run):
    return gen_lag_p95_ms(run)
