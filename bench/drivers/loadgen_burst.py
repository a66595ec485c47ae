"""Open-loop load generator with bursty arrivals: ``loadgen.py`` with the
gaps between the pool's arrivals drawn again.

    python loadgen_burst.py < params.json

It takes ``loadgen.py``'s parameters plus ``arrival_burstiness`` b, and
speaks on standard output as ``loadgen.py`` does. The jobs, their
durations, shapes and order are the pool's; only the gaps change, to
the program's two-phase hyperexponential (``traces/generator.py``):
75% of gaps have mean (1 - b) and 25% mean (1 + 3b) of the mean gap,
for a squared coefficient of variation of 1 + 6 b^2. The phases and
the draws come from a stream of their own seeded from ``pool_seed``,
and the gaps are scaled so that their sample mean is the pool's: the
mean rate, and with it the compression of simulated time, stay those
of the mix without bursts.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from typing import List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import loadgen  # noqa: E402  (puts bench/ and src/ on the path)
from benchlib import philly  # noqa: E402
from repro.serve.scheduler.client import SchedulerClient  # noqa: E402

FAST_SHARE = 0.75
BURST_STREAM = 1     # the phases' stream, apart from the pool's own draw


def bursty(jobs: List[philly.Job], pool_seed: int,
           b: float) -> List[philly.Job]:
    """``jobs`` (in arrival order, the first arriving one gap after
    zero) with hyperexponential gaps of the same sample mean."""
    n = len(jobs)
    rng = np.random.default_rng([int(pool_seed), BURST_STREAM])
    fast = rng.uniform(size=n) < FAST_SHARE
    gaps = rng.exponential(1.0, size=n) * np.where(fast, 1.0 - b,
                                                   1.0 + 3.0 * b)
    gaps *= (jobs[-1].arrival / n) / gaps.mean()
    return [dataclasses.replace(j, arrival=float(a))
            for j, a in zip(jobs, np.cumsum(gaps))]


def main() -> int:
    params = json.loads(sys.stdin.readline())
    assert "jax" not in sys.modules, "the load generator must not import JAX"
    p = params["philly"]
    pool = bursty(philly.jobs(p, params["num_jobs"], params["seed"],
                              head=params["prefill_jobs"],
                              order=params["order"]),
                  p["pool_seed"], float(params["arrival_burstiness"]))
    by_id = {j.job_id: j for j in pool}
    clients = [SchedulerClient(tuple(params["address"]),
                               op_timeout=params["op_timeout_s"])
               for _ in range(params["connections"])]
    try:
        n_pre = params["prefill_jobs"]
        t0 = time.perf_counter()
        pre = loadgen.prefill(clients[0], pool[:n_pre], by_id)
        sim0 = pre["sim_now"]
        print(json.dumps({"event": "ready", "prefill_s":
                          time.perf_counter() - t0,
                          "prefill_ops": len(pre["ops"]),
                          "running": len(pre["finishing"])}), flush=True)
        if not sys.stdin.readline():
            return 1
        window = loadgen.Window(clients, by_id, params)
        start = time.perf_counter() + params["lead_s"]
        window.run(start,
                   [(j.arrival - sim0, j.job_id) for j in pool[n_pre:]],
                   [(f - sim0, jid) for f, jid in pre["finishing"]])
        print(json.dumps({"event": "done", "start": start,
                          "close": start + params["window_s"],
                          "ops": window.records,
                          "prefill": pre["ops"]}), flush=True)
    finally:
        for c in clients:
            c.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
