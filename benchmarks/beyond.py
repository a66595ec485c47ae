"""Beyond-paper scheduler extensions (paper §5 future directions):
aggressive backfill and best-effort scatter placement, compared against
the faithful RFold baseline on identical traces.

  PYTHONPATH=src python -m benchmarks.beyond --runs 5 --num-jobs 300
"""
from __future__ import annotations

import argparse
import json

from repro.core.allocator import make_policy
from repro.sim.metrics import aggregate, summarize
from repro.sim.simulator import Simulator
from repro.traces.generator import TraceConfig, generate_trace
from repro.compile_cache import enable_compile_cache

VARIANTS = [
    ("rfold (paper FIFO)", "rfold", {}, dict(backfill=False)),
    ("rfold + backfill", "rfold", {}, dict(backfill=True)),
    ("rfold + best-effort", "rfold_be", {}, dict(backfill=False)),
    ("rfold + both", "rfold_be", {}, dict(backfill=True)),
]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--num-jobs", type=int, default=200)
    ap.add_argument("--load", type=float, default=1.5)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    enable_compile_cache()
    print("variant,jcr,jct_p50,jct_p90,jct_p99,util_mean")
    results = {}
    for label, name, pkw, skw in VARIANTS:
        sums = []
        for r in range(args.runs):
            cfg = TraceConfig(num_jobs=args.num_jobs, seed=500 + r,
                              target_load=args.load)
            pol = make_policy(name, num_xpus=4096, cube_n=4, **pkw)
            res = Simulator(pol, generate_trace(cfg), **skw).run()
            sums.append(summarize(res))
        agg = aggregate(sums)
        results[label] = agg
        print("%s,%.3f,%.0f,%.0f,%.0f,%.3f" % (
            label, agg["jcr"], agg["jct_p50"], agg["jct_p90"],
            agg["jct_p99"], agg["util_mean"]))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
