"""Allocator / simulator performance benchmark (placement hot path).

Tracks the perf trajectory of the incremental placement engine: per
policy, the end-to-end simulation wall-clock and the placement rate
(scheduled jobs per second of allocator time) at 80- and 200-job scale
on the paper's 4096-XPU cluster, plus the retained naive RFold path as
the speedup baseline.

  PYTHONPATH=src python -m benchmarks.allocator_bench
  PYTHONPATH=src python -m benchmarks.allocator_bench --out BENCH_allocator.json

Engine results are parity-checked against the naive oracle in
``tests/test_placement_engine.py``; this file only measures.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict

from repro.core.allocator import make_policy
from repro.sim.simulator import Simulator
from repro.traces.generator import TraceConfig, generate_trace
from repro.compile_cache import enable_compile_cache

POLICIES = [
    ("firstfit_16c", "firstfit", dict(dims=(16, 16, 16))),
    ("folding_16c", "folding", dict(dims=(16, 16, 16))),
    ("reconfig_4c", "reconfig", dict(num_xpus=4096, cube_n=4)),
    ("rfold_4c", "rfold", dict(num_xpus=4096, cube_n=4)),
    ("rfold_be_4c", "rfold_be", dict(num_xpus=4096, cube_n=4)),
]


def _run_once(name: str, kw: dict, num_jobs: int, seed: int,
              naive: bool = False, gated: bool = True) -> Dict:
    pol = make_policy(name, **kw)
    if naive:
        pol.use_naive = True
    jobs = generate_trace(TraceConfig(num_jobs=num_jobs, seed=seed,
                                      target_load=1.5))
    t0 = time.perf_counter()
    res = Simulator(pol, jobs, gated=gated).run()
    wall = time.perf_counter() - t0
    placed = sum(1 for j in res.jobs if j.scheduled)
    return {
        "sim_seconds": round(wall, 4),
        "placements": placed,
        "placements_per_sec": round(placed / wall, 1) if wall else None,
        "jcr": round(res.jcr, 4),
    }


def main(argv=None) -> Dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=str, default="BENCH_allocator.json")
    ap.add_argument("--job-scales", type=int, nargs="+", default=[80, 200])
    ap.add_argument("--seed", type=int, default=100)
    ap.add_argument("--skip-naive", action="store_true",
                    help="skip the slow naive-RFold baseline run")
    args = ap.parse_args(argv)
    enable_compile_cache()

    results: Dict = {"policies": {}, "baseline": {}}
    for scale in args.job_scales:
        print(f"# allocator bench @ {scale} jobs "
              f"(policy,sim_seconds,placements_per_sec,jcr)")
        for label, name, kw in POLICIES:
            r = _run_once(name, kw, scale, args.seed)
            results["policies"].setdefault(label, {})[str(scale)] = r
            print("%s,%.3f,%.0f,%.3f" % (label, r["sim_seconds"],
                                         r["placements_per_sec"], r["jcr"]))

    if not args.skip_naive:
        # Speedup anchor: the retained naive engine + ungated drain on the
        # acceptance workload (RFold 4^3, 80 jobs).
        naive = _run_once("rfold", dict(num_xpus=4096, cube_n=4), 80,
                          args.seed, naive=True, gated=False)
        fast = results["policies"]["rfold_4c"]["80"]
        results["baseline"] = {
            "naive_rfold_80": naive,
            "speedup_vs_naive": round(
                naive["sim_seconds"] / fast["sim_seconds"], 1),
        }
        print("naive_rfold_80,%.3f  speedup %.1fx" %
              (naive["sim_seconds"], results["baseline"]["speedup_vs_naive"]))

    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"# wrote {args.out}")
    return results


if __name__ == "__main__":
    main()
