"""Reconfiguration plan-search benchmark: batched engine vs naive oracle.

The claim under test is PR 4's batched plan search itself: pre-scored
per-fold offset tables + the vectorized single-cube search + fresh-bound
pruning + dirty-cube cache refresh must beat the retained pure-python
offset scan at every cube granularity the paper evaluates (2^3 / 4^3 /
8^3 on the 4096-XPU cluster). Both engines run under the same gated
drain so the delta is the plan search, not the simulator; JCR equality
doubles as an in-bench parity check (the real parity suite is
``tests/test_reconfig_plan_search.py``).

  PYTHONPATH=src python -m benchmarks.reconfig_bench [--out BENCH_reconfig.json]
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

CUBE_SIZES = (8, 4, 2)


def _run(cube_n: int, num_jobs: int, seed: int, naive: bool) -> Dict:
    pol = make_policy("rfold", num_xpus=4096, cube_n=cube_n)
    pol.use_naive = naive
    jobs = generate_trace(TraceConfig(num_jobs=num_jobs, seed=seed,
                                      target_load=1.5))
    t0 = time.perf_counter()
    res = Simulator(pol, jobs, gated=True).run()
    wall = time.perf_counter() - t0
    placed = sum(1 for j in res.jobs if j.scheduled)
    return {"sim_seconds": round(wall, 4), "placements": placed,
            "placements_per_sec": round(placed / wall, 1) if wall else None,
            "jcr": round(res.jcr, 4)}


def main(argv=None) -> Dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=str, default="BENCH_reconfig.json")
    ap.add_argument("--num-jobs", type=int, default=120)
    ap.add_argument("--seed", type=int, default=100)
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized run (60 jobs)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    num_jobs = 60 if args.quick else args.num_jobs

    results: Dict = {"config": {"num_jobs": num_jobs, "seed": args.seed,
                                "num_xpus": 4096, "policy": "rfold"},
                     "cube_sizes": {}}
    print(f"# reconfig plan-search bench, rfold @ {num_jobs} jobs "
          "(cube,batched_s,naive_s,speedup,jcr)")
    for cube_n in CUBE_SIZES:
        fast = _run(cube_n, num_jobs, args.seed, naive=False)
        naive = _run(cube_n, num_jobs, args.seed, naive=True)
        assert fast["jcr"] == naive["jcr"], (cube_n, fast, naive)
        speedup = round(naive["sim_seconds"] / fast["sim_seconds"], 2) \
            if fast["sim_seconds"] else None
        results["cube_sizes"][f"{cube_n}^3"] = {
            "batched": fast, "naive": naive, "speedup": speedup}
        print("%d^3,%.3f,%.3f,%.1fx,%.3f" % (
            cube_n, fast["sim_seconds"], naive["sim_seconds"], speedup,
            fast["jcr"]))

    speedups = {k: v["speedup"] for k, v in results["cube_sizes"].items()}
    results["headline"] = {
        "criterion": "batched plan search beats the naive oracle at "
                     "every cube size (>= 2x at 8^3)",
        "speedups": speedups,
        "pass": all(s and s > 1.0 for s in speedups.values())
                and speedups["8^3"] >= 2.0,
    }
    print(f"# headline: {speedups} pass={results['headline']['pass']}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"# wrote {args.out}")
    return results


if __name__ == "__main__":
    main()
