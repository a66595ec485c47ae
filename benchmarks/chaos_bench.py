"""Chaos benchmark: degradation and recovery across the scenario matrix.

Runs every (scenario x policy) cell of the chaos layer — the five named
scenarios from :mod:`repro.sim.scenarios` against the five paper
policies at 512 XPUs — and records each cell's degradation/recovery
block (:class:`~repro.sim.faults.ChaosObserver`).

Two asserts ride on top:

* **Determinism.** Every cell is run twice with the same seed; the two
  records must be byte-identical JSON. The whole chaos path — trace,
  fault timeline, eviction/replan order, observer metrics — is seeded
  and deterministic, and the scenario-matrix CI job gates on exactly
  this.

* **Headline.** Under ``node_churn``, RFold's recovered utilization
  (time-weighted tail after the last repair) must be at least the best
  static baseline's (FirstFit, Folding). Folding and reconfiguration
  are how the paper's allocator finds capacity on a degraded fabric;
  this is the recovery claim the chaos layer exists to measure.

  PYTHONPATH=src python -m benchmarks.chaos_bench [--quick] \
      [--scenario node_churn] [--out BENCH_chaos.json]
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Optional

from repro.api import SCENARIOS, run_scenario
from repro.compile_cache import enable_compile_cache

# The service-bench parity matrix, reused: 512 XPUs per policy.
POLICY_CONFIGS = [
    ("firstfit", "FirstFit (8^3)", "firstfit", dict(dims=(8, 8, 8))),
    ("folding", "Folding (8^3)", "folding", dict(dims=(8, 8, 8))),
    ("reconfig", "Reconfig (4^3)", "reconfig",
     dict(num_xpus=512, cube_n=4)),
    ("rfold", "RFold (4^3)", "rfold", dict(num_xpus=512, cube_n=4)),
    ("rfold_be", "RFold-BE (4^3)", "rfold_be",
     dict(num_xpus=512, cube_n=4)),
]

STATIC_BASELINES = ("firstfit", "folding")
TRACE_KW = dict(cluster_xpus=512, size_max=512)


def run_cell(scenario: str, policy: str, policy_kw: dict,
             num_jobs: int, seed: int) -> Dict:
    """One (scenario, policy) cell, run twice with the same seed; the
    returned record carries the determinism verdict."""
    t0 = time.perf_counter()
    first = run_scenario(scenario, policy=policy, policy_kw=policy_kw,
                         num_jobs=num_jobs, seed=seed,
                         trace_kw=dict(TRACE_KW))
    second = run_scenario(scenario, policy=policy, policy_kw=policy_kw,
                          num_jobs=num_jobs, seed=seed,
                          trace_kw=dict(TRACE_KW))
    identical = (json.dumps(first, sort_keys=True)
                 == json.dumps(second, sort_keys=True))
    first["deterministic"] = identical
    first["cell_s"] = round(time.perf_counter() - t0, 3)
    return first


def run_matrix(scenarios: List[str], num_jobs: int,
               seed: int) -> Dict[str, Dict[str, Dict]]:
    out: Dict[str, Dict[str, Dict]] = {}
    for scenario in scenarios:
        out[scenario] = {}
        for key, label, policy, kw in POLICY_CONFIGS:
            cell = run_cell(scenario, policy, kw, num_jobs, seed)
            cell["label"] = label
            out[scenario][key] = cell
            ch = cell["chaos"]
            print(f"  {scenario:13s} {label:16s} "
                  f"det={cell['deterministic']} "
                  f"jcr={cell['summary']['jcr']:.3f} "
                  f"dip={ch['dip_depth']:.3f} "
                  f"recovered_util={ch['recovered_util']:.3f} "
                  f"pre={ch['preempted']} mig={ch['migrated']} "
                  f"({cell['cell_s']}s)")
    return out


def headline_from(matrix: Dict[str, Dict[str, Dict]],
                  tolerance: float) -> Dict:
    """The recovery claim: under ``node_churn`` RFold (a) sustains at
    least the best static baseline's time-weighted utilization over
    the whole degraded run, and (b) recovers — tail utilization back
    within the observer's tolerance of its pre-fault level. The
    comparison deliberately uses ``util_overall`` rather than the
    post-repair tail: a policy that stalls during degradation piles up
    a backlog whose drain saturates its tail window, so tail
    utilization alone rewards exactly the wrong behaviour. Determinism
    is always asserted, on every cell that ran. ``tolerance`` absorbs
    sub-fault noise (one 8-node fault on 512 XPUs is 1.6 % of
    capacity)."""
    det = all(cell["deterministic"]
              for cells in matrix.values() for cell in cells.values())
    head: Dict = {"deterministic": det, "tolerance": tolerance}
    churn = matrix.get("node_churn")
    if churn is None:
        head.update({"criterion": "determinism only "
                                  "(node_churn not in this run)",
                     "pass": det})
        return head
    rfold = churn["rfold"]["chaos"]["util_overall"]
    recovered = bool(churn["rfold"]["chaos"]["recovered"])
    static_best = max(churn[k]["chaos"]["util_overall"]
                      for k in STATIC_BASELINES)
    head.update({
        "criterion": "rfold util_overall >= max(static) - tolerance "
                     "under node_churn, rfold recovered, all cells "
                     "deterministic",
        "rfold_util": round(rfold, 4),
        "static_best_util": round(static_best, 4),
        "rfold_recovered": recovered,
        "util_ok": rfold >= static_best - tolerance,
        "pass": det and recovered and rfold >= static_best - tolerance,
    })
    return head


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized: 60-job cells")
    ap.add_argument("--scenario", default=None, choices=sorted(SCENARIOS),
                    help="run a single scenario (CI matrix cell); "
                         "default: all five")
    ap.add_argument("--num-jobs", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tolerance", type=float, default=0.02,
                    help="absolute recovered-util slack for the "
                         "node_churn headline")
    ap.add_argument("--out", default="BENCH_chaos.json")
    args = ap.parse_args(argv)
    enable_compile_cache()

    num_jobs = args.num_jobs or (60 if args.quick else 120)
    scenarios = [args.scenario] if args.scenario else sorted(SCENARIOS)
    print(f"# chaos bench: {len(scenarios)} scenario(s) x "
          f"{len(POLICY_CONFIGS)} policies, {num_jobs} jobs/cell, "
          f"every cell run twice (determinism)")

    t0 = time.time()
    matrix = run_matrix(scenarios, num_jobs, args.seed)
    head = headline_from(matrix, args.tolerance)

    bench = {"num_jobs": num_jobs, "seed": args.seed,
             "scenarios": matrix, "headline": head,
             "wall_s": round(time.time() - t0, 1)}
    with open(args.out, "w") as f:
        json.dump(bench, f, indent=1)
    print(f"# headline: deterministic={head['deterministic']}", end="")
    if "rfold_util" in head:
        print(f", rfold util {head['rfold_util']} vs static best "
              f"{head['static_best_util']} "
              f"(recovered={head['rfold_recovered']})", end="")
    print(f" -> pass={head['pass']}")
    print(f"# wrote {args.out} ({bench['wall_s']}s)")


if __name__ == "__main__":
    main()
