"""Fleet-batched eval benchmark: one engine, many simulators.

Two claims under test (see DESIGN.md §Fleet-batched eval and
§Continuous-batching broker):

* **Parity + host headline.** The CI-sized eval matrix (3 runs x 200
  jobs x 8 policy configs; ``--quick`` shrinks it) is run per-task
  (``fleet_size=0`` — the retained sequential oracle path) and with
  the runner's *defaults* (fleet mode is unconditional now), both at
  ``workers=0`` so the delta is the fleet layer itself, not process
  parallelism. The Table 1 / Fig 3 / Fig 4 aggregates must be
  **byte-identical**, and on the default numpy engine the fleet side
  must be **no slower than sequential** (>= 1.0x): the broker's
  continuous quorum/deadline scheduling plus the genuinely batched
  host multibox (``fit_mask_multi_fast``) and inline free-counts must
  at least pay for their own coordination.

* **Engine headline.** On a batched engine — where a call costs real
  dispatch, which is the whole reason the multibox kernel exists —
  serving a fleet's *coalesced query stream* must beat answering the
  same stream with per-simulator batch-1 calls by >= 5x, with the
  broker demonstrably issuing batched (B > 1, multi-request) engine
  calls. The headline replays an eval-shaped query stream (per
  round, each of N simulators submits one multibox over its own
  16^3 occupancy against a shared candidate-box set, plus one
  free-counts query — the static-torus epoch pattern) through the
  *real* broker under the fleet's production flush policy, one
  thread per simulator, against the ``jax`` engine (the accelerator
  path that runs everywhere CI does; the Pallas kernel shares its
  batching axis). The same stream is then driven batch-1, and both
  sides are warmed before timing. Answers are asserted equivalent
  per round (same fit truth-planes, same free counts — the broker's
  bucketed path returns bool planes where the inline path returns
  int32 0/1).

  Where the 5x comes from: one fused program per flush (integral
  image + all K planes + free counts, written in-place into a single
  (B, K, X, Y, Z) buffer) replaces ~22 per-sim dispatches; the
  free-counts content cache answers the follow-up free query of
  every simulator from the planes flush; and the bucket's stable box
  table means the steady state re-runs one compiled program at exact
  K rather than retracing per flush union.

  This is deliberately an engine-serving measurement, like the
  multi-box kernel bench it extends (one VMEM pass for K boxes ->
  one engine pass for B simulators): end-to-end eval wall-clock on
  a CPU-only container is GIL-bound python simulation plus host
  numpy mask work, which batching cannot compress (Amdahl — the
  parity section reports that delta honestly). The stream replay is
  the fraction the fleet layer actually owns, and the fraction that
  turns into accelerator dispatch/occupancy on real hardware.

  PYTHONPATH=src python -m benchmarks.fleet_bench [--quick] \
      [--out BENCH_fleet.json]
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict

from repro.eval import (EvalRunner, aggregate_by_label, fig3, fig4,
                        make_tasks, table1)
from repro.compile_cache import enable_compile_cache

# Dual headline floors: fleet mode may not slow the host path down,
# and must beat per-sim batch-1 driving on a compiled engine by 5x.
NUMPY_FLOOR = 1.0
ENGINE_FLOOR = 5.0

# The paper's full policy matrix (benchmarks.paper_eval.TABLE1_CONFIGS
# + the Fig-3 extras), inlined so the bench stays import-light.
EVAL_CONFIGS = [
    ("FirstFit (16^3)", "firstfit", dict(dims=(16, 16, 16))),
    ("Folding (16^3)", "folding", dict(dims=(16, 16, 16))),
    ("Reconfig (8^3)", "reconfig", dict(num_xpus=4096, cube_n=8)),
    ("RFold (8^3)", "rfold", dict(num_xpus=4096, cube_n=8)),
    ("Reconfig (4^3)", "reconfig", dict(num_xpus=4096, cube_n=4)),
    ("RFold (4^3)", "rfold", dict(num_xpus=4096, cube_n=4)),
    ("Reconfig (2^3)", "reconfig", dict(num_xpus=4096, cube_n=2)),
    ("RFold (2^3)", "rfold", dict(num_xpus=4096, cube_n=2)),
]


def _strip(records):
    return [{k: v for k, v in r.items() if k != "sim_s"} for r in records]


def _figures(records):
    aggs = aggregate_by_label(records)
    return {"table1": table1(aggs), "fig3": fig3(aggs),
            "fig4": fig4(aggs)}


def parity_section(runs: int, num_jobs: int, seed0: int) -> Dict:
    """Sequential oracle (``fleet_size=0``) vs the runner defaults on
    the default (numpy) engine: byte-equal figures required, and the
    fleet side must not be slower (the numpy half of the headline)."""
    tasks = make_tasks(EVAL_CONFIGS, runs=runs, num_jobs=num_jobs,
                       load=1.5, seed0=seed0)
    t0 = time.perf_counter()
    seq = EvalRunner(workers=0, fleet_size=0).run(tasks)
    seq_s = time.perf_counter() - t0

    fleet_runner = EvalRunner(workers=0)   # fleet mode is the default
    t0 = time.perf_counter()
    fl = fleet_runner.run(tasks)
    fleet_s = time.perf_counter() - t0

    figs_seq, figs_fl = _figures(seq), _figures(fl)
    identical = (
        _strip(seq) == _strip(fl)
        and json.dumps(figs_seq, sort_keys=True, default=float)
        == json.dumps(figs_fl, sort_keys=True, default=float))
    return {
        "runs": runs, "num_jobs": num_jobs, "configs": len(EVAL_CONFIGS),
        "tasks": len(tasks), "identical": identical,
        "sequential_s": round(seq_s, 3), "fleet_s": round(fleet_s, 3),
        "numpy_speedup": round(seq_s / fleet_s, 2) if fleet_s else None,
        "fleet": fleet_runner.last_stats.get("fleet"),
    }


# The static-torus epoch pattern: one multibox over the simulator's
# own grid against its candidate-box set, plus one free-counts query.
# K = 20 candidate boxes — the scale a folding policy's fold
# enumeration actually produces per step.
REPLAY_BOXES = ((1, 1, 8), (1, 2, 4), (1, 4, 8), (2, 2, 2), (2, 2, 8),
                (2, 4, 2), (2, 4, 8), (2, 8, 4), (4, 2, 2), (4, 4, 1),
                (4, 4, 4), (4, 8, 2), (8, 2, 1), (8, 4, 4), (8, 8, 2),
                (8, 8, 8), (16, 1, 1), (16, 2, 2), (16, 4, 1),
                (16, 16, 1))


def engine_section(sims: int, rounds: int, seed0: int,
                   engine: str = "jax") -> Dict:
    """The engine headline: replay ``rounds`` coalescing rounds of
    ``sims`` simulators' mask queries through the real broker under
    the fleet's production flush policy (one thread per simulator)
    vs driving the identical stream with per-simulator batch-1
    calls. Both sides warm; answers asserted equivalent."""
    import threading

    import numpy as np

    from repro.kernels.fitmask import ops
    from repro.sim.fleet import Fleet

    eng = ops.get_engine(engine)
    rng = np.random.default_rng(seed0)
    # Evolving occupancy per (simulator, round): fill drifts like a
    # loaded cluster's does.
    occ = rng.random((sims, rounds, 1, 16, 16, 16)) < \
        rng.uniform(0.1, 0.6, size=(sims, rounds, 1, 1, 1, 1))

    def drive_sequential():
        out = []
        for s in range(sims):
            row = []
            for t in range(rounds):
                row.append((np.asarray(eng.multibox(occ[s, t],
                                                    REPLAY_BOXES)),
                            np.asarray(eng.free_counts(occ[s, t]))))
            out.append(row)
        return out

    def drive_fleet():
        # The production broker policy: engine-aware quorum/deadline,
        # bucketed padded programs, fc content cache.
        broker = Fleet(eng).broker
        broker.pad_hint = sims
        out = [[None] * rounds for _ in range(sims)]

        def sim(s):
            try:
                for t in range(rounds):
                    mb = broker.multibox(occ[s, t], REPLAY_BOXES)
                    fc = broker.free_counts(occ[s, t])
                    out[s][t] = (mb, fc)
            finally:
                # Each simulator retires itself so survivors' rounds
                # keep flushing — exactly what Fleet.run does.
                broker.deactivate()

        for _ in range(sims):
            broker.register()
        threads = [threading.Thread(target=sim, args=(s,))
                   for s in range(sims)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        return out, broker.stats

    # Warm both sides (jit compiles at the bucket's padded and exact-K
    # table shapes, and at B=1 for the sequential path), then time
    # several passes and keep the best of each: dispatch timings on a
    # shared/loaded host are noisy, and best-of-N measures the
    # machinery rather than the scheduler.
    passes = 3
    drive_fleet()
    fleet_s, fleet_out, stats = None, None, None
    for _ in range(passes):
        t0 = time.perf_counter()
        out, st = drive_fleet()
        dt = time.perf_counter() - t0
        if fleet_s is None or dt < fleet_s:
            fleet_s, fleet_out, stats = dt, out, st

    drive_sequential()
    seq_s, seq_out = None, None
    for _ in range(passes):
        t0 = time.perf_counter()
        out = drive_sequential()
        dt = time.perf_counter() - t0
        if seq_s is None or dt < seq_s:
            seq_s, seq_out = dt, out

    # Truth-plane equivalence: inline multibox answers int32 0/1, the
    # broker's bucketed flush path answers bool — same fit truth.
    identical = all(
        np.array_equal(a[0] != 0, b[0] != 0)
        and np.array_equal(a[1], b[1])
        for srow, frow in zip(seq_out, fleet_out)
        for a, b in zip(srow, frow))
    return {
        "engine": engine, "sims": sims, "rounds": rounds,
        "k_boxes": len(REPLAY_BOXES), "grid": "16^3",
        "queries": sims * rounds * 2, "identical": identical,
        "sequential_s": round(seq_s, 3), "fleet_s": round(fleet_s, 3),
        "speedup": round(seq_s / fleet_s, 2) if fleet_s else None,
        "broker": stats.as_dict(),
    }


def canary_section(seed0: int, flushes: int = 6) -> Dict:
    """The post-failover parity canary as a first-class drill: two
    injected faults kill the pallas engine under the broker, which
    fails over to jax — and the first post-failover flushes are
    parity-checked against the host numpy oracle. Zero mismatches is
    a CI gate (the answers are pure functions of the inputs, so any
    mismatch is a real defect, not noise)."""
    import numpy as np

    from repro.sim.fleet import QueryBroker

    broker = QueryBroker("pallas")
    broker.inject_engine_faults(2)
    rng = np.random.default_rng(seed0)
    boxes = ((2, 2, 2), (4, 2, 1), (3, 3, 1))
    for _ in range(flushes):
        occ = rng.random((2, 16, 16, 16)) < 0.35
        broker.multibox(occ, boxes)
    st = broker.stats
    return {
        "start_engine": "pallas",
        "adopted_engine": broker.engine_name,
        "flushes": flushes,
        "engine_failovers": st.engine_failovers,
        "canary_checks": st.canary_checks,
        "canary_mismatches": st.canary_mismatches,
        "pass": bool(st.engine_failovers >= 1 and st.canary_checks >= 1
                     and st.canary_mismatches == 0),
    }


def main(argv=None) -> Dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=str, default="BENCH_fleet.json")
    ap.add_argument("--seed0", type=int, default=100)
    ap.add_argument("--quick", action="store_true",
                    help="smaller matrix for smoke runs")
    ap.add_argument("--engine", type=str, default="jax",
                    help="batched engine for the headline section")
    args = ap.parse_args(argv)
    enable_compile_cache()

    runs, num_jobs = (2, 60) if args.quick else (3, 200)
    sims, rounds = (6, 80) if args.quick else (8, 120)

    print(f"# fleet bench: parity matrix {runs}x{num_jobs}x"
          f"{len(EVAL_CONFIGS)} (numpy), headline replay {sims} sims "
          f"x {rounds} rounds ({args.engine})")
    par = parity_section(runs, num_jobs, args.seed0)
    print(f"# parity: identical={par['identical']} "
          f"seq={par['sequential_s']}s fleet={par['fleet_s']}s "
          f"(numpy, {par['numpy_speedup']}x)")
    eng = engine_section(sims, rounds, args.seed0, engine=args.engine)
    print(f"# replay: identical={eng['identical']} "
          f"seq={eng['sequential_s']}s fleet={eng['fleet_s']}s "
          f"-> {eng['speedup']}x, broker {eng['broker']}")

    can = canary_section(args.seed0)
    print(f"# canary: {can['start_engine']} -> "
          f"{can['adopted_engine']} after "
          f"{can['engine_failovers']} failover(s), "
          f"{can['canary_checks']} checks "
          f"{can['canary_mismatches']} mismatches "
          f"(pass={can['pass']})")

    broker = eng["broker"]
    pass_numpy = bool(par["identical"] and par["numpy_speedup"]
                      and par["numpy_speedup"] >= NUMPY_FLOOR)
    pass_engine = bool(eng["identical"] and eng["speedup"]
                       and eng["speedup"] >= ENGINE_FLOOR
                       and broker["batched_calls"] > 0
                       and broker["mean_grids_per_call"] > 1)
    results = {
        "config": {"quick": args.quick, "seed0": args.seed0},
        "parity": par,
        "engine": eng,
        "canary": can,
        "headline": {
            "criterion": "fleet mode (the runner default) is >= "
                         f"{NUMPY_FLOOR}x sequential on the numpy host "
                         "engine with byte-identical eval aggregates, "
                         "AND the broker-coalesced query stream is >= "
                         f"{ENGINE_FLOOR}x faster than per-sim batch-1 "
                         f"driving on the batched ({args.engine}) "
                         "engine at CI size, broker issuing batched "
                         "(B > 1) engine calls, answers equivalent, "
                         "AND the post-failover parity canary records "
                         "zero mismatches",
            "numpy_speedup": par["numpy_speedup"],
            "engine_speedup": eng["speedup"],
            "batched_calls": broker["batched_calls"],
            "mean_grids_per_call": broker["mean_grids_per_call"],
            "flush_triggers": {
                "all_parked": broker["flush_all_parked"],
                "quorum": broker["flush_quorum"],
                "timeout": broker["flush_timeout"],
            },
            "requeued": broker["requeued"],
            "b_pad_waste": broker["b_pad_waste"],
            "k_pad_waste": broker["k_pad_waste"],
            "fc_cache_hits": broker["fc_cache_hits"],
            "canary_checks": can["canary_checks"],
            "canary_mismatches": can["canary_mismatches"],
            "pass_numpy": pass_numpy,
            "pass_engine": pass_engine,
            "pass_canary": can["pass"],
            "pass": pass_numpy and pass_engine and can["pass"],
        },
    }
    print(f"# headline: numpy {par['numpy_speedup']}x "
          f"(pass={pass_numpy}), {args.engine} {eng['speedup']}x "
          f"(pass={pass_engine}) -> pass={results['headline']['pass']}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"# wrote {args.out}")
    return results


if __name__ == "__main__":
    main()
