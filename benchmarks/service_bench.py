"""Allocator-service benchmark: parity, placement latency, admission.

Three sections over the live daemon (``repro.serve.scheduler``):

* **Parity.** A Poisson trace is simulated twice — in-process policy
  vs the daemon driven through :class:`RemotePolicy` over TCP — and
  the per-job schedules must be **byte-identical** on every policy.
  This is the CI smoke: the service is only a service if it is still
  the same allocator.

* **Latency headline.** The p99 wall-clock of a ``submit`` RPC while
  replaying a Poisson arrival trace against the daemon (completions
  retired between arrivals, so the occupancy grid churns like a loaded
  cluster's). The same op stream is replayed against an in-process
  :class:`AllocatorCore` — the identical state machine minus the
  socket and event loop — so the headline isolates what the service
  layer *owns*: protocol encode/decode, the loop hop, and event
  fan-out. Asserted: ``p99(remote) - p99(in-process) <= threshold``
  (default 25 ms — generous over the ~1 ms a local RPC costs, tight
  enough to catch an accidental O(n) in the daemon path). The
  placement work itself (tens to hundreds of ms at the p99 on the
  4096-XPU paper cluster — a fresh shape's feasibility probe places
  on an empty clone) is the allocator the other benches measure.

* **Admission under overload.** Flood a small cluster (bounded queue)
  with more feasible jobs than it can hold: every overflow submit must
  be REJECTED statelessly, the queue depth must never exceed the
  bound, and the daemon must still answer ``status`` promptly while
  overloaded.

* **Resilience (PR 9).** The crash-loop drill
  (``benchmarks.crash_loop``): the daemon is killed at seeded points
  mid-churn, recovers from snapshot + WAL tail, absorbs the resent
  in-flight ops through the journal-persisted dedup cache, and must
  land on a final state digest byte-identical to an uninterrupted
  control run. The dedup/lease/WAL counters land in the artifact.

  PYTHONPATH=src python -m benchmarks.service_bench [--quick] \
      [--out BENCH_service.json]
"""
from __future__ import annotations

import argparse
import json
import time
from heapq import heappop, heappush
from typing import Dict, List

import numpy as np

from repro.api import (Scheduler, SchedulerConfig, Simulator, TraceConfig,
                       generate_trace, make_policy, summarize)
from repro.serve.scheduler import PLACED, QUEUED, REJECTED, AllocatorCore
from repro.compile_cache import enable_compile_cache

OVERHEAD_THRESHOLD_MS = 25.0

PARITY_CONFIGS = [
    ("FirstFit (8^3)", "firstfit", dict(dims=(8, 8, 8))),
    ("Folding (8^3)", "folding", dict(dims=(8, 8, 8))),
    ("Reconfig (4^3)", "reconfig", dict(num_xpus=512, cube_n=4)),
    ("RFold (4^3)", "rfold", dict(num_xpus=512, cube_n=4)),
    ("RFold-BE (4^3)", "rfold_be", dict(num_xpus=512, cube_n=4)),
]


def _job_record(jobs) -> str:
    return json.dumps(
        [[j.job_id, j.start, j.finish, j.dropped, j.slowdown,
          j.placement_meta] for j in jobs],
        sort_keys=True, default=list)


def parity_section(num_jobs: int, seed: int) -> Dict:
    """Drive the same trace through the in-process policy and through
    the daemon (simulator-as-client); schedules and summary metrics
    must match byte for byte."""
    trace_cfg = TraceConfig(num_jobs=num_jobs, cluster_xpus=512,
                            size_max=512, seed=seed)
    rows = []
    for label, policy, kw in PARITY_CONFIGS:
        local = Simulator(make_policy(policy, **kw),
                          generate_trace(trace_cfg)).run()
        t0 = time.perf_counter()
        with Scheduler(SchedulerConfig(policy=policy, policy_kw=kw)) as s:
            remote = Simulator(s.remote_policy(),
                               generate_trace(trace_cfg)).run()
        remote_s = time.perf_counter() - t0
        identical = (
            _job_record(local.jobs) == _job_record(remote.jobs)
            and json.dumps(summarize(local), sort_keys=True)
            == json.dumps(summarize(remote), sort_keys=True))
        rows.append({"label": label, "identical": identical,
                     "jobs": num_jobs,
                     "remote_s": round(remote_s, 3)})
    return {"configs": rows,
            "identical": all(r["identical"] for r in rows)}


def _replay(jobs, submit, done) -> Dict:
    """Poisson replay: retire completions between arrivals, time every
    submit. ``submit``/``done`` are callables returning reply dicts —
    the daemon client or the in-process core speak the same shape."""
    submit_ms: List[float] = []
    done_ms: List[float] = []
    outcomes: Dict[str, int] = {}
    finishing: List = []  # (finish_time, job_id) min-heap
    duration = {j.job_id: j.duration for j in jobs}
    for job in jobs:
        now = job.arrival
        while finishing and finishing[0][0] <= now:
            _, jid = heappop(finishing)
            t0 = time.perf_counter()
            d = done(jid)
            done_ms.append((time.perf_counter() - t0) * 1e3)
            for st in d["started"]:
                if st["outcome"] == PLACED:
                    heappush(finishing,
                             (now + duration[st["job_id"]],
                              st["job_id"]))
        t0 = time.perf_counter()
        r = submit(job)
        submit_ms.append((time.perf_counter() - t0) * 1e3)
        outcomes[r["outcome"]] = outcomes.get(r["outcome"], 0) + 1
        if r["outcome"] == PLACED:
            heappush(finishing, (now + job.duration, job.job_id))
    arr = np.asarray(submit_ms)
    return {
        "outcomes": outcomes,
        "submit_p50_ms": round(float(np.percentile(arr, 50)), 3),
        "submit_p99_ms": round(float(np.percentile(arr, 99)), 3),
        "submit_max_ms": round(float(arr.max()), 3),
        "done_p99_ms": round(float(np.percentile(done_ms, 99)), 3)
        if done_ms else None,
        "rpcs": len(submit_ms) + len(done_ms),
    }


def latency_section(num_jobs: int, seed: int) -> Dict:
    """The same Poisson op stream against the in-process core and the
    live daemon; the difference in p99 is the service layer's bill."""
    trace_cfg = TraceConfig(num_jobs=num_jobs, seed=seed)
    policy_kw = dict(num_xpus=4096, cube_n=4)

    def core_replay(core):
        return _replay(
            generate_trace(trace_cfg),
            lambda job: core.apply({"op": "submit", "job_id": job.job_id,
                                    "shape": list(job.shape.dims)})[0],
            lambda jid: core.apply({"op": "done", "job_id": jid})[0])

    # Warm-up pass on a throwaway core: fold enumeration and shape
    # factorization caches are process-global LRUs, and whichever side
    # runs first would otherwise pay every miss for both.
    core_replay(AllocatorCore(SchedulerConfig(policy="rfold",
                                              policy_kw=policy_kw)))

    core = AllocatorCore(SchedulerConfig(policy="rfold",
                                         policy_kw=policy_kw))
    local = core_replay(core)

    with Scheduler(SchedulerConfig(policy="rfold",
                                   policy_kw=policy_kw)) as sched:
        remote = _replay(
            generate_trace(trace_cfg),
            lambda job: sched.submit(job.shape, job_id=job.job_id),
            sched.done)

    assert remote["outcomes"] == local["outcomes"], (remote, local)
    return {
        "jobs": num_jobs,
        "outcomes": remote["outcomes"],
        "local": local,
        "remote": remote,
        "overhead_p50_ms": round(remote["submit_p50_ms"]
                                 - local["submit_p50_ms"], 3),
        "overhead_p99_ms": round(remote["submit_p99_ms"]
                                 - local["submit_p99_ms"], 3),
    }


def admission_section(flood: int) -> Dict:
    """Overload a one-cube cluster with a bounded queue: overflow must
    be rejected statelessly and the daemon must stay responsive."""
    max_queue = 8
    cfg = SchedulerConfig(policy="rfold",
                          policy_kw=dict(num_xpus=64, cube_n=4),
                          max_queue=max_queue)
    counts = {PLACED: 0, QUEUED: 0, REJECTED: 0}
    depth_ok = True
    with Scheduler(cfg) as sched:
        for _ in range(flood):
            r = sched.submit((4, 4, 4))  # whole-cube: one fits at a time
            counts[r["outcome"]] += 1
            depth_ok &= sched.status()["queue_depth"] <= max_queue
        t0 = time.perf_counter()
        st = sched.status()
        status_ms = (time.perf_counter() - t0) * 1e3
        journal_ops = st["journal_ops"]
    expected_rejects = flood - 1 - max_queue
    return {
        "flood": flood, "max_queue": max_queue, "counts": counts,
        "depth_bounded": depth_ok,
        "rejects_stateless": journal_ops == 1 + max_queue,
        "status_under_load_ms": round(status_ms, 3),
        "pass": (counts[REJECTED] == expected_rejects and depth_ok
                 and journal_ops == 1 + max_queue),
    }


def resilience_section(num_jobs: int, seed: int, kills: int) -> Dict:
    """Crash-loop drill + the recovered daemon's resilience counters
    (dedup hits, WAL tail length, recovered op count)."""
    from benchmarks.crash_loop import run_drill
    drill = run_drill(num_jobs, seed, kills)
    return {
        "ops": drill["ops"], "kills": drill["kills"],
        "identical": drill["identical"],
        "resends_clean": drill["crash"]["resends_clean"],
        "counters": drill["crash"]["resilience"],
        "pass": drill["pass"],
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized: 50-job parity, 150-job latency")
    ap.add_argument("--threshold-ms", type=float,
                    default=OVERHEAD_THRESHOLD_MS,
                    help="max p99 service overhead vs in-process")
    ap.add_argument("--out", default="BENCH_service.json")
    args = ap.parse_args(argv)
    enable_compile_cache()

    parity_jobs = 50 if args.quick else 120
    latency_jobs = 150 if args.quick else 500
    flood = 40 if args.quick else 200
    drill_jobs, drill_kills = (36, 3) if args.quick else (60, 5)

    print(f"# service bench: parity {parity_jobs} jobs x "
          f"{len(PARITY_CONFIGS)} policies, latency {latency_jobs} jobs, "
          f"admission flood {flood}, crash drill {drill_jobs} jobs / "
          f"{drill_kills} kills")

    par = parity_section(parity_jobs, seed=3)
    for row in par["configs"]:
        print(f"  parity {row['label']:16s} identical={row['identical']} "
              f"({row['remote_s']}s remote)")

    lat = latency_section(latency_jobs, seed=11)
    print(f"  latency: remote p50 {lat['remote']['submit_p50_ms']}ms "
          f"p99 {lat['remote']['submit_p99_ms']}ms | in-process p99 "
          f"{lat['local']['submit_p99_ms']}ms | service overhead p99 "
          f"{lat['overhead_p99_ms']}ms ({lat['remote']['rpcs']} RPCs)")

    adm = admission_section(flood)
    print(f"  admission: {adm['counts']} depth_bounded="
          f"{adm['depth_bounded']} stateless={adm['rejects_stateless']}")

    res = resilience_section(drill_jobs, seed=17, kills=drill_kills)
    print(f"  resilience: kills at {res['kills']} identical="
          f"{res['identical']} dedup_hits="
          f"{res['counters']['dedup_hits']} "
          f"wal_tail={res['counters']['wal_tail_ops']}")

    headline = {
        "p99_ms": lat["remote"]["submit_p99_ms"],
        "local_p99_ms": lat["local"]["submit_p99_ms"],
        "overhead_p99_ms": lat["overhead_p99_ms"],
        "threshold_ms": args.threshold_ms,
        "parity": par["identical"],
        "admission": adm["pass"],
        "resilience": res["pass"],
        "pass": (par["identical"] and adm["pass"] and res["pass"]
                 and lat["overhead_p99_ms"] <= args.threshold_ms),
    }
    bench = {"parity": par, "latency": lat, "admission": adm,
             "resilience": res, "headline": headline}
    with open(args.out, "w") as f:
        json.dump(bench, f, indent=1)
    print(f"# headline: p99 {headline['p99_ms']}ms, service overhead "
          f"{headline['overhead_p99_ms']}ms "
          f"(<= {headline['threshold_ms']}ms) parity={headline['parity']} "
          f"admission={headline['admission']} "
          f"resilience={headline['resilience']} pass={headline['pass']}")
    print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
