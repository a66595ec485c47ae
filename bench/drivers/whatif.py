"""What-if cells: an operator's sweep of simulators over one broker.

This process holds the chip. Each sweep is what ``api.EvalRunner`` runs
for one fleet: ``repro.sim.fleet.Fleet`` on the configuration's engine,
one ``QueryBroker`` batching the fitmask queries of ``sims`` simulators
onto it, each simulator the configuration's policy with backfill. The
jobs come from the benchmark's own generator (``benchlib.philly``): one
pool of ``num_jobs`` Philly-statistic jobs at the mix's offered load,
drawn once in set-up from its ``pool_seed``, which every simulator of
every sweep replays in an order drawn from ``--seed``, the sweep and the
simulator. So caches keyed by content cannot flatter later sweeps, and
every seed offers the same work. Sweeps start until ``--seconds`` have
passed, and the last one finishes; the rate counts whole sweeps over
that whole span.

After the window every simulator of every sweep is run again by the
plain reference (``benchlib.plainsched.simulate``), which shares no code
with the program: ``schedule_mismatches`` counts simulators whose
schedule (each job's start, finish, drop and placement) differs;
``summary_mismatches`` those whose JCR, JCT percentiles, utilization,
job counts or utilization CDF differ; ``broker_faults`` counts engine
retries, failovers and canary mismatches over all sweeps;
``failed_sims`` counts simulators of sweeps that raised. Each has the
limit 0.
"""
from __future__ import annotations

import shutil
import tempfile
import time
from typing import Any, Callable, Dict, List

from benchlib import philly, plainsched, tracefile
from benchlib.device import CompileCounter, memory_peak_bytes
from benchlib.harness import Cell, Run
from benchlib.reference import canonical
from benchlib.spans import Spans, instrument_engine, restore_engine
from benchlib.warmup import warm

SUMMARY = ("summary", "cdf_levels", "cdf")
BROKER_FAULTS = ("engine_retries", "engine_failovers", "canary_mismatches")
_SEED_MOD = 2 ** 63


def traffic(cell: Cell) -> Dict[str, Any]:
    """The mix's job parameters at the configuration's size."""
    n = cell.config["num_xpus"]
    return {**cell.mix["philly"], "cluster_xpus": n, "size_max": n}


def sim_jobs(drawn, cell: Cell, sweep: int, sim: int) -> List[philly.Job]:
    """Simulator ``sim`` of sweep ``sweep``: the pool in its own order."""
    return philly.arrange(drawn, [cell.seed % _SEED_MOD, sweep, sim])


def _unit(cell: Cell, jobs: List[philly.Job]) -> Callable[[Any], Dict]:
    """One simulator of the fleet; returns what an operator reads."""
    from repro.core.allocator import make_policy
    from repro.core.geometry import JobShape
    from repro.sim.job import Job
    from repro.sim.metrics import summarize, utilization_cdf
    from repro.sim.simulator import Simulator
    c = cell.config

    def go(broker) -> Dict[str, Any]:
        policy = make_policy(c["policy"], mask_client=broker,
                             **c["policy_kw"])
        res = Simulator(policy, [Job(j.job_id, j.arrival, j.duration,
                                     JobShape(j.shape)) for j in jobs],
                        backfill=cell.mix["backfill"]).run()
        levels, cdf = utilization_cdf(res)
        return {"summary": summarize(res),
                "cdf_levels": [float(x) for x in levels],
                "cdf": [float(x) for x in cdf],
                "schedule": [[j.job_id, j.start, j.finish, j.dropped,
                              j.placement_meta] for j in res.jobs]}
    return go


def check(cell: Cell, sweeps: List[Dict[str, Any]]) -> List[tuple]:
    c = cell.config
    schedules = summaries = 0
    for s in sweeps:
        for jobs, got in zip(s["jobs"], s["records"]):
            want = plainsched.simulate(c["policy"], c["policy_kw"], jobs,
                                       cell.mix["backfill"])
            schedules += canonical(got["schedule"]) != canonical(
                want["schedule"])
            summaries += any(canonical(got[k]) != canonical(want[k])
                             for k in SUMMARY)
    faults = sum(int(s["broker"].get(k, 0)) for s in sweeps
                 for k in BROKER_FAULTS)
    failed = sum(len(s["jobs"]) - len(s["records"]) for s in sweeps)
    return [("schedule_mismatches", schedules, 0),
            ("summary_mismatches", summaries, 0),
            ("broker_faults", faults, 0),
            ("failed_sims", failed, 0)]


def run(cell: Cell) -> Run:
    from jax.profiler import TraceAnnotation
    from repro.kernels.fitmask import ops as fitmask_ops
    from repro.sim.fleet import Fleet

    engine_name = cell.engine or cell.config["engine"]
    engine = fitmask_ops.get_engine(engine_name)
    warm(engine, cell.config["warm"]["whatif"])
    cell.mark("warm")
    drawn = philly.pool(traffic(cell), cell.mix["num_jobs"])
    _unit(cell, [])          # imports the simulator here, not in the window
    spans = Spans() if cell.trace else None
    if spans is not None:
        instrument_engine(engine, spans)
    if cell.engine_hook is not None:
        cell.engine_hook(engine)
    counter = CompileCounter()
    workdir = cell.workdir or tempfile.mkdtemp(prefix="bench_trace_")
    sims = cell.mix["sims"]
    try:
        before = counter.compiles
        if cell.trace:
            tracefile.start(workdir)
        setup_s = time.perf_counter() - cell.t_start
        sweeps: List[Dict[str, Any]] = []
        with TraceAnnotation(tracefile.WINDOW):
            t0 = time.perf_counter()
            while not sweeps or time.perf_counter() - t0 < cell.seconds:
                jobs = [sim_jobs(drawn, cell, len(sweeps), k)
                        for k in range(sims)]
                fleet = Fleet(engine_name)
                try:
                    records = fleet.run([_unit(cell, j) for j in jobs])
                    broker = fleet.broker.stats.as_dict()
                except Exception as e:  # noqa: BLE001 -- a failed sweep
                    records, broker = [], {"error": repr(e)[:300]}
                sweeps.append({"jobs": jobs, "records": records,
                               "broker": broker})
            span = time.perf_counter() - t0
        if cell.trace:
            tracefile.stop()
        after = counter.compiles
        mem = memory_peak_bytes(cell.chips)
        ran = len(sweeps) * sims
        failed = sum(sims - len(s["records"]) for s in sweeps)
        run = Run(setup_s=setup_s, window_s=span, attempted=ran,
                  failed=failed, memory_peak_bytes=mem, spans=spans,
                  peaks=cell.peaks,
                  extra={"sweeps": len(sweeps),
                         "jobs": ran * cell.mix["num_jobs"],
                         "span_s": span, "trace_t0": t0,
                         "trace_t1": t0 + span})
        run.counters["window_compiles"] = after - before
        run.counters["broker"] = {
            k: sum(int(s["broker"].get(k, 0)) for s in sweeps)
            for k in ("grids", "engine_calls", "padded_grids", "flushes")}
        if cell.trace:
            run.trace = tracefile.reduce_events(tracefile.load(workdir))
        if cell.check:
            t_check = time.perf_counter()
            run.checks = check(cell, sweeps)
            run.extra["check_s"] = time.perf_counter() - t_check
        return run
    finally:
        restore_engine(engine)
        if cell.workdir is None:
            shutil.rmtree(workdir, ignore_errors=True)
