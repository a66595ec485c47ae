"""Chip smoke test: the scheduler's device path, once, on one TPU.

    python chip_smoke.py [--seed N]

One process drives the system through ``repro.api`` at the paper's
cluster size, with the fitmask engine compiled on the chip:

* ``served-rfold``: ``api.Scheduler`` with RFold on the default pod
  (4096 XPUs as 64 cubes of 4^3), ``engine="pallas"``, a fsynced WAL
  in a temporary checkpoint directory. A ``SchedulerClient`` submits
  ~200 Philly-statistic jobs at arrival and retires the oldest live
  job after every third submit. Every reply must be ``ok``, and the
  final ``state_digest`` must equal that of a numpy-engine daemon fed
  the same stream.
* ``served-firstfit``: the same on the static 16^3 torus, the engine's
  other grid shape (one large grid instead of 64 small ones).
* ``fleet``: the what-if path, four RFold simulators over one
  ``QueryBroker`` on ``pallas``; JCR, JCT and utilization must equal
  the numpy engine's, with no engine retry, failover or canary
  mismatch and at least one engine call.

Each phase prints its jobs placed, wall seconds and compile count and
seconds. These are set-up figures of a cold run, not speed results.
The last line is ``{"ok": true, "device": {...}}`` as JAX reports the
device. With no TPU (or when any phase fails) the script exits
non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from typing import Any, Dict, List, Sequence

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro import api  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve.scheduler import protocol  # noqa: E402
from repro.traces.generator import TRACE_PRESETS  # noqa: E402

PAPER_XPUS = 4096       # the paper's pod: 64 cubes of 4^3 (TPU v4 layout)
CUBE_N = 4
SERVED_JOBS = 200
FLEET_SIMS = 4
FLEET_JOBS = 200
FLEET_LOAD = 1.5


class SmokeFailure(AssertionError):
    """A phase produced a wrong or incomplete result."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileCounter:
    """Counts XLA backend compiles and persistent-cache hits through
    ``jax.monitoring``: a phase's cost in compiling, not in running."""

    def __init__(self) -> None:
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits}


def philly_jobs(num_jobs: int, seed: int, num_xpus: int) -> List[Any]:
    """Philly-statistic jobs (Jeon et al., ATC '19; the ``philly``
    preset) sized for a cluster of ``num_xpus``."""
    cfg = api.TraceConfig.preset("philly", num_jobs=num_jobs, seed=seed,
                                 cluster_xpus=num_xpus, size_max=num_xpus)
    return api.generate_trace(cfg)


def drive_served(policy: str, policy_kw: Dict[str, Any],
                 jobs: Sequence[Any], engine: str) -> Dict[str, Any]:
    """Run one daemon on ``engine`` through a ``SchedulerClient``:
    submit every job in arrival order and, after every third submit,
    retire the oldest job the daemon still knows (dropped jobs are
    never retired). The client raises on any reply that is not
    ``ok`` — the daemon answers a failed engine call with such a reply
    and keeps serving. Returns the per-op outcomes, the final digest
    and the number of jobs placed."""
    outcomes: List[Any] = []
    placed = 0
    live: List[int] = []
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as ckpt:
        cfg = api.SchedulerConfig(policy=policy, policy_kw=dict(policy_kw),
                                  engine=engine, checkpoint_dir=ckpt,
                                  fsync=True)
        with api.Scheduler(cfg) as sched:
            client = sched.new_client()
            try:
                for n, job in enumerate(jobs, start=1):
                    reply = client.submit(list(job.shape.dims),
                                          job_id=job.job_id)
                    outcomes.append(reply["outcome"])
                    if reply["outcome"] == protocol.PLACED:
                        placed += 1
                    if reply["outcome"] != protocol.DROPPED:
                        live.append(job.job_id)
                    if n % 3 == 0 and live:
                        reply = client.done(live.pop(0))
                        outcomes.append(("done", tuple(reply["started"])))
                        placed += len(reply["started"])
                status = client.status()
            finally:
                client.close()
    return {"outcomes": outcomes, "digest": status["state_digest"],
            "placed": placed, "jobs": len(jobs)}


def served_phase(policy: str, policy_kw: Dict[str, Any],
                 jobs: Sequence[Any], engine: str = "pallas"
                 ) -> Dict[str, Any]:
    """The served path on ``engine`` against the numpy reference run
    of the same stream: identical outcomes and state digest."""
    ref = drive_served(policy, policy_kw, jobs, "numpy")
    t0 = time.perf_counter()
    got = drive_served(policy, policy_kw, jobs, engine)
    wall = time.perf_counter() - t0
    _check(got["outcomes"] == ref["outcomes"],
           f"{policy}: {engine} outcomes differ from the numpy reference")
    _check(got["digest"] == ref["digest"],
           f"{policy}: {engine} state_digest {got['digest']} != numpy "
           f"{ref['digest']}")
    return {"placed": got["placed"], "jobs": got["jobs"],
            "digest": got["digest"], "wall_s": wall}


def run_fleet(engine: str, num_xpus: int, num_jobs: int, sims: int,
              seed: int) -> Dict[str, Any]:
    """``sims`` RFold simulators as one fleet over one query broker."""
    trace_kw = {**TRACE_PRESETS["philly"], "cluster_xpus": num_xpus,
                "size_max": num_xpus}
    tasks = api.make_tasks(
        [("RFold (4^3)", "rfold", {"num_xpus": num_xpus, "cube_n": CUBE_N})],
        runs=sims, num_jobs=num_jobs, load=FLEET_LOAD, seed0=seed,
        trace_kw=trace_kw)
    runner = api.EvalRunner(
        workers=0, engine=api.EngineConfig(engine=engine, fleet_size=sims))
    records = runner.run(tasks)
    fleet = runner.last_stats["fleet"]
    _check(fleet["fleets"] == 1, f"{engine}: expected one fleet, got "
           f"{fleet['fleets']}")
    return {"summaries": [r["summary"] for r in records],
            "broker": fleet["broker"]}


def fleet_phase(num_xpus: int, num_jobs: int, sims: int, seed: int,
                engine: str = "pallas") -> Dict[str, Any]:
    """The what-if path on ``engine``: the fleet's JCR, JCT and
    utilization equal the numpy engine's, and the broker stayed on
    ``engine`` the whole run."""
    ref = run_fleet("numpy", num_xpus, num_jobs, sims, seed)
    t0 = time.perf_counter()
    got = run_fleet(engine, num_xpus, num_jobs, sims, seed)
    wall = time.perf_counter() - t0
    for i, (g, r) in enumerate(zip(got["summaries"], ref["summaries"])):
        # JCR, the JCT percentiles and utilization, compared as JSON so
        # that a NaN percentile (no job completed) equals itself.
        _check(json.dumps(g, sort_keys=True) == json.dumps(r, sort_keys=True),
               f"fleet sim {i}: {engine} summary {g} != numpy {r}")
    broker = got["broker"]
    for key in ("engine_failovers", "engine_retries", "canary_mismatches"):
        _check(broker[key] == 0, f"fleet broker {key} = {broker[key]}")
    _check(broker["engine_calls"] >= 1, "fleet broker made no engine call")
    placed = sum(s["num_jobs"] - s["num_dropped"] for s in got["summaries"])
    return {"placed": placed, "jobs": sims * num_jobs, "wall_s": wall,
            "engine_calls": broker["engine_calls"]}


def device_info() -> Dict[str, Any]:
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the traces (default 0)")
    args = ap.parse_args(argv)

    import jax
    device = device_info()
    print(f"# device: {device['kind']} ({device['platform']}) x"
          f"{device['count']}, jax {jax.__version__}", flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU; nothing was run",
              file=sys.stderr)
        return 1
    print(f"# compile cache: {enable_compile_cache()}", flush=True)
    counter = CompileCounter()

    phases = (
        ("served-rfold", lambda: served_phase(
            "rfold", {"num_xpus": PAPER_XPUS, "cube_n": CUBE_N},
            philly_jobs(SERVED_JOBS, args.seed, PAPER_XPUS))),
        ("served-firstfit", lambda: served_phase(
            "firstfit", {"dims": (16, 16, 16)},
            philly_jobs(SERVED_JOBS, args.seed, PAPER_XPUS))),
        ("fleet", lambda: fleet_phase(PAPER_XPUS, FLEET_JOBS, FLEET_SIMS,
                                      args.seed)),
    )
    for name, run in phases:
        before = counter.snapshot()
        res = run()
        after = counter.snapshot()
        print(f"# phase {name}: passed; {res['placed']}/{res['jobs']} jobs "
              f"placed, {res['wall_s']:.3f} s wall on the device engine, "
              f"{after['compiles'] - before['compiles']} compiles "
              f"({after['compile_s'] - before['compile_s']:.3f} s), "
              f"{after['cache_hits'] - before['cache_hits']} cache hits",
              flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
