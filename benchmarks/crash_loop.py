"""Crash-loop drill: the daemon dies 5 times mid-stream, the state
doesn't.

A deterministic op stream is derived from the ``node_churn`` chaos
scenario — the trace's submits, a retire-every-3rd ``done`` rule, and
the scenario's seeded fault/repair schedule, merged in time order —
and replayed against the allocator daemon twice:

* **Control run**: uninterrupted; the final ``state_digest`` is the
  oracle.
* **Crash run**: at 5 seeded points the daemon is ``kill``-ed (no
  final checkpoint — recovery is snapshot + WAL tail replay), a fresh
  daemon recovers on the same checkpoint dir, and the op that was in
  flight at the kill is **resent with its original request_id** — the
  journal-persisted dedup cache must absorb the retry (the state
  digest must not move), exactly what a reconnecting client does.

Pass criterion: the crash run's final digest and journal length are
byte-identical to the control run's, every resend was a no-op, and at
least one resend was answered from the dedup cache. The resilience
counters (dedup/lease/WAL) land in the JSON artifact for
``benchmarks/report.py``.

  PYTHONPATH=src python -m benchmarks.crash_loop [--kills 5] \
      [--out BENCH_crash_loop.json]
"""
from __future__ import annotations

import argparse
import json
import random
import shutil
import tempfile
import time
from typing import Dict, List, Optional, Tuple

from repro.api import (SCENARIOS, Scheduler, SchedulerClient,
                       SchedulerConfig, TraceConfig, fault_schedule,
                       generate_trace, make_policy)
from repro.serve.scheduler import protocol
from repro.compile_cache import enable_compile_cache

POLICY_KW = dict(num_xpus=512, cube_n=4)


def build_op_stream(num_jobs: int, seed: int,
                    scenario: str = "node_churn") -> List[Dict]:
    """The deterministic op list both runs replay: submits at arrival,
    a ``done`` for the oldest-submitted job after every 3rd submit
    (already-finished/dropped targets answer a stateless error —
    deterministic either way), and the scenario's fault/repair events
    at their scheduled times."""
    sc = SCENARIOS[scenario]
    cfg = TraceConfig(num_jobs=num_jobs, seed=seed, cluster_xpus=512,
                      size_max=512, **sc.trace_kw)
    jobs = generate_trace(cfg)
    model = make_policy("rfold", **POLICY_KW).cluster
    faults = fault_schedule(sc, model, jobs, seed)

    timeline: List[Tuple[float, int, Dict]] = []
    fifo: List[int] = []
    for n, job in enumerate(jobs, start=1):
        timeline.append((job.arrival, len(timeline),
                         {"op": "submit", "job_id": job.job_id,
                          "shape": list(job.shape.dims)}))
        fifo.append(job.job_id)
        if n % 3 == 0:
            timeline.append((job.arrival, len(timeline),
                             {"op": "done", "job_id": fifo.pop(0)}))
    for ev in faults:
        timeline.append((ev.time, len(timeline),
                         {"op": ev.action, "kind": ev.kind,
                          "targets": [list(t) if isinstance(t, tuple)
                                      else t for t in ev.targets]}))
    timeline.sort(key=lambda e: (e[0], e[1]))
    return [msg for _, _, msg in timeline]


class _RawClient:
    """Fixed-identity wire driver: op ``i`` always goes out as
    ``request_id crash:<i>`` — across daemon restarts too — so a
    resend after a crash is the genuine idempotent-retry path."""

    def __init__(self, address):
        self._c = SchedulerClient(address, client_id="crash",
                                  max_retries=0)

    def send(self, i: int, msg: Dict) -> Dict:
        wire = dict(msg, seq=i, client="crash",
                    request_id=f"crash:{i}")
        self._c._sock.sendall(protocol.encode(wire))
        return self._c._await_reply(i, 60.0)

    def close(self) -> None:
        self._c.close()


def _run_stream(ops: List[Dict], ckpt_dir: str,
                kill_at: Optional[List[int]] = None) -> Dict:
    """Replay ``ops`` against a daemon on ``ckpt_dir``; with
    ``kill_at``, crash + recover + resend-at-same-rid at those op
    indices. Returns the final digest/journal plus drill stats."""
    cfg = SchedulerConfig(policy="rfold", policy_kw=dict(POLICY_KW),
                          checkpoint_dir=ckpt_dir, checkpoint_every=7)
    kill_at = sorted(kill_at or [])
    sched = Scheduler(cfg).start()
    client = _RawClient(sched.address)
    resends_clean = True
    try:
        for i, msg in enumerate(ops):
            reply = client.send(i, msg)
            if kill_at and i == kill_at[0]:
                kill_at.pop(0)
                client.close()
                sched.kill()  # crash: no final checkpoint
                sched = Scheduler(cfg).start()
                client = _RawClient(sched.address)
                # The retry a real client would issue after losing the
                # ack: same request_id. Journaled ops must dedup;
                # either way the state digest must not move.
                before = client.send(10_000_000 + i, {"op": "status"})
                client.send(i, msg)
                after = client.send(20_000_000 + i, {"op": "status"})
                resends_clean &= (before["state_digest"]
                                  == after["state_digest"])
        st = client.send(len(ops), {"op": "status"})
        return {"digest": st["state_digest"],
                "journal_ops": st["journal_ops"],
                "resilience": st["resilience"],
                "resends_clean": resends_clean}
    finally:
        client.close()
        sched.stop()


def run_drill(num_jobs: int, seed: int, kills: int) -> Dict:
    ops = build_op_stream(num_jobs, seed)
    # Kill only right after submits: submits journal (unless rejected),
    # so the resent op exercises the dedup cache, not just statelessness.
    submit_idx = [i for i, m in enumerate(ops) if m["op"] == "submit"]
    kill_at = sorted(random.Random(seed).sample(
        submit_idx[1:], min(kills, max(0, len(submit_idx) - 1))))

    tmp = tempfile.mkdtemp(prefix="crash_loop_")
    try:
        t0 = time.perf_counter()
        control = _run_stream(ops, tmp + "/control")
        crash = _run_stream(ops, tmp + "/crash", kill_at=kill_at)
        wall = time.perf_counter() - t0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    identical = (control["digest"] == crash["digest"]
                 and control["journal_ops"] == crash["journal_ops"])
    return {
        "ops": len(ops), "num_jobs": num_jobs, "seed": seed,
        "kills": kill_at,
        "control": control, "crash": crash,
        "identical": identical,
        "wall_s": round(wall, 3),
        "pass": (identical and crash["resends_clean"]
                 and crash["resilience"]["dedup_hits"] >= 1),
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-jobs", type=int, default=60)
    ap.add_argument("--seed", type=int, default=17)
    ap.add_argument("--kills", type=int, default=5)
    ap.add_argument("--quick", action="store_true",
                    help="smaller stream for CI smoke")
    ap.add_argument("--out", default="BENCH_crash_loop.json")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.quick:
        args.num_jobs = min(args.num_jobs, 36)
        args.kills = min(args.kills, 3)

    res = run_drill(args.num_jobs, args.seed, args.kills)
    print(f"# crash loop: {res['ops']} ops, kills at {res['kills']}")
    print(f"  control digest {res['control']['digest'][:16]}... "
          f"({res['control']['journal_ops']} journal ops)")
    print(f"  crash   digest {res['crash']['digest'][:16]}... "
          f"({res['crash']['journal_ops']} journal ops, "
          f"recovered {res['crash']['resilience']['recovered_ops']} at "
          f"last boot, {res['crash']['resilience']['dedup_hits']} dedup "
          f"hits, wal tail {res['crash']['resilience']['wal_tail_ops']})")
    print(f"# identical={res['identical']} "
          f"resends_clean={res['crash']['resends_clean']} "
          f"pass={res['pass']} ({res['wall_s']}s)")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)
        print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
