"""Served cells: the scheduler daemon on the chip under open-loop load.

This process holds the chip. It starts the daemon in-process through
``repro.api.Scheduler`` with the configuration's engine (``pallas``), a
fsynced write-ahead log in a temporary directory, and backfill as the
configuration states. The load comes from ``loadgen.py`` in a child
process that never imports JAX and talks to the daemon over loopback
TCP, as remote users do.

Set-up: import, engine warm-up, daemon start, and the child's prefill
to steady occupancy. Window: submits at ``knee_fraction`` of the
configuration's knee, dones as placed jobs finish, for ``--seconds``.
Then, with the profiler stopped and the device's peak memory read:

* the daemon's state digest is read, and the daemon is killed (no
  final snapshot), so recovery has to come from snapshot + WAL;
* the plain reference (``benchlib.plainsched``, which shares no code
  with the program) reads the journal the daemon left on disk
  (snapshot, then the write-ahead log's tail) and replays it from an
  empty pod with its own placement, admission and fitmask;
* ``acked_ops_lost``: acknowledged ops missing from that journal;
  ``reply_mismatches``: replies (set-up and window) that differ from
  the reference's reply to the same request; ``digest_mismatch``: 1
  when the daemon's state digest differs from the one the reference
  computes over its own final state (occupancy, allocated jobs and
  their shapes, queue, next id); ``error_replies``: ops the daemon
  refused. Each has the limit 0.
"""
from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List

from benchlib import philly, plainsched, tracefile
from benchlib.device import CompileCounter, memory_peak_bytes
from benchlib.harness import Cell, Run
from benchlib.reference import canonical
from benchlib.spans import (Spans, instrument_core, instrument_engine,
                            restore_engine)
from benchlib.warmup import warm

HERE = os.path.dirname(os.path.abspath(__file__))
LOADGEN = os.path.join(HERE, "loadgen.py")


def _scheduler_config(cell: Cell, engine: str, ckpt: str):
    from repro import api
    c = cell.config
    return api.SchedulerConfig(policy=c["policy"],
                               policy_kw=dict(c["policy_kw"]),
                               backfill=c["served"]["backfill"],
                               engine=engine, checkpoint_dir=ckpt,
                               fsync=c["served"]["fsync"],
                               ack_mode=c["served"]["ack_mode"])


def submit_rate(cell: Cell) -> float:
    if cell.rate is not None:
        return float(cell.rate)
    return cell.config["served"]["knee_submits_per_s"] * \
        cell.mix["knee_fraction"]


def loadgen_params(cell: Cell, address) -> Dict[str, Any]:
    mix = cell.mix
    rate = submit_rate(cell)
    p = {**mix["philly"], "cluster_xpus": cell.config["num_xpus"],
         "size_max": cell.config["num_xpus"]}
    num_jobs = mix["prefill_jobs"] + int(math.ceil(rate * cell.seconds
                                                   * 1.5)) + 16
    return {"address": list(address), "philly": p, "num_jobs": num_jobs,
            "seed": cell.seed, "prefill_jobs": mix["prefill_jobs"],
            "order": mix.get("order", "seeded"),
            "connections": mix["connections"],
            "compression": philly.mean_gap(p, num_jobs) * rate,
            "window_s": cell.seconds, "grace_s": mix["grace_s"],
            "lead_s": mix["lead_s"], "op_timeout_s": mix["op_timeout_s"]}


def _child_env() -> Dict[str, str]:
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    return env


def _read_event(child: subprocess.Popen, event: str) -> Dict[str, Any]:
    line = child.stdout.readline()
    if not line:
        raise RuntimeError(f"load generator exited before {event!r}: "
                           f"{child.stderr.read()[-2000:]}")
    msg = json.loads(line)
    if msg.get("event") != event:
        raise RuntimeError(f"load generator said {msg!r}, not {event!r}")
    return msg


def _ok(reply) -> bool:
    return isinstance(reply, dict) and reply.get("ok") is True


def check(cell: Cell, ckpt: str, digest: str, ops: List[List[Any]],
          prefill: List[List[Any]]) -> List[tuple]:
    """The daemon's journal, as left on disk in ``ckpt``, replayed
    through the plain reference; see the module docstring."""
    c = cell.config
    ref = plainsched.PlainServed(c["policy"], dict(c["policy_kw"]),
                                 c["served"]["backfill"])
    want: Dict[str, Dict[str, Any]] = {}
    for op in plainsched.read_journal(ckpt):
        reply = ref.apply(op)
        if op.get("rid") is not None:
            want[op["rid"]] = reply
    acked = [o for o in prefill + ops if _ok(o[6])]
    lost = sum(1 for o in acked if o[5] not in want)
    mismatched = sum(1 for o in acked
                     if canonical(want.get(o[5])) != canonical(o[6]))
    refused = sum(1 for o in prefill + ops
                  if isinstance(o[6], dict) and o[6].get("ok") is False)
    return [("acked_ops_lost", lost, 0),
            ("reply_mismatches", mismatched, 0),
            ("digest_mismatch", int(ref.digest() != digest), 0),
            ("error_replies", refused, 0)]


def run(cell: Cell) -> Run:
    from jax.profiler import TraceAnnotation
    from repro import api
    from repro.kernels.fitmask import ops as fitmask_ops

    engine_name = cell.engine or cell.config["engine"]
    engine = fitmask_ops.get_engine(engine_name)
    warm(engine, cell.config["warm"]["served"])
    cell.mark("warm")
    spans = Spans() if cell.trace else None
    if spans is not None:
        instrument_engine(engine, spans)
    if cell.engine_hook is not None:
        cell.engine_hook(engine)
    counter = CompileCounter()
    ckpt = tempfile.mkdtemp(prefix="bench_wal_")
    workdir = cell.workdir or tempfile.mkdtemp(prefix="bench_trace_")
    sched = None
    child = None
    try:
        sconf = _scheduler_config(cell, engine_name, ckpt)
        sched = api.Scheduler(sconf).start()
        cell.mark("daemon")
        core = sched._daemon.core
        if spans is not None:
            instrument_core(core, spans)
        if cell.core_hook is not None:
            cell.core_hook(core)
        child = subprocess.Popen(
            [sys.executable, LOADGEN], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=_child_env())
        child.stdin.write(json.dumps(loadgen_params(cell, sched.address))
                          + "\n")
        child.stdin.flush()
        _read_event(child, "ready")
        cell.mark("prefill")
        before = counter.compiles
        if cell.trace:
            tracefile.start(workdir)
        setup_s = time.perf_counter() - cell.t_start
        with TraceAnnotation(tracefile.WINDOW):
            trace_t0 = time.perf_counter()
            child.stdin.write("go\n")
            child.stdin.flush()
            done = _read_event(child, "done")
            trace_t1 = time.perf_counter()
        if cell.trace:
            tracefile.stop()
        after = counter.compiles
        child.stdin.close()
        child.wait(timeout=60)
        status = sched.client.status()
        mem = memory_peak_bytes(cell.chips)
        sched.kill()
        sched = None
        ops = done["ops"]
        failed = sum(1 for o in ops if o[4] is None or not _ok(o[6]))
        run = Run(setup_s=setup_s, window_s=cell.seconds,
                  attempted=len(ops), failed=failed,
                  memory_peak_bytes=mem, ops=ops, spans=spans,
                  peaks=cell.peaks,
                  extra={"start": done["start"], "close": done["close"],
                         "trace_t0": trace_t0, "trace_t1": trace_t1})
        run.counters["window_compiles"] = after - before
        if cell.trace:
            run.trace = tracefile.reduce_events(tracefile.load(workdir))
        if cell.check:
            t0 = time.perf_counter()
            run.checks = check(cell, ckpt, status["state_digest"], ops,
                               done["prefill"])
            run.extra["check_s"] = time.perf_counter() - t0
        return run
    finally:
        restore_engine(engine)
        if sched is not None:
            sched.kill()
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        shutil.rmtree(ckpt, ignore_errors=True)
        if cell.workdir is None:
            shutil.rmtree(workdir, ignore_errors=True)
