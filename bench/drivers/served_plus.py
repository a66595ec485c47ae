"""Served cells beyond ``served.py``: the same daemon, load generator and
plain reference, with a standby replica, or with another arrival
process.

A mix names its load generator (``loadgen``: ``loadgen`` or
``loadgen_burst``, a module beside this one); a configuration whose
``served.standby`` is set runs the primary's daemon with that standby
in a process of its own (``standby.py``), on its own engine, tailing
the primary's journal over loopback TCP.

Set-up: import, the primary's daemon, then the standby, which must
follow within :data:`FOLLOW_S` seconds (the primary's ``status`` shows
``repl.follower_live`` and the standby's shows no ``repl.last_error``),
else the run stops with that error before anything is warmed; then
engine warm-up and the child's prefill. Window: as ``served.py``.

Then, with the profiler stopped: the primary's state digest and
replication counters are read, the device's peak memory, and the
primary is killed with no final snapshot. With a standby, it is
promoted; ``failover_s`` (in ``run.extra`` and on standard error, not
a metric) is the time from the kill to the promoted standby's first
reply. Its state digest is read and its process killed. The plain reference
(``benchlib.plainsched``) replays the primary's journal as left on
disk, and compares as ``served.check`` does (``acked_ops_lost``,
``reply_mismatches``, ``digest_mismatch``, ``error_replies``), with the
``replicated`` field of each reply left out. With a standby, also:

* ``degraded_acks``: acknowledged replies whose ``replicated`` is not
  true, plus the primary's ``sync_timeouts``;
* ``acked_ops_lost_standby``: acknowledged ops missing from the journal
  the standby left on disk;
* ``standby_digest_mismatch``: 1 when the promoted standby's state
  digest differs from the reference's.

Every check has the limit 0.
"""
from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

from benchlib import plainsched, registry, tracefile
from benchlib.device import CompileCounter, memory_peak_bytes
from benchlib.harness import Cell, Run
from benchlib.reference import canonical
from benchlib.spans import (Spans, instrument_core, instrument_engine,
                            restore_engine)
from benchlib.warmup import warm

served = registry.load_driver("served")

HERE = os.path.dirname(os.path.abspath(__file__))
STANDBY = os.path.join(HERE, "standby.py")
FOLLOW_S = 10.0           # the standby's time to follow, in set-up
STANDBY_OP_TIMEOUT_S = 30.0
# Faults the tests plant: the standby stopped (SIGSTOP) through the
# window, or acknowledging records it has not written.
PLANTS = ("pause_standby", "standby_lost_wal")


class Standby:
    """The standby replica's process and a client connection to it."""

    def __init__(self, cell: Cell, primary, plant: Optional[str]):
        from repro.serve.scheduler.client import SchedulerClient
        c = cell.config
        spec = c["served"]["standby"]
        self.dir = tempfile.mkdtemp(prefix="bench_standby_")
        self.client = None
        self.proc = subprocess.Popen(
            [sys.executable, STANDBY], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env={**served._child_env(), "JAX_PLATFORMS": "cpu"})
        self.proc.stdin.write(json.dumps({
            "address": list(primary), "policy": c["policy"],
            "policy_kw": c["policy_kw"],
            "backfill": c["served"]["backfill"], "engine": spec["engine"],
            "fsync": spec["fsync"], "checkpoint_dir": self.dir,
            "plant": "lost_wal" if plant == "standby_lost_wal" else None})
            + "\n")
        self.proc.stdin.flush()
        try:
            ready = served._read_event(self.proc, "ready")
            self.client = SchedulerClient(tuple(ready["address"]),
                                          op_timeout=STANDBY_OP_TIMEOUT_S)
        except BaseException:
            self.close()
            shutil.rmtree(self.dir, ignore_errors=True)
            raise

    def await_following(self, primary_client, seconds: float) -> None:
        """Return once the standby follows; raise with the standby's
        error if it has one, or after ``seconds``."""
        deadline = time.monotonic() + seconds
        while True:
            error = self.client.status()["repl"]["last_error"]
            if error is not None:
                raise RuntimeError(f"the standby does not follow the "
                                   f"primary: {error}")
            if primary_client.status()["repl"]["follower_live"]:
                return
            if time.monotonic() > deadline:
                raise RuntimeError(f"the standby did not pull from the "
                                   f"primary within {seconds} s")
            time.sleep(0.05)

    def pause(self) -> None:
        self.proc.send_signal(signal.SIGSTOP)

    def resume(self) -> None:
        self.proc.send_signal(signal.SIGCONT)

    def close(self) -> None:
        """Kill the process (no final snapshot) and close the client;
        the journal it left stays in ``self.dir``."""
        if self.client is not None:
            self.client.close()
            self.client = None
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()


def loadgen_path(cell: Cell) -> str:
    return os.path.join(HERE, cell.mix.get("loadgen", "loadgen") + ".py")


def loadgen_params(cell: Cell, address) -> Dict[str, Any]:
    params = served.loadgen_params(cell, address)
    if "arrival_burstiness" in cell.mix:
        params["arrival_burstiness"] = cell.mix["arrival_burstiness"]
    return params


def _unreplicated(op: List[Any]) -> List[Any]:
    reply = op[6]
    if isinstance(reply, dict):
        reply = {k: v for k, v in reply.items() if k != "replicated"}
    return op[:6] + [reply]


def check(cell: Cell, ckpt: str, digest: str, ops: List[List[Any]],
          prefill: List[List[Any]],
          replica: Optional[Dict[str, Any]] = None) -> List[tuple]:
    """The primary's journal, as left on disk in ``ckpt``, replayed
    through the plain reference; with a standby (``replica``: its
    journal's ``dir``, its promoted ``digest`` and the primary's
    ``sync_timeouts``) the replication checks too. See the module
    docstring."""
    c = cell.config
    ref = plainsched.PlainServed(c["policy"], dict(c["policy_kw"]),
                                 c["served"]["backfill"])
    want: Dict[str, Dict[str, Any]] = {}
    for op in plainsched.read_journal(ckpt):
        reply = ref.apply(op)
        if op.get("rid") is not None:
            want[op["rid"]] = reply
    every = prefill + ops
    acked = [o for o in every if served._ok(o[6])]
    bare = [_unreplicated(o) for o in acked]
    checks = [
        ("acked_ops_lost", sum(1 for o in acked if o[5] not in want), 0),
        ("reply_mismatches",
         sum(1 for o in bare if canonical(want.get(o[5])) != canonical(o[6])),
         0),
        ("digest_mismatch", int(ref.digest() != digest), 0),
        ("error_replies", sum(1 for o in every if isinstance(o[6], dict)
                              and o[6].get("ok") is False), 0)]
    if replica is None:
        return checks
    on_standby = {op.get("rid") for op in
                  plainsched.read_journal(replica["dir"])}
    degraded = sum(1 for o in acked if o[6].get("replicated") is not True)
    return checks + [
        ("degraded_acks", degraded + replica["sync_timeouts"], 0),
        ("acked_ops_lost_standby",
         sum(1 for o in acked if o[5] not in on_standby), 0),
        ("standby_digest_mismatch", int(ref.digest() != replica["digest"]),
         0)]


def run(cell: Cell, plant: Optional[str] = None) -> Run:
    from jax.profiler import TraceAnnotation
    from repro import api
    from repro.kernels.fitmask import ops as fitmask_ops

    if plant is not None and plant not in PLANTS:
        raise ValueError(f"no plant {plant!r}; have {PLANTS}")
    engine_name = cell.engine or cell.config["engine"]
    engine = fitmask_ops.get_engine(engine_name)
    ckpt = tempfile.mkdtemp(prefix="bench_wal_")
    workdir = cell.workdir or tempfile.mkdtemp(prefix="bench_trace_")
    sched = standby = child = None
    spans = None
    try:
        sched = api.Scheduler(
            served._scheduler_config(cell, engine_name, ckpt)).start()
        cell.mark("daemon")
        if cell.config["served"].get("standby") is not None:
            standby = Standby(cell, sched.address, plant)
            standby.await_following(sched.client, FOLLOW_S)
            cell.mark("standby")
        warm(engine, cell.config["warm"]["served"])
        cell.mark("warm")
        if cell.trace:
            spans = Spans()
            instrument_engine(engine, spans)
        if cell.engine_hook is not None:
            cell.engine_hook(engine)
        counter = CompileCounter()
        core = sched._daemon.core
        if spans is not None:
            instrument_core(core, spans)
        if cell.core_hook is not None:
            cell.core_hook(core)
        child = subprocess.Popen(
            [sys.executable, loadgen_path(cell)], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=served._child_env())
        child.stdin.write(json.dumps(loadgen_params(cell, sched.address))
                          + "\n")
        child.stdin.flush()
        served._read_event(child, "ready")
        cell.mark("prefill")
        if plant == "pause_standby":
            standby.pause()
        before = counter.compiles
        if cell.trace:
            tracefile.start(workdir)
        setup_s = time.perf_counter() - cell.t_start
        with TraceAnnotation(tracefile.WINDOW):
            trace_t0 = time.perf_counter()
            child.stdin.write("go\n")
            child.stdin.flush()
            done = served._read_event(child, "done")
            trace_t1 = time.perf_counter()
        if cell.trace:
            tracefile.stop()
        after = counter.compiles
        child.stdin.close()
        child.wait(timeout=60)
        status = sched.client.status()
        mem = memory_peak_bytes(cell.chips)
        t_kill = time.perf_counter()
        sched.kill()
        sched = None
        replica = None
        extra: Dict[str, Any] = {}
        if standby is not None:
            if plant == "pause_standby":
                standby.resume()
            standby.client.call("promote")
            extra["failover_s"] = time.perf_counter() - t_kill
            print(f"failover took {extra['failover_s']:.6f} s",
                  file=sys.stderr)
            replica = {"dir": standby.dir,
                       "digest": standby.client.status()["state_digest"],
                       "sync_timeouts": status["repl"]["sync_timeouts"]}
            standby.close()
        ops = done["ops"]
        failed = sum(1 for o in ops if o[4] is None or not served._ok(o[6]))
        run = Run(setup_s=setup_s, window_s=cell.seconds,
                  attempted=len(ops), failed=failed,
                  memory_peak_bytes=mem, ops=ops, spans=spans,
                  peaks=cell.peaks,
                  extra={"start": done["start"], "close": done["close"],
                         "trace_t0": trace_t0, "trace_t1": trace_t1,
                         **extra})
        run.counters["window_compiles"] = after - before
        if cell.trace:
            run.trace = tracefile.reduce_events(tracefile.load(workdir))
        if cell.check:
            t0 = time.perf_counter()
            run.checks = check(cell, ckpt, status["state_digest"], ops,
                               done["prefill"], replica)
            run.extra["check_s"] = time.perf_counter() - t0
        return run
    finally:
        restore_engine(engine)
        if sched is not None:
            sched.kill()
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        if standby is not None:
            standby.close()
            shutil.rmtree(standby.dir, ignore_errors=True)
        shutil.rmtree(ckpt, ignore_errors=True)
        if cell.workdir is None:
            shutil.rmtree(workdir, ignore_errors=True)
