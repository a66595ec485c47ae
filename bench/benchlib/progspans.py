"""Per-layer numbers read from the program's own spans (``repro.obs``).

The program records spans while a JAX profiler session is active, as
it is in a ``--trace 1`` run's window. Every reader here takes the
records that lie inside the traced window (``run.extra["trace_t0"]``
to ``run.extra["trace_t1"]``); the served readers keep only the spans
of the window's ops, matched by request id, as ``readers._window_spans``
does for the benchmark's own spans. A program without ``repro.obs``
records nothing, and every reader then returns None.

Program spans read here (the program's DESIGN.md lists their tags):

* ``daemon.op``: one op on the daemon's loop, from its line arriving to
  its reply drained;
* ``core.apply`` > ``plan.search``: the allocator core's op and each
  placement attempt it makes (``try_place``), backfill included;
* ``wal.append`` > ``wal.fsync``; ``wal.snapshot``: the journal;
* ``engine.call`` > ``engine.launch``, ``engine.wait``,
  ``engine.fetch``: one fitmask device call;
* ``broker.wait`` > ``broker.flush``: a simulator parked in the fleet
  broker, and the rounds it leads meanwhile.
"""
from __future__ import annotations

import time
from collections import defaultdict
from typing import Any, Dict, Iterable, List, Optional, Sequence

MONOTONIC = "clock_gettime(CLOCK_MONOTONIC)"


def _records(run) -> List[Any]:
    try:
        from repro import obs
    except ImportError:
        return []
    t0, t1 = run.extra.get("trace_t0"), run.extra.get("trace_t1")
    if t0 is None or t1 is None:
        return []
    recs = obs.records()
    if len(recs) >= obs.CAPACITY and recs[0].t0 > t0:
        return []   # the log dropped part of the window: no partial sums
    return [r for r in recs if t0 <= r.t0 and r.t1 <= t1]


def _ok(op) -> bool:
    return op[4] is not None and isinstance(op[6], dict) \
        and op[6].get("ok") is True


def _served(run) -> Dict[str, List[Any]]:
    """Records of the window's answered ops that the core applied, by
    request id."""
    rids = {op[5] for op in run.ops if _ok(op)}
    by_rid: Dict[str, List[Any]] = defaultdict(list)
    for r in _records(run):
        if r.rid in rids:
            by_rid[r.rid].append(r)
    return {rid: recs for rid, recs in by_rid.items()
            if any(r.name == "core.apply" for r in recs)}


def _by_sid(records: Iterable[Any]) -> Dict[int, Any]:
    return {r.sid: r for r in records}


def served_ms_per_op(run, name: str,
                     parent: Optional[str] = None) -> Optional[float]:
    """Mean milliseconds per applied window op in spans called
    ``name`` (only those directly under a ``parent`` span, if given)."""
    ops = _served(run)
    if not ops:
        return None
    total = 0.0
    for recs in ops.values():
        sids = _by_sid(recs)
        for r in recs:
            if r.name != name:
                continue
            if parent is not None and (
                    r.parent not in sids or sids[r.parent].name != parent):
                continue
            total += r.seconds
    return 1e3 * total / len(ops)


def served_count_per_op(run, name: str) -> Optional[float]:
    """Mean number of spans called ``name`` per applied window op."""
    ops = _served(run)
    if not ops:
        return None
    n = sum(1 for recs in ops.values() for r in recs if r.name == name)
    return n / len(ops)


def loop_wait_ms_per_op(run) -> Optional[float]:
    """Mean time from the load generator's send to the daemon's loop
    taking the op up (``daemon.op`` opening): the wire and the wait
    behind other ops on the loop. The two processes share the clock
    only where ``perf_counter`` is ``CLOCK_MONOTONIC``."""
    if time.get_clock_info("perf_counter").implementation != MONOTONIC:
        return None
    sent = {op[5]: op[3] for op in run.ops if _ok(op)}
    ops = _served(run)
    waits = []
    for rid, recs in ops.items():
        starts = [r.t0 for r in recs if r.name == "daemon.op"]
        if starts:
            waits.append(min(starts) - sent[rid])
    return 1e3 * sum(waits) / len(waits) if waits else None


def _whatif(run) -> Optional[List[Any]]:
    """The window's records, or None when there are none or no jobs."""
    recs = _records(run)
    if not recs or not run.extra.get("jobs"):
        return None
    return recs


def whatif_ms_per_job(run, name: str) -> Optional[float]:
    """Milliseconds per simulated job in spans called ``name``."""
    recs = _whatif(run)
    if recs is None:
        return None
    total = sum(r.seconds for r in recs if r.name == name)
    return 1e3 * total / run.extra["jobs"]


def self_ms_per_job(run, name: str,
                    less: Sequence[str]) -> Optional[float]:
    """Milliseconds per simulated job in spans called ``name``, less
    the spans called one of ``less`` under them at any depth (the
    outermost of those only, so none is taken off twice)."""
    recs = _whatif(run)
    if recs is None:
        return None
    sids = _by_sid(recs)
    total = sum(r.seconds for r in recs if r.name == name)
    for r in recs:
        if r.name not in less:
            continue
        up = sids.get(r.parent)
        while up is not None and up.name != name and up.name not in less:
            up = sids.get(up.parent)
        if up is not None and up.name == name:
            total -= r.seconds
    return 1e3 * total / run.extra["jobs"]
