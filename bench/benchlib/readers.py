"""How each metric is read from a run: from the ops the load generator
timed, the spans, the program's counters, or the reduced trace. The
files under ``bench/metrics/`` each name one of these. A reader that
finds nothing to read returns None and the metric is left out of the
line; none returns 0 for a share of a roofline or a peak.

Op records are ``[op, job_id, due, sent, replied, rid, reply]`` on
``time.perf_counter`` (see ``bench/drivers/loadgen.py``).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

from .stats import percentile
from .work import fitmask_work, roofline_seconds


def _ok(rec) -> bool:
    return rec[4] is not None and isinstance(rec[6], dict) \
        and rec[6].get("ok") is True


def latency_ms(run) -> List[float]:
    """Due time to reply, every op of the window; a failed op is inf."""
    return [(r[4] - r[2]) * 1e3 if _ok(r) else math.inf for r in run.ops]


def op_percentile(run, q: float) -> Optional[float]:
    lat = latency_ms(run)
    return percentile(lat, q) if lat else None


def ops_per_s(run) -> Optional[float]:
    start, close = run.extra.get("start"), run.extra.get("close")
    if start is None or not run.ops:
        return None
    done = sum(1 for r in run.ops if _ok(r) and start <= r[4] <= close)
    return done / (close - start)


def gen_lag_p95_ms(run) -> Optional[float]:
    lag = [(r[3] - r[2]) * 1e3 for r in run.ops if r[3] is not None]
    return percentile(lag, 95) if lag else None


def _window_spans(run) -> Dict[str, Any]:
    """Spans of the window's ops, grouped by request id."""
    rids = {r[5]: r for r in run.ops if _ok(r)}
    by_rid: Dict[str, Dict[str, float]] = {}
    if run.spans is None:
        return {"rids": rids, "by_rid": by_rid}
    for s in run.spans.records:
        if s.rid in rids:
            d = by_rid.setdefault(s.rid, {})
            d[s.name] = d.get(s.name, 0.0) + s.seconds
    return {"rids": rids, "by_rid": by_rid}


def per_op_ms(run, names) -> Optional[float]:
    """Mean milliseconds per window op spent in the named spans."""
    w = _window_spans(run)
    applied = [d for d in w["by_rid"].values() if "bench.apply" in d]
    if not applied:
        return None
    return 1e3 * sum(d.get(n, 0.0) for d in applied for n in names) \
        / len(applied)


def plan_ms_per_op(run) -> Optional[float]:
    """Apply time left after the journal, the snapshot and the engine:
    the allocator core and its plan search."""
    w = _window_spans(run)
    applied = [d for d in w["by_rid"].values() if "bench.apply" in d]
    if not applied:
        return None
    rest = sum(d["bench.apply"] - d.get("bench.wal", 0.0)
               - d.get("bench.snapshot", 0.0) - d.get("bench.engine", 0.0)
               for d in applied)
    return 1e3 * rest / len(applied)


def wire_queue_ms_per_op(run) -> Optional[float]:
    """Client round trip (send to reply) less the op's apply span: the
    client, the wire and the wait in the daemon's loop."""
    w = _window_spans(run)
    gaps = [(w["rids"][rid][4] - w["rids"][rid][3]) - d["bench.apply"]
            for rid, d in w["by_rid"].items() if "bench.apply" in d]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None


def _traced_engine_spans(run):
    t0, t1 = run.extra.get("trace_t0"), run.extra.get("trace_t1")
    if run.spans is None or t0 is None:
        return []
    return [s for s in run.spans.records
            if s.name == "bench.engine" and t0 <= s.t0 and s.t1 <= t1]


def engine_ms_per_job(run) -> Optional[float]:
    jobs = run.extra.get("jobs")
    spans = _traced_engine_spans(run)
    if not jobs or not spans:
        return None
    return 1e3 * sum(s.seconds for s in spans) / jobs


def fitmask_roofline_pct(run) -> Optional[float]:
    """Least time the chip could take for the fitmask work the callers
    asked for in the traced window, over the kernel's device time."""
    if run.trace is None or not run.trace.get("kernel_s"):
        return None
    least = 0.0
    for s in _traced_engine_spans(run):
        if s.kind == "multibox" and s.shape is not None:
            ops, nbytes = fitmask_work(*s.shape)
            least += roofline_seconds(ops, nbytes, run.peaks)[0]
    if least <= 0.0:
        return None
    return 100.0 * least / run.trace["kernel_s"]


def device_idle_pct(run) -> Optional[float]:
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def window_compiles(run) -> Optional[float]:
    return run.counters.get("window_compiles")


def broker_grids_per_call(run) -> Optional[float]:
    b = run.counters.get("broker")
    if not b or not b["engine_calls"]:
        return None
    return b["grids"] / b["engine_calls"]
