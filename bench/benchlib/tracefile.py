"""The profiler's trace of a window, reduced to device numbers.

:func:`start` and :func:`stop` bracket the window with JAX's profiler
(host annotations kept, the Python call tracer off). :func:`load`
turns the ``.xplane.pb`` it writes into plain event lists, and
:func:`reduce_events` computes, from those lists alone:

* ``busy_s``: the union of the intervals in which an operation ran on
  a device, averaged over the devices used;
* ``window_s``: the length of the ``bench.window`` annotation;
* ``kernel_s``: the summed device time of the fitmask kernel's events;
* ``device_ops``: device operations by total time, each named by
  :func:`op_name`;
* ``idle_gaps``: the device's idle time inside the window, by what the
  host was doing (the innermost ``bench.*`` annotation that covers
  the middle of each gap, else ``host.other``).

Events are ``[name, start_ns, duration_ns]`` in the trace's one time
base. The reduction is checked on a recorded trace in
``bench/tests/fixtures``.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

WINDOW = "bench.window"
# Names of the fitmask kernel's events on the device (the Pallas
# kernel body and the jitted wrapper that holds it).
KERNEL_PATTERN = re.compile(r"fitmask_multibox", re.IGNORECASE)
# Device lines whose events are operations (not whole programs or steps).
OP_LINES = ("XLA Ops",)

Event = Tuple[str, float, float]


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def load(log_dir: str) -> Dict[str, Any]:
    """``{"devices": {plane: {line: [events]}}, "host": [events]}``;
    host events are the ``bench.*`` annotations only."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no trace under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                lines[line.name] = [[e.name, float(e.start_ns),
                                     float(e.duration_ns)]
                                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"devices": devices, "host": host}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _op_events(lines: Dict[str, List[Event]]) -> List[Event]:
    for name in OP_LINES:
        if name in lines:
            return lines[name]
    return []


def op_name(name: str) -> str:
    """A device op's HLO text shortened to its name and result shape:
    ``%_fitmask_multibox.1 = s32[64,64,4,4,4]{...} custom-call(...)``
    becomes ``_fitmask_multibox.1 s32[64,64,4,4,4]``."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    return f"{lhs.lstrip('%')} {rhs.split('{', 1)[0].split(' ', 1)[0]}"


def reduce_events(events: Dict[str, Any], top: int = 10) -> Optional[Dict[str, Any]]:
    """Device numbers of the traced window, or None when the trace holds
    no window or no device operation."""
    windows = [e for e in events["host"] if e[0] == WINDOW]
    if not windows:
        return None
    _, w0, wdur = max(windows, key=lambda e: e[2])
    w1 = w0 + wdur
    busy_ns: List[float] = []
    kernel_ns = 0.0
    kernel_events = 0
    by_op: Dict[str, float] = defaultdict(float)
    merged: List[Tuple[float, float]] = []
    for lines in events["devices"].values():
        ops = [e for e in _op_events(lines) if e[1] < w1 and e[1] + e[2] > w0]
        if not ops:
            continue
        spans = _clip(_union([(s, s + d) for _, s, d in ops]), w0, w1)
        busy_ns.append(sum(e - s for s, e in spans))
        merged.extend(spans)
        for name, s, d in ops:
            by_op[op_name(name)] += d
            if KERNEL_PATTERN.search(name):
                kernel_ns += d
                kernel_events += 1
    if not busy_ns:
        return None
    gaps = []
    cursor = w0
    for s, e in _union(merged):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < w1:
        gaps.append((cursor, w1))
    host = sorted((s, s + d, name) for name, s, d in events["host"]
                  if name != WINDOW)
    by_activity: Dict[str, float] = defaultdict(float)
    active: List[Tuple[float, float, str]] = []
    nxt = 0
    for s, e in gaps:                      # gaps are in time order
        mid = (s + e) / 2.0
        while nxt < len(host) and host[nxt][0] <= mid:
            active.append(host[nxt])
            nxt += 1
        active = [h for h in active if h[1] >= mid]
        # The innermost annotation is the one that started last.
        label = max(active)[2] if active else "host.other"
        by_activity[label] += (e - s) / 1e9
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": wdur / 1e9,
        "kernel_s": kernel_ns / 1e9,
        "kernel_events": kernel_events,
        "device_ops": sorted(([n, t / 1e9] for n, t in by_op.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(([n, t] for n, t in by_activity.items()),
                            key=lambda x: -x[1])[:top],
    }
