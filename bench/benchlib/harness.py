"""One run of one cell, from the command line to the result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration and a traffic mix; the mix names its
driver (``bench/drivers/<driver>.py``), whose ``run(cell)`` sets up,
measures for ``--seconds``, checks what the timed path produced against
the plain reference, and returns a :class:`Run`. The harness then reads
the cell's metrics with their readers (``bench/metrics/<metric>.py``):
the end-to-end ones with ``--trace 0``, the per-layer ones with
``--trace 1``, and prints the result as the last line of standard
output, with each compared number beside its limit on standard error.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import registry
from .device import NoAccelerator, device_info, require_chips


@dataclass
class Cell:
    """What a driver is asked to run."""

    name: str
    config: Dict[str, Any]
    mix: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    chips: int
    t_start: float                  # perf_counter when the process began
    peaks: Dict[str, float] = field(default_factory=dict)
    # Rehearsal and control hooks; a benchmark run leaves them unset.
    engine: Optional[str] = None    # engine in place of the config's
    engine_hook: Optional[Callable[[Any], None]] = None
    core_hook: Optional[Callable[[Any], None]] = None
    rate: Optional[float] = None    # submits/s in place of the mix's
    check: bool = True              # compare with the reference
    workdir: Optional[str] = None   # where the trace is written
    # Seconds from the process's start to the end of each set-up phase.
    marks: Dict[str, float] = field(default_factory=dict)

    def mark(self, phase: str) -> None:
        self.marks[phase] = time.perf_counter() - self.t_start


@dataclass
class Run:
    """What a driver measured and checked."""

    setup_s: float
    window_s: float
    attempted: int
    failed: int
    memory_peak_bytes: int
    ops: List[List[Any]] = field(default_factory=list)
    spans: Any = None               # benchlib.spans.Spans, traced runs
    trace: Optional[Dict[str, Any]] = None   # tracefile.reduce_events
    counters: Dict[str, Any] = field(default_factory=dict)
    checks: List[Tuple[str, float, float]] = field(default_factory=list)
    peaks: Dict[str, float] = field(default_factory=dict)
    extra: Dict[str, Any] = field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return all(v <= lim for _, v, lim in self.checks)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def make_cell(bench: Dict[str, Any], name: str, seed: int, seconds: float,
              trace: bool, t_start: float, root: Path = registry.ROOT,
              **hooks) -> Cell:
    entry = registry.find_cell(bench, name)
    return Cell(name=name, config=registry.load_config(bench, entry["config"],
                                                       root),
                mix=registry.load_mix(entry["traffic"], root / "bench"),
                seed=seed, seconds=seconds, trace=trace,
                chips=int(entry["chips"]), t_start=t_start, **hooks)


def read_metrics(bench: Dict[str, Any], cell: Cell, run: Run,
                 root: Path = registry.ROOT) -> Dict[str, Dict[str, Any]]:
    section = "per_layer" if cell.trace else "end_to_end"
    out: Dict[str, Dict[str, Any]] = {}
    for m in registry.metrics_for(bench, cell.name, section):
        value = registry.load_reader(m["name"], root / "bench")(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(cell: Cell, run: Run, metrics: Dict[str, Dict[str, Any]],
                device: Dict[str, Any]) -> Dict[str, Any]:
    dev = {**device, "memory_peak_bytes": run.memory_peak_bytes}
    line: Dict[str, Any] = {"correct": run.correct,
                            "attempted": run.attempted,
                            "failed": run.failed, "metrics": metrics,
                            "device": dev}
    if cell.trace and run.trace is not None:
        dev["busy_s"] = run.trace["busy_s"]
        dev["window_s"] = run.trace["window_s"]
        line["breakdown"] = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
    line["checks"] = {name: {"value": v, "limit": lim}
                      for name, v, lim in run.checks}
    return line


def _finite(obj: Any) -> Any:
    """JSON has no infinity: an unbounded number is written as null."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_finite(v) for v in obj]
    return obj


def run_cell(cell: Cell, bench: Dict[str, Any], device: Dict[str, Any],
             root: Path = registry.ROOT) -> Tuple[Run, Dict[str, Any]]:
    driver = registry.load_driver(cell.mix["driver"], root / "bench")
    run = driver.run(cell)
    metrics = read_metrics(bench, cell, run, root)
    return run, _finite(result_line(cell, run, metrics, device))


def main(argv=None, t_start: float = 0.0) -> int:
    args = parse_args(argv)
    bench = registry.load_benchmark()
    cell = make_cell(bench, args.workload, args.seed, args.seconds,
                     bool(args.trace), t_start)
    try:
        device = device_info()
        require_chips(device, cell.chips)
        cell.peaks = registry.peaks_for(device["kind"])
    except (NoAccelerator, registry.UnknownName) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell.mark("device")
    run, line = run_cell(cell, bench, device)
    print("set-up phases (s from start): " + ", ".join(
        f"{k} {v:.3f}" for k, v in cell.marks.items()), file=sys.stderr)
    if "check_s" in run.extra:
        print(f"reference check took {run.extra['check_s']:.3f} s",
              file=sys.stderr)
    for name, v, lim in run.checks:
        print(f"check {name}: {v} (limit {lim})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0
