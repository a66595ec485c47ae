"""Everything ``BENCHMARK.json`` names, found by its name.

A cell is a configuration plus a traffic mix. Each piece lives in a
file of its own, so a later change adds a file and an entry and edits
nothing that is there:

* configuration: the ``file`` its entry in ``BENCHMARK.json`` names
  (``bench/configs/<config>.json``);
* traffic mix: ``bench/traffic/<mix>.json``, which names its driver
  module ``bench/drivers/<driver>.py``;
* metric: ``bench/metrics/<metric>.py``, a module with
  ``read(run) -> float | None``;
* chip peaks: ``bench/peaks.json``, keyed by ``device_kind``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


class UnknownName(KeyError):
    """A cell, configuration, mix, metric or device that is not there."""


def _read_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return _read_json(root / "BENCHMARK.json")


def _by_name(entries: List[Dict[str, Any]], name: str,
             what: str) -> Dict[str, Any]:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise UnknownName(f"no {what} named {name!r}; have "
                      f"{sorted(e['name'] for e in entries)}")


def find_cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    return _by_name(bench["workloads"], name, "workload")


def load_config(bench: Dict[str, Any], name: str,
                root: Path = ROOT) -> Dict[str, Any]:
    entry = _by_name(bench["configs"], name, "configuration")
    return _read_json(root / entry["file"])


def load_mix(name: str, bench_dir: Path = BENCH) -> Dict[str, Any]:
    path = bench_dir / "traffic" / f"{name}.json"
    if not path.exists():
        raise UnknownName(f"no traffic mix file {path}")
    return _read_json(path)


def _load_module(path: Path, what: str) -> ModuleType:
    if not path.exists():
        raise UnknownName(f"no {what} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{what}_{path.stem.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_driver(name: str, bench_dir: Path = BENCH) -> ModuleType:
    return _load_module(bench_dir / "drivers" / f"{name}.py", "driver")


def load_reader(metric: str,
                bench_dir: Path = BENCH) -> Callable[[Any], Optional[float]]:
    return _load_module(bench_dir / "metrics" / f"{metric}.py",
                        "metric").read


def metrics_for(bench: Dict[str, Any], cell: str,
                section: str) -> List[Dict[str, Any]]:
    """The metrics of ``section`` (``end_to_end`` or ``per_layer``) that
    the cell reports: those that list it under ``workloads``, and those
    with no such list. A per-layer metric with no list goes with every
    cell that reports the end-to-end metric it moves."""
    e2e = metrics_for(bench, cell, "end_to_end") \
        if section == "per_layer" else None
    out = []
    for m in bench[section]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif section == "end_to_end":
            out.append(m)
        elif any(e["name"] == m["moves"] for e in e2e):
            out.append(m)
    return out


def peaks_for(kind: str, bench_dir: Path = BENCH) -> Dict[str, float]:
    """The chip's published peaks. A device that is not in the table is
    an error, never a default."""
    table = _read_json(bench_dir / "peaks.json")["devices"]
    if kind not in table:
        raise UnknownName(f"device kind {kind!r} is not in "
                          f"bench/peaks.json; have {sorted(table)}")
    return table[kind]
