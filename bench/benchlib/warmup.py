"""Compile (or load from the persistent cache) every kernel shape a
cell's traffic uses, before its window opens.

The configuration file lists the shapes per driver under ``warm``:
``multibox`` as ``[B, K]`` pairs and ``free_counts`` as ``B`` values,
with the grid's ``[X, Y, Z]``. The engine pads B and K to powers of
two, so each list holds every such pair up to the largest B and K that
the cell's traffic reached in engine calls counted on the CPU; a call
of another shape inside the window shows as ``window_compiles``.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np


def warm(engine: Any, shapes: Dict[str, Any]) -> int:
    """Call ``engine`` once per listed shape; returns the calls made."""
    grid = tuple(shapes["grid"])
    calls = 0
    for b, k in shapes.get("multibox", []):
        occ = np.zeros((b,) + grid, bool)
        boxes = [(1, 1, 1)] * k
        np.asarray(engine.multibox(occ, boxes))
        calls += 1
    for b in shapes.get("free_counts", []):
        np.asarray(engine.free_counts(np.zeros((b,) + grid, bool)))
        calls += 1
    return calls
