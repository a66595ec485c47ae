"""What a fitmask call has to do, whatever implements it.

The work is counted from the caller's request, unpadded: ``B`` grids of
``X x Y x Z`` cells and ``K`` candidate boxes, one decision per (grid,
box, origin). Reading the occupancy costs one byte per cell; writing
the answer costs one bit per decision. Each decision needs
``DECISION_OPS`` integer operations (three differences of the integral
image and a comparison, the nested per-axis differencing) and each cell
one more (its term of the integral image). Padding, the output's
encoding and any reduction on the device are left out, so a faster
encoding shows as a higher share and never as more work.
"""
from __future__ import annotations

from typing import Dict, Tuple

DECISION_OPS = 4


def fitmask_work(b: int, k: int, x: int, y: int, z: int) -> Tuple[int, float]:
    """(integer operations, bytes) one fitmask call needs."""
    cells = b * x * y * z
    decisions = cells * k
    return DECISION_OPS * decisions + cells, cells + decisions / 8.0


def roofline_seconds(ops: float, nbytes: float,
                     peaks: Dict[str, float]) -> Tuple[float, str]:
    """The least time the chip could take, and which peak bounds it.
    The operations are integer ones, so the int8 peak is the ceiling."""
    t_ops = ops / peaks["int8_ops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_mem else (t_mem, "bytes")
