"""The plain reference of the device path, its control, and the faults
the harness must catch.

:class:`PlainFitmask` answers the fitmask contract the straightforward
way: box ``(a, b, c)`` fits at an origin when the origin's window lies
inside the grid (no wrap) and no cell of it is occupied. It imports
nothing of the program and keeps no state between calls. The plain
scheduler above it is :mod:`benchlib.plainsched`.

:class:`StaleFitmask` is the control. The configuration guarantees an
exact answer at every op; the control answers from occupancy that lags
one query behind a release (cells freed since the previous call still
count as busy). Its answers are never unsafe, only stale, which is the
shortcut a faster engine would be tempted to take. :data:`FAULTS` are
planted in the program's engine in the fault test and on the chip.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Sequence, Tuple

import numpy as np
from numpy.lib.stride_tricks import sliding_window_view

from .spans import route_through_calls

Box = Tuple[int, int, int]


def _any_along(occ: np.ndarray, axis: int, n: int) -> np.ndarray:
    """Whether any of the ``n`` cells from each origin along ``axis`` is
    occupied (origins whose run would leave the grid are dropped)."""
    windows = sliding_window_view(occ, n, axis=axis)
    return windows.any(axis=-1)


class PlainFitmask:
    host_free = True

    def multibox(self, occ, boxes: Sequence[Box]) -> np.ndarray:
        occ = np.asarray(occ).astype(bool)
        b, x, y, z = occ.shape
        out = np.zeros((b, len(boxes), x, y, z), bool)
        for k, (a, bb, c) in enumerate(boxes):
            a, bb, c = int(a), int(bb), int(c)
            if a > x or bb > y or c > z:
                continue
            # A box holds an occupied cell iff some run along x, then
            # along y, then along z does: one axis at a time.
            busy = _any_along(_any_along(_any_along(occ, 1, a), 2, bb), 3, c)
            out[:, k, :x - a + 1, :y - bb + 1, :z - c + 1] = ~busy
        return out

    def free_counts(self, occ) -> np.ndarray:
        occ = np.asarray(occ).astype(bool)
        return (~occ).sum(axis=(1, 2, 3)).astype(np.int64)


class StaleFitmask(PlainFitmask):
    """The control: answers on the union of this call's occupancy and
    the previous call's of the same shape."""

    def __init__(self) -> None:
        self._last: Dict[Tuple[int, ...], np.ndarray] = {}

    def _lagged(self, occ) -> np.ndarray:
        occ = np.asarray(occ).astype(bool)
        prev = self._last.get(occ.shape)
        self._last[occ.shape] = occ.copy()
        return occ if prev is None else occ | prev

    def multibox(self, occ, boxes: Sequence[Box]) -> np.ndarray:
        return super().multibox(self._lagged(occ), boxes)

    def free_counts(self, occ) -> np.ndarray:
        return super().free_counts(self._lagged(occ))


# -- planting: the control and the faults in the program's place ------

def use_control(engine: Any) -> None:
    """Put the control in the program's engine's place."""
    route_through_calls(engine)
    control = StaleFitmask()
    engine.multibox = control.multibox
    engine.free_counts = control.free_counts


def _half_batch(engine: Any) -> None:
    """Half of the grids left out: only the first half is answered."""
    route_through_calls(engine)
    inner = engine.multibox

    def multibox(occ, boxes):
        out = np.array(inner(occ, boxes))
        out[(len(out) + 1) // 2:] = 0
        return out
    engine.multibox = multibox


def _flip(engine: Any) -> None:
    """An answer altered where it is produced: the first origin where a
    box fits is reported as not fitting."""
    route_through_calls(engine)
    inner = engine.multibox

    def multibox(occ, boxes):
        out = np.array(inner(occ, boxes))
        hits = np.flatnonzero(out)
        if hits.size:
            out.flat[hits[0]] = 0
        return out
    engine.multibox = multibox


def _unchanged(engine: Any) -> None:
    """A step that returns its state unchanged: every call after the
    first of a shape answers as the first one did."""
    route_through_calls(engine)
    inner = engine.multibox
    seen: Dict[Tuple, np.ndarray] = {}

    def multibox(occ, boxes):
        key = (np.asarray(occ).shape, tuple(map(tuple, boxes)))
        if key not in seen:
            seen[key] = np.array(inner(occ, boxes))
        return seen[key]
    engine.multibox = multibox


FAULTS: Dict[str, Callable[[Any], None]] = {
    "half_batch": _half_batch,
    "flip": _flip,
    "unchanged": _unchanged,
}


def _lost_wal(core: Any) -> None:
    """The durability step returns its state unchanged: journal appends
    and snapshots write nothing, yet every op is acknowledged."""
    core._wal_writer().append = lambda rec: None
    core.sync_checkpoint = lambda: None


def _plan_skip(core: Any) -> None:
    """Plan search answers wrongly: every seventh job it could place is
    reported as not placeable now (its plan is undone), so it queues."""
    policy = core.policy
    inner = policy.try_place
    calls = [0]

    def try_place(job_id, shape):
        placement = inner(job_id, shape)
        if placement is not None:
            calls[0] += 1
            if calls[0] % 7 == 0:
                policy.release(job_id)
                return None
        return placement
    policy.try_place = try_place


# Faults planted in the served daemon's allocator core.
CORE_FAULTS: Dict[str, Callable[[Any], None]] = {"lost_wal": _lost_wal,
                                                 "plan_skip": _plan_skip}


def canonical(obj: Any) -> str:
    """One spelling of a JSON-able value (tuples as lists, sorted keys),
    so that a reply and its reference compare as text; a NaN equals
    itself."""
    import json
    return json.dumps(json.loads(json.dumps(obj)), sort_keys=True)
