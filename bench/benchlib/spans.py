"""Spans around the calls into each layer, taken from the benchmark's
own files (the program has none of its own).

Each span is also a ``jax.profiler.TraceAnnotation`` of the same name,
so the profiler's trace can say what the host was doing while the
device sat idle. Spans live in memory and are read after the window.
They are installed only in a ``--trace 1`` run: end-to-end numbers are
taken with none of this in the path.

Span names:

* ``bench.apply``: ``AllocatorCore.apply``, one per op the daemon
  serves, tagged with the op's request id;
* ``bench.wal``: the journal writer's append (frame, write, fsync);
* ``bench.snapshot``: the periodic journal snapshot;
* ``bench.engine``: one fitmask engine call (``multibox`` or
  ``free_counts``), tagged with the caller's unpadded shape.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Tuple


@dataclass
class Span:
    name: str
    t0: float
    t1: float
    rid: Optional[str] = None       # request id of the op it serves
    kind: Optional[str] = None      # engine call: multibox | free_counts
    shape: Optional[Tuple[int, ...]] = None   # (B, K, X, Y, Z)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Spans:
    """An in-memory span log. ``rid`` of the op being applied is kept
    per thread, so engine and journal spans know which op they serve."""

    def __init__(self) -> None:
        self.records: List[Span] = []
        self._local = threading.local()
        self._lock = threading.Lock()

    def _add(self, span: Span) -> None:
        with self._lock:
            self.records.append(span)

    def wrap(self, name: str, fn: Callable, *,
             shape_of: Optional[Callable[..., Tuple[int, ...]]] = None,
             kind: Optional[str] = None) -> Callable:
        from jax.profiler import TraceAnnotation

        def wrapped(*args, **kw):
            shape = shape_of(*args) if shape_of is not None else None
            t0 = time.perf_counter()
            try:
                with TraceAnnotation(name):
                    return fn(*args, **kw)
            finally:
                self._add(Span(name, t0, time.perf_counter(),
                               getattr(self._local, "rid", None), kind,
                               shape))
        return wrapped

    def wrap_apply(self, fn: Callable) -> Callable:
        from jax.profiler import TraceAnnotation

        def apply(msg, *args, **kw):
            rid = msg.get("request_id")
            self._local.rid = rid
            t0 = time.perf_counter()
            try:
                with TraceAnnotation("bench.apply"):
                    return fn(msg, *args, **kw)
            finally:
                self._local.rid = None
                self._add(Span("bench.apply", t0, time.perf_counter(), rid))
        return apply


def _multibox_shape(occ, boxes) -> Tuple[int, ...]:
    b, x, y, z = occ.shape
    return (b, len(boxes), x, y, z)


def _counts_shape(occ) -> Tuple[int, ...]:
    b, x, y, z = occ.shape
    return (b, 0, x, y, z)


ENGINE_ATTRS = ("multibox", "free_counts", "multibox_bucketed")


def route_through_calls(engine: Any) -> None:
    """Make the broker's fused entry (``multibox_bucketed``) go through
    the instance's ``multibox`` and ``free_counts``, as the engine
    base class does, so that what is put on those two sees every call.
    The ``pallas`` engine has no fused entry of its own, so for it this
    changes nothing."""
    engine.multibox_bucketed = lambda occ, boxes: (
        engine.multibox(occ, boxes), engine.free_counts(occ))


def instrument_engine(engine: Any, spans: Spans) -> None:
    """Span every call of one fitmask engine instance. The engine's own
    methods are shadowed on the instance, so every caller (an inline
    mask client, the fleet broker) goes through the span."""
    route_through_calls(engine)
    engine.multibox = spans.wrap("bench.engine", engine.multibox,
                                 shape_of=_multibox_shape, kind="multibox")
    engine.free_counts = spans.wrap("bench.engine", engine.free_counts,
                                    shape_of=_counts_shape,
                                    kind="free_counts")


def restore_engine(engine: Any) -> None:
    """Drop whatever was put on the engine instance (spans, a planted
    control or fault): the engine is a process-wide singleton."""
    for attr in ENGINE_ATTRS:
        engine.__dict__.pop(attr, None)


def instrument_core(core: Any, spans: Spans) -> None:
    """Span the allocator core's op dispatch, its journal appends and
    its snapshots."""
    core.apply = spans.wrap_apply(core.apply)
    writer = core._wal_writer()
    writer.append = spans.wrap("bench.wal", writer.append)
    core.sync_checkpoint = spans.wrap("bench.snapshot", core.sync_checkpoint)
