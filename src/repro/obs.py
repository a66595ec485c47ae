"""Spans inside the scheduler: where an op's or a sweep's time goes.

A span is a timed interval with a name, tags, a parent and the request
id of the op it serves::

    with obs.span("wal.append", bytes=n) as sp:
        ...
        sp.tag(fsyncs=1)

Spans record only while a JAX profiler session is active in this
process (``jax.profiler.trace``, ``jax.profiler.start_trace``, or a
profiler server started with ``jax.profiler.start_server`` and
captured from outside). That is the operator's one switch: a scheduler
nobody profiles pays one check per span and :func:`span` hands back a
shared object that does nothing. The check looks for JAX in
``sys.modules`` first, so the numpy host path never imports JAX.

While recording, each span is also a ``jax.profiler.TraceAnnotation``
of the same name, so the profiler's trace shows it on the host plane
on the device trace's clock, and each closed span is kept as a
:class:`Record` in a bounded in-memory log (the oldest are dropped
past :data:`CAPACITY`). :func:`records` copies the log out; it never
drains it.

The parent is the innermost span open in the same context: the same
thread, or the same asyncio task, since the daemon serves many
connections on one thread. A span takes its parent's request id
unless it is given one. Counts go on spans as tags when they close;
:meth:`_Span.count` gives the number of spans of a name closed under
a span so far, so a caller can tag how many plan searches an op ran
without counting them itself.

Span names and their tags are listed in DESIGN.md ("Tracing a live
scheduler").
"""
from __future__ import annotations

import collections
import contextvars
import itertools
import sys
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional

CAPACITY = 1 << 18   # records kept; the oldest are dropped past it


class Record(NamedTuple):
    """One closed span; times are ``time.perf_counter`` seconds."""

    name: str
    t0: float
    t1: float
    sid: int                 # span id, unique in the process
    parent: Optional[int]    # the enclosing span's id
    rid: Optional[str]       # request id of the op it serves
    tags: Dict[str, Any]

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


_log: "collections.deque[Record]" = collections.deque(maxlen=CAPACITY)
_ids = itertools.count(1)
_current: "contextvars.ContextVar[Optional[_Span]]" = \
    contextvars.ContextVar("repro_obs_span", default=None)
_is_enabled: Optional[Callable[[], bool]] = None
_Annotation: Any = None   # jax.profiler.TraceAnnotation, once bound


def _bind() -> Optional[Callable[[], bool]]:
    """The profiler's own check, once JAX has been imported by someone
    else; None before that."""
    global _is_enabled, _Annotation
    if _is_enabled is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _Annotation = TraceAnnotation
        _is_enabled = TraceAnnotation.is_enabled
    return _is_enabled


def recording() -> bool:
    """Whether spans record now: a JAX profiler session is active."""
    on = _is_enabled or _bind()
    return on is not None and on()


class _Off:
    """The span handed out while nothing records: every call is a no-op."""

    __slots__ = ()
    recording = False

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def tag(self, **tags: Any) -> None:
        pass

    def count(self, name: str) -> int:
        return 0


_OFF = _Off()


class _Span:
    __slots__ = ("name", "rid", "tags", "sid", "t0", "_parent", "_token",
                 "_ann", "_counts")
    recording = True

    def __init__(self, name: str, rid: Optional[str],
                 tags: Dict[str, Any]) -> None:
        self.name = name
        self.rid = rid
        self.tags = tags
        self._counts: Dict[str, int] = {}

    def __enter__(self) -> "_Span":
        parent = _current.get()
        if self.rid is None and parent is not None:
            self.rid = parent.rid
        self._parent = parent
        self.sid = next(_ids)
        self._token = _current.set(self)
        self._ann = _Annotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        _current.reset(self._token)
        parent = self._parent
        if parent is not None:
            counts = parent._counts
            counts[self.name] = counts.get(self.name, 0) + 1
            for name, n in self._counts.items():
                counts[name] = counts.get(name, 0) + n
        _log.append(Record(self.name, self.t0, t1, self.sid,
                           parent.sid if parent is not None else None,
                           self.rid, self.tags))
        return False

    def tag(self, **tags: Any) -> None:
        """Add tags; ``rid`` sets the request id, which spans opened
        under this one from then on take as theirs."""
        if "rid" in tags:
            self.rid = tags.pop("rid")
        self.tags.update(tags)

    def count(self, name: str) -> int:
        """Spans called ``name`` closed under this one so far, at any
        depth."""
        return self._counts.get(name, 0)


def span(name: str, rid: Optional[str] = None, **tags: Any):
    """A context manager timing one step; a no-op unless recording.
    Tags that cost anything to compute are best set with
    ``if sp.recording: sp.tag(...)`` inside the block."""
    on = _is_enabled or _bind()
    if on is None or not on():
        return _OFF
    return _Span(name, rid, tags)


def records() -> List[Record]:
    """A copy of the recorded spans, oldest first."""
    return list(_log)
