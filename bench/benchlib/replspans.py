"""Per-layer numbers of a replicated served cell, read from the
primary's ``repl.serve`` spans (``repro.obs``).

A standby's pull carries no request id, so its spans are not any op's:
the readers here take every ``repl.serve`` span of the traced window
and divide by the window's applied ops (``progspans._served``). A
program that records no such span, or no such tag, makes every reader
return None.
"""
from __future__ import annotations

from typing import Any, Callable, List, Optional

from .progspans import _records, _served

SPAN = "repl.serve"


def pulls(run, tag: Optional[str] = None) -> List[Any]:
    """The window's ``repl.serve`` records (those with ``tag``, if
    given)."""
    return [r for r in _records(run)
            if r.name == SPAN and (tag is None or tag in r.tags)]


def per_op(run, value: Callable[[Any], float],
           tag: Optional[str] = None) -> Optional[float]:
    """``value`` of each pull, summed, per applied op of the window."""
    recs = pulls(run, tag)
    ops = _served(run)
    if not recs or not ops:
        return None
    return sum(value(r) for r in recs) / len(ops)
