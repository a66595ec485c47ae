"""Per-layer numbers read from the tags of the program's own spans.

A span's tags carry counts the span's time cannot: the bytes an
``engine.fetch`` copied back, the box slots a ``broker.flush`` served
and needed. The records are those of the traced window
(``progspans._whatif``); a program whose spans lack the tag, or that
records no spans, makes every reader here return None.
"""
from __future__ import annotations

from typing import List, Optional

from .progspans import _whatif


def _tagged(run, name: str, tags: List[str]):
    recs = _whatif(run)
    if recs is None:
        return None
    return [r for r in recs
            if r.name == name and all(t in r.tags for t in tags)]


def tag_per_job(run, name: str, tag: str) -> Optional[float]:
    """The ``tag`` of spans called ``name``, summed, per simulated job."""
    recs = _tagged(run, name, [tag])
    if not recs:
        return None
    return sum(r.tags[tag] for r in recs) / run.extra["jobs"]


def tag_ratio(run, name: str, num: str, den: str) -> Optional[float]:
    """Sum of the ``num`` tag over the sum of the ``den`` tag, over
    spans called ``name`` that carry both."""
    recs = _tagged(run, name, [num, den])
    if not recs:
        return None
    total = sum(r.tags[den] for r in recs)
    return sum(r.tags[num] for r in recs) / total if total else None
