"""Request/response interface between torus models and fitmask engines.

A torus *submits* its per-epoch mask work to one client, fixed when the
torus is built:

  * :class:`InlineMaskClient` — answers immediately from one engine,
    the registry's instance for the engine the torus was named with.
  * ``repro.sim.fleet.QueryBroker`` — the fleet layer's client, passed
    in as ``mask_client=``: blocks the submitting simulator, coalesces
    concurrent requests from many simulators, and answers them all
    with genuinely batched engine calls (grids stacked on the B axis,
    candidate boxes unioned on K).
  * ``None`` — the numpy engine: integral images built directly
    inside the torus, with no engine call and no indirection.

The contract is deliberately tiny — the two primitives every policy
reduces to:

  ``multibox(occ, boxes) -> (B, K, X, Y, Z) integer/bool numpy``
      occ is a (B, X, Y, Z) bool grid batch; plane k is the full-grid
      fit mask of ``boxes[k]``, *nonzero where the box fits* (zero
      where it overhangs or cannot fit), in the *request's* box order.
      The dtype is the serving path's choice — classic engines return
      int32 0/1, the broker's bucketed flush path returns bool —
      so consumers test ``!= 0`` rather than comparing dtypes (both
      encodings carry identical truth values; parity-tested).
  ``free_counts(occ) -> (B,) int64 numpy``
      free cells per grid.

Both return host numpy arrays: callers index and cache them without
engine-specific conversions. Answers are a pure function of
``(occ[b], box)`` per plane, which is what makes any batching client
bit-exact with the inline path (see DESIGN.md §Fleet-batched eval).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

Box = Tuple[int, int, int]


class MaskQueryClient:
    """The request/response contract a torus submits mask work to.

    ``host_free`` advertises that the backing engine computes on the
    host with cost linear in the number of boxes (numpy). Toruses use
    it to choose a *lazy* mask strategy (ask only for the shape in
    hand) instead of the prefetch-everything-seen strategy that
    amortizes dispatch on compiled engines."""

    host_free = False

    def multibox(self, occ, boxes: Sequence[Box]) -> np.ndarray:
        """(B, X, Y, Z) occupancy x K boxes -> (B, K, X, Y, Z) numpy,
        nonzero where the box fits (consumers test ``!= 0``)."""
        raise NotImplementedError

    def free_counts(self, occ) -> np.ndarray:
        """(B, X, Y, Z) occupancy -> (B,) int64 free-cell counts."""
        raise NotImplementedError


class InlineMaskClient(MaskQueryClient):
    """Answers requests immediately from one fitmask engine — the
    single-simulator path. The engine's methods are looked up at each
    call, so a method set on the registry's instance after the torus
    was built is what answers."""

    def __init__(self, engine):
        self.engine = engine
        self.host_free = bool(getattr(engine, "host_free", False))

    def multibox(self, occ, boxes: Sequence[Box]) -> np.ndarray:
        return np.asarray(self.engine.multibox(occ, boxes))

    def free_counts(self, occ) -> np.ndarray:
        return np.asarray(self.engine.free_counts(occ)).astype(np.int64)


def resolve_mask_client(selection=None) -> Optional[InlineMaskClient]:
    """The client a torus built with this engine selection (an engine
    name, an :class:`~repro.core.engineconfig.EngineConfig`, or
    ``None``) submits to when no ``mask_client`` is given: ``None`` for
    numpy — the host path, free of indirection and jax imports — else
    an :class:`InlineMaskClient` over the registry's engine instance."""
    from repro.core.engineconfig import EngineConfig
    from repro.kernels.fitmask import ops  # numpy-only at import time
    name = EngineConfig.coerce(selection).resolve_name()
    if name == ops.DEFAULT_ENGINE:
        return None
    return InlineMaskClient(ops.get_engine(name))
