"""Typed engine selection: which fitmask backend, driven how.

The engine is named once, where a torus, a query broker or a daemon is
built, and nothing in the process changes it afterwards. An unnamed
selection means ``numpy``; a name is checked by the engine registry
(``repro.kernels.fitmask.ops.engine_name``), which is the one place a
name becomes an engine. :class:`EngineConfig` carries the name together
with how the fleet and service layers drive it.

This module imports neither jax nor the engine registry at import time
(the registry is consulted lazily) so the numpy-purity of the host path
is preserved.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Union

# Failover order (PR 9): when a compiled engine starts raising at
# runtime, the fleet broker degrades *down* this chain — each step
# strictly reduces the stack it depends on, ending at the pure-numpy
# host engine that cannot lose a backend. The chain is total over the
# compiled tiers; registry engines outside it (e.g. ``ref``) degrade
# straight to numpy.
FAILOVER_CHAIN = ("pallas", "jax", "numpy")


def failover_candidates(name: str) -> tuple:
    """Engines to try, in order, after ``name`` fails at runtime.
    Numpy is the floor (empty tuple — nothing left to fail over to);
    unknown names also return empty (a custom engine *instance* has no
    registry identity, so the broker never fails it over — errors
    propagate, preserving the historical contract)."""
    from repro.kernels.fitmask import ops  # lazy: numpy-only either way
    if name not in ops.available_engines():
        return ()
    if name in FAILOVER_CHAIN:
        return FAILOVER_CHAIN[FAILOVER_CHAIN.index(name) + 1:]
    return ("numpy",)


@dataclass(frozen=True)
class EngineConfig:
    """One typed value for "which fitmask backend, driven how".

    ``engine``
        Registry name (``numpy``/``jax``/``pallas``/``ref``); ``None``
        means ``numpy``.
    ``fleet_size`` / ``quorum`` / ``timeout`` / ``max_inflight``
        How the fleet/service layers drive the backend: simulators per
        broker and the broker's flush policy. ``"auto"`` defers to the
        engine-aware policy in ``repro.sim.fleet.Fleet``.
    """

    engine: Optional[str] = None
    fleet_size: Union[str, int, None] = "auto"
    quorum: Union[str, float, None] = "auto"
    timeout: Union[str, float, None] = "auto"
    max_inflight: Optional[int] = None

    @classmethod
    def coerce(cls, value) -> "EngineConfig":
        """Accept the spellings call sites already use: ``None`` (all
        defaults), a bare engine name string, or an EngineConfig."""
        if value is None:
            return cls()
        if isinstance(value, EngineConfig):
            return value
        if isinstance(value, str):
            return cls(engine=value)
        raise TypeError("engine selection must be None, an engine name "
                        f"or an EngineConfig, got {value!r}")

    def resolve_name(self) -> str:
        """The registry name: the explicit one, checked by the registry
        (``KeyError`` if unknown), else ``numpy``."""
        from repro.kernels.fitmask import ops
        return ops.engine_name(self.engine)
