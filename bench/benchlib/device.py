"""The accelerator a run is measured on, and what it compiled.

A run measures a TPU or nothing: with no TPU, fewer chips than the
cell asks for, or a device kind missing from ``bench/peaks.json``,
:func:`require_chips` raises and the run prints no result.
"""
from __future__ import annotations

from typing import Any, Dict


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


def device_info() -> Dict[str, Any]:
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def require_chips(info: Dict[str, Any], chips: int) -> None:
    if info["platform"] != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{info['platform']!r}); nothing was run")
    if info["count"] < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{info['count']}")


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    import jax
    peaks = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class CompileCounter:
    """Counts XLA backend compiles through ``jax.monitoring``: a compile
    inside the measured window shows as one."""

    def __init__(self) -> None:
        import jax
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
