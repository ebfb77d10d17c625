"""The chip a run needs, JAX's compile cache, and compiles in the window."""
from __future__ import annotations

import os
from typing import Dict

from .spec import ROOT

#: fixed, inside the checkout: the directory is part of the cache key
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at one fixed directory and
    cache every program, however quick to compile, so that only a
    checkout's first run compiles.  Call before JAX is imported.

    ``JAX_COMPILATION_CACHE_DIR`` is honoured where it is set; otherwise it
    is set to :data:`CACHE_DIR`, so the program under test, which reads the
    same variable, caches there too."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    return path


def require_chips(n: int, platform: str = "tpu"):
    """The first ``n`` devices, or :class:`NoChip`."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from None
    if devices[0].platform != platform or len(devices) < n:
        raise NoChip(f"needs {n} {platform} chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform!r} device(s)")
    return devices[:n]


def describe(devices) -> Dict:
    """``device`` of the result line: as JAX reports it, with the peak
    bytes in use on the fullest chip."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks) if peaks else None}


class CompileCounter:
    """Counts the programs built while ``armed`` — compiled, or loaded from
    the persistent cache: one built inside the measured window is a fault
    of the warm-up."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **kw) -> None:
        if self.armed and name == self.EVENT:
            self.count += 1
