"""What a run hands to the metric readers: its host spans, counters, the
per-request timeline and, in a traced run, the device summary."""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple


class Spans:
    """Host spans around the benchmark's calls into the program.

    Each span is kept in memory on the host clock (``time.perf_counter``)
    and, while a profiler trace is active, also written into it as a
    ``jax.profiler.TraceAnnotation`` named ``bench.<name>``, on the same
    clock as the device trace."""

    def __init__(self):
        import jax
        self._annotation = jax.profiler.TraceAnnotation
        self.items: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        with self._annotation("bench." + name):
            yield
        self.items.append((name, t0, time.perf_counter()))

    def total(self, name: str, lo: float, hi: float) -> float:
        """Seconds spent in spans ``name`` within ``[lo, hi]``."""
        return sum(max(0.0, min(e, hi) - max(s, lo))
                   for n, s, e in self.items if n == name)


@dataclasses.dataclass
class RequestRecord:
    """One served request on the host clock (seconds)."""
    prompt_len: int
    max_new: int
    arrival: float                    # scheduled
    submitted: Optional[float] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    measured: bool = True             # arrived inside the window
    done: bool = False


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read.  A reader returns ``None`` when
    the run holds nothing for it."""
    workload: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    device_kind: str
    setup_s: float
    window: Tuple[float, float]       # host clock, seconds
    spans: Spans
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    requests: List[RequestRecord] = dataclasses.field(default_factory=list)
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)
    trace: Any = None                 # harness.trace.Summary of the window

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]
