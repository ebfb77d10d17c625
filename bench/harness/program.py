"""The program's own spans and compiled programs in a profiler trace: what
``harness/trace.py`` reads around them.

- Program spans are the program's ``jax.profiler.TraceAnnotation``s, named
  ``segfold.<what>`` (``repro.obs.span``), on the host plane beside the
  benchmark's ``bench.*`` spans and on the same clock.  Idle device time is
  attributed to the innermost program span open over it, by the same
  ``trace.attribute`` that splits it by the benchmark's spans
  (``host:none`` where no program span is open).
- Program runs are the events of line ``XLA Modules`` of a device plane, one
  per run of a compiled program, named ``<program>(<fingerprint>)``:
  ``jit_engine_decode(1234)`` is a run of ``jit_engine_decode``.  Their
  device intervals are moved onto the host clock by the offset
  ``trace.load`` found.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, Iterable, List, Tuple

from . import trace

PREFIX = "segfold."
_FINGERPRINT = re.compile(r"\(\d+\)$")


def module_name(event_name: str) -> str:
    """``jit_engine_decode(8812630943)`` -> ``jit_engine_decode``."""
    return _FINGERPRINT.sub("", event_name)


def load(path: str) -> Tuple[trace.Trace, List[trace.Span],
                             List[trace.Op]]:
    """``(benchmark trace, program spans, program runs)`` of one
    ``.xplane.pb``: the first is what ``trace.load`` reads, host spans
    ``bench.*`` only, so everything computed from it reads as before."""
    from jax.profiler import ProfileData
    # one pass keeps both kinds of span: the prefix goes to str.startswith
    tr = trace.load(path, span_prefix=("bench.", PREFIX))
    spans = [s for s in tr.spans if s.name.startswith(PREFIX)]
    tr = dataclasses.replace(
        tr, spans=[s for s in tr.spans if not s.name.startswith(PREFIX)])
    runs = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        dev = int(plane.name.rsplit(":", 1)[1])
        for line in plane.lines:
            if line.name == "XLA Modules":
                runs += [trace.Op(module_name(e.name),
                                  e.start_ns + tr.offset_ns,
                                  e.start_ns + e.duration_ns + tr.offset_ns,
                                  False, dev) for e in line.events]
    runs.sort(key=lambda r: r.start)
    return tr, spans, runs


@dataclasses.dataclass
class ProgramSummary:
    """The program's side of one traced window, in seconds."""
    idle_by_program_span: List[Tuple[str, float]]  # mean over devices
    span_names: frozenset             # program spans open in the window
    module_runs: Dict[str, List[float]]   # device time of each whole run

    def idle_in(self, names: Iterable[str]):
        """Idle device seconds whose innermost program span is one of
        ``names``; ``None`` where none of them ran in the window."""
        names = set(names)
        if not names & self.span_names:
            return None
        return sum(v for k, v in self.idle_by_program_span if k in names)

    def modules(self) -> List[Tuple[str, float, int]]:
        """``(program, device seconds, runs)``, most device time first."""
        rows = [(k, sum(v), len(v)) for k, v in self.module_runs.items()]
        return sorted(rows, key=lambda r: -r[1])


def summarize(tr: trace.Trace, spans: List[trace.Span],
              runs: List[trace.Op], lo: float, hi: float) -> ProgramSummary:
    """Reduce the program spans and runs inside the host-clock window
    ``[lo, hi]``; ``tr`` gives the device ops whose gaps are the idle time.
    A run counts where it lies wholly inside the window."""
    by_dev: Dict[int, List[trace.Interval]] = collections.defaultdict(list)
    for o in tr.ops:
        by_dev[o.device].append((o.start, o.end))
    idle: List[trace.Interval] = []
    for dev in range(tr.n_devices):
        idle += trace.gaps(by_dev.get(dev, []), lo, hi)
    module_runs: Dict[str, List[float]] = collections.defaultdict(list)
    for r in runs:
        if lo <= r.start and r.end <= hi:
            module_runs[r.name].append((r.end - r.start) * 1e-9)
    return ProgramSummary(
        idle_by_program_span=[
            (k, v * 1e-9 / tr.n_devices)
            for k, v in trace.attribute(idle, spans, lo, hi)],
        span_names=frozenset(s.name for s in spans
                             if s.end > lo and s.start < hi),
        module_runs=dict(module_runs))
