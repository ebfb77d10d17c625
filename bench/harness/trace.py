"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

What is read:

- Device planes ``/device:TPU:<n>``, line ``XLA Ops``: one event per HLO
  operation that ran.  Busy time is the union of these intervals; nested or
  overlapping events count once.
- A Mosaic (Pallas) kernel is an ``XLA Ops`` event whose HLO text has
  ``custom_call_target="tpu_custom_call"``; its kernel name is the HLO
  instruction name (``%segment_spmm.3`` -> ``segment_spmm``).
- Host spans are the benchmark's own ``jax.profiler.TraceAnnotation``s,
  named ``bench.<what>``, on the host plane.
- Device and host clocks differ by an offset of a millisecond or two.  It is
  bounded from both sides by each program run (line ``XLA Modules``, stat
  ``run_id``): the device starts the run after the host's
  ``DoEnqueueProgram`` of that run ends, and ends it before the host's
  ``CompleteCallbacks`` of that run starts.  The midpoint of the tightest
  bounds is used to place device intervals on the host clock.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns) on the host clock

MOSAIC = 'custom_call_target="tpu_custom_call"'
_NUMBER = re.compile(r"\.\d+")


@dataclasses.dataclass(frozen=True)
class Op:
    """One device operation, placed on the host clock."""
    name: str            # short HLO name without its numeric suffix
    start: float         # ns
    end: float           # ns
    mosaic: bool
    device: int


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    spans: List[Span]
    n_devices: int
    offset_ns: float


def short_name(hlo_text: str) -> str:
    """The HLO instruction name without ``%`` and numeric suffixes:
    ``%segment_spgemm.1 = f32[...] custom-call(...)`` -> ``segment_spgemm``,
    ``%copy.76.remat = ...`` -> ``copy.remat``."""
    return _NUMBER.sub("", hlo_text.strip().split(" ", 1)[0].lstrip("%"))


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {paths}")
    return paths[0]


def _stats(event) -> Dict[str, object]:
    return {k: v for k, v in event.stats}


def load(path: str, span_prefix: str = "bench.") -> Trace:
    """Read device ops and host spans from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    raw_ops: List[Tuple[str, float, float, bool, int]] = []
    module_runs: Dict[int, Tuple[float, float]] = {}
    enqueue_end: Dict[int, float] = {}
    callback_start: Dict[int, float] = {}
    spans: List[Span] = []
    devices = set()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.add(dev)
                    for e in line.events:
                        raw_ops.append((e.name, e.start_ns,
                                        e.start_ns + e.duration_ns,
                                        MOSAIC in e.name, dev))
                elif line.name == "XLA Modules":
                    for e in line.events:
                        rid = _stats(e).get("run_id")
                        if rid is not None:
                            module_runs[int(rid)] = (
                                e.start_ns, e.start_ns + e.duration_ns)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        spans.append(Span(e.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
                    elif e.name == "DoEnqueueProgram":
                        rid = _stats(e).get("run_id")
                        if rid is not None:
                            enqueue_end[int(rid)] = e.start_ns + e.duration_ns
                    elif e.name == "CompleteCallbacks":
                        rid = _stats(e).get("run_id")
                        if rid is not None:
                            callback_start.setdefault(int(rid), e.start_ns)
    offset = clock_offset(module_runs, enqueue_end, callback_start)
    ops = [Op(short_name(n), s + offset, e + offset, m, d)
           for n, s, e, m, d in raw_ops]
    ops.sort(key=lambda o: o.start)
    spans.sort(key=lambda s: s.start)
    return Trace(ops=ops, spans=spans, n_devices=max(1, len(devices)),
                 offset_ns=offset)


def clock_offset(module_runs: Dict[int, Interval],
                 enqueue_end: Dict[int, float],
                 callback_start: Dict[int, float]) -> float:
    """Host minus device clock, in ns (see the module docstring)."""
    lo = [enqueue_end[r] - s for r, (s, _) in module_runs.items()
          if r in enqueue_end]
    hi = [callback_start[r] - e for r, (_, e) in module_runs.items()
          if r in callback_start]
    if not lo and not hi:
        return 0.0
    if not hi:
        return max(lo)
    if not lo:
        return min(hi)
    a, b = max(lo), min(hi)
    return (a + b) / 2 if a <= b else a


def union(intervals: Sequence[Interval], lo: float,
          hi: float) -> List[Interval]:
    """Merged intervals, clipped to ``[lo, hi]``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals: Sequence[Interval], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(intervals, lo, hi))


def gaps(intervals: Sequence[Interval], lo: float,
         hi: float) -> List[Interval]:
    """The parts of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in union(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def self_times(ops: Sequence[Op]) -> List[float]:
    """Each op's duration less that of the ops nested in it on its device
    (a ``while`` loop's events enclose its body's), in ns."""
    own = [o.end - o.start for o in ops]
    stacks: Dict[int, List[int]] = collections.defaultdict(list)
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start, -ops[i].end))
    for i in order:
        stack = stacks[ops[i].device]
        while stack and ops[stack[-1]].end <= ops[i].start:
            stack.pop()
        if stack and ops[i].end <= ops[stack[-1]].end:
            own[stack[-1]] -= ops[i].end - ops[i].start
        stack.append(i)
    return own


@dataclasses.dataclass
class Summary:
    """Device numbers of one traced window, in seconds."""
    window_s: float
    busy_s: float                 # union of op intervals, mean over devices
    mosaic_s: Dict[str, float]    # kernel -> device time, mean over chips
    top_ops: List[Tuple[str, float]]
    idle_by_span: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def summarize(tr: Trace, lo: float, hi: float, top: int = 10) -> Summary:
    """Reduce the ops and spans inside the host-clock window ``[lo, hi]``."""
    n = tr.n_devices
    by_dev: Dict[int, List[Interval]] = collections.defaultdict(list)
    per_op: Dict[str, float] = collections.defaultdict(float)
    mosaic: Dict[str, float] = collections.defaultdict(float)
    for o, own in zip(tr.ops, self_times(tr.ops)):
        s, e = max(o.start, lo), min(o.end, hi)
        if e <= s:
            continue
        by_dev[o.device].append((s, e))
        per_op[o.name] += own * (e - s) / (o.end - o.start) / n
        if o.mosaic:
            mosaic[o.name] += (e - s) / n
    busy = sum(covered(iv, lo, hi) for iv in by_dev.values()) / n
    idle: List[Interval] = []
    for dev in range(n):
        idle += gaps(by_dev.get(dev, []), lo, hi)
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy * 1e-9,
        mosaic_s={k: v * 1e-9 for k, v in mosaic.items()},
        top_ops=[(k, v * 1e-9) for k, v in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:top]],
        idle_by_span=[(k, v * 1e-9 / n) for k, v in attribute(
            idle, tr.spans, lo, hi)[:top]])


def attribute(idle: Sequence[Interval], spans: Sequence[Span], lo: float,
              hi: float) -> List[Tuple[str, float]]:
    """Idle device time by the innermost benchmark host span it falls in
    (``host:none`` where none is open), longest first."""
    inner = [s for s in spans if s.end > lo and s.start < hi]
    # cut the window at every span edge: inside each piece one span is the
    # innermost (shortest) open one
    marks = sorted([(max(s.start, lo), 1, i) for i, s in enumerate(inner)]
                   + [(min(s.end, hi), 0, i) for i, s in enumerate(inner)])
    pieces: List[Tuple[float, float, str]] = []
    active: set = set()
    t = lo
    for x, is_start, i in marks + [(hi, 0, -1)]:
        if x > t:
            name = (min((inner[j] for j in active),
                        key=lambda sp: sp.end - sp.start).name
                    if active else "host:none")
            pieces.append((t, x, name))
            t = x
        if i >= 0:
            (active.add if is_start else active.discard)(i)
    total: Dict[str, float] = collections.defaultdict(float)
    k = 0
    for gs, ge in sorted(idle):
        while k < len(pieces) and pieces[k][1] <= gs:
            k += 1
        j = k
        while j < len(pieces) and pieces[j][0] < ge:
            s, e = max(pieces[j][0], gs), min(pieces[j][1], ge)
            if e > s:
                total[pieces[j][2]] += e - s
            j += 1
    return sorted(total.items(), key=lambda kv: -kv[1])


def window_of(tr: Trace, name: str) -> Optional[Interval]:
    """The first host span called ``name``."""
    for s in tr.spans:
        if s.name == name:
            return s.start, s.end
    return None


def kernel_time(summary: Summary, match: Callable[[str], bool]) -> float:
    return sum(v for k, v in summary.mosaic_s.items() if match(k))
