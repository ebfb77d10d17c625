"""Unified analytical cost model for the schedule search.

One candidate schedule's predicted wall time is::

    cost_us = traffic_bytes / bytes_per_us + steps * step_us

— a two-term roofline: the revisiting-model HBM bytes of the schedule
(lane-aware; see :func:`repro.core.schedule.lane_traffic_spmm`)
over an effective bandwidth, plus a per-grid-step overhead term that prices
grid launch/bookkeeping.  Imbalance and padding need no separate penalty
knob: pads occupy grid steps (``steps`` counts the *padded* lane length) and
move no bytes, so an imbalanced lane split pays exactly its idle steps.

``lane_parallel`` switches the step count's execution semantics:

* ``True`` — lanes occupy parallel grid dimensions that real hardware runs
  concurrently; a step costs one unit regardless of ``n_lanes``.
* ``False`` — the interpret backend (and any fully sequential executor)
  runs the whole grid serially; steps scale with ``n_lanes``.

The two shipped defaults were fixed once against ``BENCH_kernels.json``
interpret timings (see ``benchmarks/kernel_bench.py::autotune_sweep``, which
re-fits and reports the coefficients on every run so drift is visible):
interpret wall time tracks bytes at a couple of KB/us with ~10 us of
emulation overhead per grid step; the TPU model uses ~800 GB/s HBM and
sub-microsecond step overhead.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Two-coefficient roofline cost model (see module docstring)."""

    bytes_per_us: float          # effective HBM bandwidth, bytes / microsecond
    step_us: float               # per-grid-step overhead, microseconds
    lane_parallel: bool = True   # False: lanes execute sequentially
    # fraction of one step's overhead that prefetch="cross_pass" saves at
    # each of the (n_tiles_n - 1) pass boundaries: real hardware overlaps
    # the boundary pipeline drain with the previous pass's tail compute
    # (1.0), while the sequential interpreter replays every copy inline
    # and saves nothing (0.0) — so prefetch never wins the interpret
    # objective on a phantom credit
    prefetch_step_credit: float = 0.0

    def steps(self, *, n_lanes: int, lane_len: int, unroll: int,
              n_tiles_n: int = 1) -> float:
        """Grid steps one kernel launch executes for this schedule shape.

        ``lane_len`` is the *padded* per-lane item count (a multiple of
        ``unroll``); each grid step retires ``unroll`` items of one lane
        for one N tile."""
        per_lane = (lane_len / max(1, unroll)) * max(1, n_tiles_n)
        return per_lane * (n_lanes if not self.lane_parallel else 1)

    def cost_us(self, *, traffic_bytes: float, n_lanes: int, lane_len: int,
                unroll: int, n_tiles_n: int = 1,
                prefetch: bool = False) -> float:
        base = (traffic_bytes / self.bytes_per_us
                + self.steps(n_lanes=n_lanes, lane_len=lane_len,
                             unroll=unroll, n_tiles_n=n_tiles_n)
                * self.step_us)
        if prefetch and n_tiles_n > 1:
            # cross-pass prefetch hides one boundary drain per N-tile
            # transition (worth a step_us fraction set by the model)
            base -= (n_tiles_n - 1) * self.step_us * self.prefetch_step_credit
        return base


def calibrate(samples: Iterable[Tuple[float, float, float]],
              lane_parallel: bool = False) -> CostModel:
    """Fit ``(bytes_per_us, step_us)`` from measured ``(bytes, steps, us)``
    triples by non-negative least squares on ``us ≈ bytes/bw + steps·c``.

    Solves the 2×2 normal equations for ``(1/bw, c)`` and clamps each
    coefficient at a small positive floor — a degenerate sample set (all
    bytes equal, or all steps equal) must still yield a usable monotone
    model, not a division by zero or a negative bandwidth that would
    invert the ranking."""
    rows: Sequence[Tuple[float, float, float]] = [
        (float(b), float(s), float(t)) for b, s, t in samples]
    if not rows:
        raise ValueError("calibrate() needs at least one (bytes, steps, us) "
                         "sample")
    # normal equations for least squares on [bytes, steps] @ [inv_bw, c] = us
    sbb = sum(b * b for b, _, _ in rows)
    sss = sum(s * s for _, s, _ in rows)
    sbs = sum(b * s for b, s, _ in rows)
    sbt = sum(b * t for b, _, t in rows)
    sst = sum(s * t for _, s, t in rows)
    det = sbb * sss - sbs * sbs
    if abs(det) > 1e-12 * max(1.0, sbb) * max(1.0, sss):
        inv_bw = (sbt * sss - sst * sbs) / det
        c = (sst * sbb - sbt * sbs) / det
    else:
        # rank-deficient: attribute everything to whichever axis varies
        inv_bw = sbt / sbb if sbb > 0 else 0.0
        c = sst / sss if sss > 0 else 0.0
    inv_bw = max(inv_bw, 1e-12)
    c = max(c, 1e-9)
    return CostModel(bytes_per_us=1.0 / inv_bw, step_us=c,
                     lane_parallel=lane_parallel)


#: compiled-target model: ~800 GB/s effective HBM, 0.5 us per grid step,
#: lanes concurrent.  Not yet calibrated against real-device timings (no
#: accelerator in CI) — the coefficients set plausible relative weights.
DEFAULT_TPU = CostModel(bytes_per_us=8.0e5, step_us=0.5, lane_parallel=True,
                        prefetch_step_credit=1.0)

#: interpret-backend model, fixed against BENCH_kernels.json timings
#: (autotune_sweep refits and reports both coefficient sets every run):
#: the interpreter streams ~2 KB/us and pays ~10 us of emulation per grid
#: step, and the whole grid — lanes included — runs sequentially.
DEFAULT_INTERPRET = CostModel(bytes_per_us=2.0e3, step_us=10.0,
                              lane_parallel=False)
