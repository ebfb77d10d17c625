"""How each metric is read from a :class:`~harness.record.Run`.

``bench/metrics/<metric>.py`` binds one of these as its ``read``.  A reader
returns ``None`` where the run holds nothing for it; a share of a peak or a
roofline is then left out, never reported as 0.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from .peaks import peaks_for
from .trace import kernel_time
from .work import decoder_flops_per_token


def setup_s(run) -> float:
    return run.setup_s


def _in(run, t: float) -> bool:
    return run.window[0] <= t <= run.window[1]


def gen_tok_s(run) -> Optional[float]:
    """Output tokens that reached the host inside the window, per second
    of the window."""
    if not run.requests:
        return None
    n = sum(_in(run, t) for r in run.requests for t in r.token_times)
    return n / run.window_s


def admit_share(run) -> Optional[float]:
    """Share of the window spent inside ``Engine.admit_pending``."""
    if not run.requests:
        return None
    return 100.0 * run.spans.total("admit", *run.window) / run.window_s


def mfu(run) -> Optional[float]:
    """Model FLOPs of every token the window pushed through the model —
    prompt tokens at admission, one token per live request at each decode
    step — per second, over the chip's bf16 peak."""
    if not run.requests:
        return None
    cfg, flops = run.config, 0.0
    blocks = run.extra["ffn_blocks"]
    for r in run.requests:
        if r.token_times and _in(run, r.token_times[0]):
            flops += decoder_flops_per_token(
                cfg, blocks, np.arange(r.prompt_len)).sum()
        later = [k for k, t in enumerate(r.token_times) if k and _in(run, t)]
        if later:
            flops += decoder_flops_per_token(
                cfg, blocks, r.prompt_len + np.asarray(later) - 1).sum()
    return 100.0 * flops / run.window_s / peaks_for(run.device_kind).bf16_flops


def idle_share(run) -> Optional[float]:
    """1 - device busy time (union of op intervals) / traced window."""
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    return 100.0 * run.trace.idle_share


def mosaic_share(run) -> Optional[float]:
    """Device time in Mosaic (Pallas) kernels over the traced window."""
    if run.trace is None or not run.trace.mosaic_s:
        return None
    return 100.0 * sum(run.trace.mosaic_s.values()) / run.trace.window_s


def spgemm_pass_ms(run) -> Optional[float]:
    passes = run.counters.get("passes")
    return 1e3 * run.window_s / passes if passes else None


def segment_spgemm_roofline(run) -> Optional[float]:
    """Least time the chip could take for the window's SpGEMM calls, over
    the Segment SpGEMM kernels' device time.  The least time of one call
    is max(flops / peak, bytes / HBM bandwidth) with both counted from the
    two patterns (``harness.work.spgemm_work``)."""
    if run.trace is None:
        return None
    kernel = kernel_time(run.trace, lambda k: k.startswith("segment_spgemm"))
    if kernel <= 0:
        return None
    pk = peaks_for(run.device_kind)
    least = run.counters["passes"] * sum(
        w.least_seconds(pk.bf16_flops, pk.hbm_bytes_per_s)
        for w in run.extra["work"].values())
    return 100.0 * least / kernel


def plan_s(run) -> Optional[float]:
    """Host seconds spent in ``plan_matmul`` during set-up."""
    plans = run.extra.get("plan_s")
    return sum(plans.values()) if plans else None
