"""``mfu.moonlight``: model FLOPs of the window's work per second over the
chip's bf16 peak — the whole step's share of the peak.

Every token the window pushed through the model (prompt tokens at
admission, one token per live request at each decode step) counts the
latent attention's projections and its causal attention over the token's
context, the dense layer, the MoE layers' routers and shared experts, and
the head (``harness.moe_work.token_flops``); the routed experts count
their rows, the window's change in ``Engine.counters()``'
``moe_rows_routed``, at ``expert_row_flops`` each."""
import numpy as np

from harness import moe_work
from harness.peaks import peaks_for


def read(run):
    if not run.requests or "moe_rows_routed" not in run.counters:
        return None
    lo, hi = run.window
    cfg = run.config
    flops = run.counters["moe_rows_routed"] * moe_work.expert_row_flops(cfg)
    for r in run.requests:
        if r.token_times and lo <= r.token_times[0] <= hi:
            flops += moe_work.token_flops(cfg, np.arange(r.prompt_len)).sum()
        later = [k for k, t in enumerate(r.token_times)
                 if k and lo <= t <= hi]
        if later:
            flops += moe_work.token_flops(
                cfg, r.prompt_len + np.asarray(later) - 1).sum()
    return 100.0 * flops / run.window_s / peaks_for(run.device_kind).bf16_flops
