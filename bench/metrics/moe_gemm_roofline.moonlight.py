"""``moe_gemm_roofline.moonlight``: the least time the chip could take for
the window's routed-expert products, over the device time of the kernels
named ``moe_gemm*``.

Counted by the benchmark from the configuration and the program's
counters (the window's change in ``Engine.counters()``), never from what
the program builds (``harness.moe_work``):

- bytes: each expert load (``moe_expert_loads``: a held expert with at
  least one routed row, in one MoE layer of one program run) reads its up,
  gate and down weights once in bf16, plus each routed row's activations
  in and out of the three projections (``moe_rows_routed``) in bf16;
- flops: 2 × 3 × hidden × expert width for each routed row;
- least time = max(bytes / HBM bandwidth, flops / bf16 peak)
  (``harness.peaks``).
"""
from harness import moe_work
from harness.peaks import peaks_for
from harness.trace import kernel_time


def read(run):
    c = run.counters
    if run.trace is None or "moe_rows_routed" not in c:
        return None
    kernel = kernel_time(run.trace, lambda k: k.startswith("moe_gemm"))
    if kernel <= 0:
        return None
    cfg, pk = run.config, peaks_for(run.device_kind)
    rows, loads = c["moe_rows_routed"], c["moe_expert_loads"]
    nbytes = (loads * moe_work.expert_load_bytes(cfg)
              + rows * moe_work.expert_row_bytes(cfg))
    least = max(nbytes / pk.hbm_bytes_per_s,
                rows * moe_work.expert_row_flops(cfg) / pk.bf16_flops)
    return 100.0 * least / kernel
