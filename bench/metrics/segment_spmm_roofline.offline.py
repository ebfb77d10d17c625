"""``segment_spmm_roofline.offline``: the least time the chip could take for
the window's block-sparse FFN products, over the device time of the kernels
named ``segment_spmm*``.

The least time is counted by the benchmark from the configuration, not from
anything the program builds:

- every run of a model program — each decode step and each prefill chunk,
  the window's change in ``Engine.counters()``' ``decode_steps`` plus
  ``prefill_chunks`` — multiplies every stored FFN block once: the stored
  blocks of up, gate and down (``run.extra["ffn_blocks"]``) in each of the
  ``num_hidden_layers`` layers;
- bytes: those blocks, ``ffn_block``² elements each, read once a run at
  2 B, the bf16 the MXU consumes (the least any implementation must move),
  plus the activations in and out of each projection at 2 B for each real
  token;
- flops: 2 × stored blocks × ``ffn_block``² × the real tokens of the
  window (``decode_rows``, a live request's row in each decode step, plus
  ``prefill_tokens``, the prompt tokens of each chunk);
- least time = max(bytes / HBM bandwidth, flops / bf16 peak) over the whole
  window (``harness.peaks``).  Each run alone is bound by its bytes at
  these widths, so the window's sum is too.
"""
from harness.peaks import peaks_for
from harness.trace import kernel_time


def read(run):
    c = run.counters
    if run.trace is None or "decode_steps" not in c:
        return None
    kernel = kernel_time(run.trace, lambda k: k.startswith("segment_spmm"))
    if kernel <= 0:
        return None
    cfg, pk = run.config, peaks_for(run.device_kind)
    block = cfg["ffn_block"] ** 2
    blocks = sum(run.extra["ffn_blocks"]) * cfg["num_hidden_layers"]
    programs = c["decode_steps"] + c["prefill_chunks"]
    tokens = c["decode_rows"] + c["prefill_tokens"]
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    # up and gate read d and write ff per token, down reads ff, writes d
    activations = 3 * (d + ff) * cfg["num_hidden_layers"] * tokens
    least = max((programs * blocks * block + activations) * 2
                / pk.hbm_bytes_per_s,
                2.0 * blocks * block * tokens / pk.bf16_flops)
    return 100.0 * least / kernel
