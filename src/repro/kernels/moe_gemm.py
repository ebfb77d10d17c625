"""Segment-scheduled grouped expert GEMM (MoE) — Pallas TPU.

MoE expert compute is the *data-dependent* instance of the paper's dynamic
dataflow: routing produces a (token-group × expert) block-sparse structure
known only at runtime.  The Segment treatment, inside jit:

* **SELECTA** ≙ sort tokens by expert (``build_moe_chunks``): consecutive
  chunks share the expert weight block, which then stays resident in VMEM
  across grid steps (row-wise reuse of the stationary operand);
* **folding** ≙ oversized expert groups are split into fixed-size chunks and
  padded groups masked — load is balanced at chunk, not expert, granularity.

Grid: ``(n_chunks, n_tiles_n)``; chunk→expert mapping is scalar-prefetched.
The weight tile for expert e, N-tile j is re-fetched only when (e, j)
changes — with chunks sorted by expert this is once per expert per N tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(chunk_expert, x, w, out):
    out[...] = jax.lax.dot_general(
        x[...].astype(jnp.float32), w[0].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(out.dtype)


@functools.partial(jax.jit, static_argnames=("chunk_rows", "bn", "interpret",
                                             "out_dtype"))
def moe_gemm(x_sorted, w, chunk_expert, *, chunk_rows: int = 128,
             bn: int = 512, interpret: bool = False, out_dtype=jnp.float32):
    """Grouped GEMM over expert-sorted tokens.

    Args:
      x_sorted: (n_chunks * chunk_rows, d_in) tokens sorted by expert and
        padded to whole chunks (invalid rows must be zero).
      w: (E, d_in, d_out) expert weights.
      chunk_expert: (n_chunks,) int32 expert id per chunk (sorted ascending —
        the SELECTA grouping).
    Returns:
      (n_chunks * chunk_rows, d_out) activations in the sorted order.
    """
    t, d_in = x_sorted.shape
    n_chunks = chunk_expert.shape[0]
    if t != n_chunks * chunk_rows:
        raise ValueError(
            f"x_sorted has {t} rows but chunk_expert describes "
            f"{n_chunks} chunks of {chunk_rows} rows — pad the sorted "
            f"tokens to whole chunks")
    e, d_in_w, d_out = w.shape
    if d_in_w != d_in:
        raise ValueError(f"expert weights contract over d_in={d_in_w} but "
                         f"tokens have d_in={d_in}")
    bn = min(bn, d_out)
    if d_out % bn:
        raise ValueError(f"d_out={d_out} must be a multiple of the N tile "
                         f"bn={bn}")
    n_tiles_n = d_out // bn

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_chunks, n_tiles_n),
        in_specs=[
            pl.BlockSpec((chunk_rows, d_in), lambda c, j, ce: (c, 0)),
            pl.BlockSpec((1, d_in, bn), lambda c, j, ce: (ce[c], 0, j)),
        ],
        out_specs=pl.BlockSpec((chunk_rows, bn), lambda c, j, ce: (c, j)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, d_out), out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(chunk_expert, x_sorted, w)


def build_moe_chunks(expert_of_token, n_experts: int, chunk_rows: int = 128,
                     capacity_factor: float = 1.25):
    """In-jit SELECTA for MoE: sort token ids by expert, pad each expert's
    group to whole chunks, emit (sort_idx, chunk_expert, valid mask).

    All shapes are static: ``n_chunks = ceil(T * capacity / chunk_rows)``
    with per-expert capacity ``cap = ceil(T * capacity_factor / E / rows) *
    rows``.  Overflowing tokens are dropped (standard MoE capacity
    semantics); the mask marks live rows.
    """
    t = expert_of_token.shape[0]
    cap_rows = int(np.ceil(t * capacity_factor / n_experts / chunk_rows)) * chunk_rows
    chunks_per_e = cap_rows // chunk_rows
    n_chunks = n_experts * chunks_per_e

    order = jnp.argsort(expert_of_token)                  # stable sort by expert
    sorted_e = expert_of_token[order]
    # position of each token within its expert group
    pos_in_e = jnp.arange(t) - jnp.searchsorted(sorted_e, sorted_e, side="left")
    keep = pos_in_e < cap_rows
    slot = sorted_e * cap_rows + pos_in_e                  # destination row
    slot = jnp.where(keep, slot, n_experts * cap_rows)     # overflow → trash row
    chunk_expert = jnp.repeat(jnp.arange(n_experts, dtype=jnp.int32), chunks_per_e)
    return order, slot, chunk_expert, keep, n_chunks, cap_rows
