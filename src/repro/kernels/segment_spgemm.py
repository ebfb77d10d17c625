"""Segment-scheduled BSR × BSR → BSR SpGEMM — Pallas TPU.

Two-phase TPU adaptation of SEGMENTBC (§III-B): the *symbolic* phase
(``repro.core.schedule.symbolic_spgemm``) computes C's block pattern ahead of
time — the V-space becomes a static compressed coordinate list at block
granularity — and this *numeric* kernel executes the (m, k, n) block triples
in Segment order through an **explicit double-buffered DMA pipeline**: both
operand block arrays live in HBM (``pl.ANY`` refs) and the kernel issues
``pltpu.make_async_copy`` for triple *i+1*'s A/B tiles into ``2·unroll``-slot
VMEM ring buffers while triple *i* runs on the MXU, waiting only at
consumption:

* per-item ``a_fetch``/``b_fetch`` flags (``repro.core.schedule.fetch_flags``
  — the same arrays the traffic model prices, so predicted fetch counts are
  kernel reality) gate every copy: segment-to-segment chaining that reuses
  boundary B blocks (SELECTA) skips the copy and reads the resident ring
  slot (``a_slot``/``b_slot``), pads move no data, a lane's first triple
  always fetches;
* triples of the same C block form contiguous segments (ordered accumulation
  in VMEM, written back once — the merge network's in-place reduction);
* folded continuations (``accum_prev``) read-modify-write their C block —
  temporal folding's partial-sum merge.

Grid: ``(n_lanes, lane_len // unroll)`` — the lane axis is **parallel**:
the triple list is cut into load-balanced lanes at C-segment boundaries
(``repro.core.schedule.partition_lanes``; a C slot never spans lanes), so
independent output chains run concurrently.  Every operand is selected by
scalar-prefetched index arrays (the ahead-of-time IPM) directly in original
BSR storage order; each grid step executes ``unroll`` same-C-slot triples
against the resident ring slots.  ``valid=0`` marks lane-padding no-ops
(contribution masked out).  Quantized per-block scales are gathered per item
and stream as per-step VMEM vectors (one vector load per step instead of
``unroll`` serialized SMEM scalar reads).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .segment_spmm import kernel_name, validate_schedule_args


def _make_pipeline_kernel(lane_len: int, unroll: int, masked: bool,
                          quant_a, quant_b):
    def _kernel(a_idx, b_idx, c_idx, seg_start, seg_write, accum_prev,
                valid, a_fetch, b_fetch, a_slot, b_slot, *refs):
        a_hbm, b_hbm, refs = refs[0], refs[1], refs[2:]
        if quant_a is not None:
            a_scale_ref, refs = refs[0], refs[1:]
        if quant_b is not None:
            b_scale_ref, refs = refs[0], refs[1:]
        out, acc, a_buf, b_buf, a_sem, b_sem = refs
        # grid coordinates are read once here: pl.program_id must not be
        # bound inside a pl.when branch (interpret mode only substitutes it
        # in the top-level kernel jaxpr)
        s = pl.program_id(1)
        n_steps = pl.num_programs(1)
        lane_base = pl.program_id(0) * lane_len
        base = lane_base + s * unroll

        def a_copy(i, slot):
            return pltpu.make_async_copy(
                a_hbm.at[a_idx[i]], a_buf.at[slot], a_sem.at[slot])

        def b_copy(i, slot):
            return pltpu.make_async_copy(
                b_hbm.at[b_idx[i]], b_buf.at[slot], b_sem.at[slot])

        def issue_a(i):
            @pl.when(a_fetch[i] == 1)
            def _():
                a_copy(i, a_slot[i]).start()

        def issue_b(i):
            @pl.when(b_fetch[i] == 1)
            def _():
                b_copy(i, b_slot[i]).start()

        # pass prologue + issue-one-step-ahead pipeline (see segment_spmm).
        # Issue order is the DMA priority mechanism: the bulky B tiles go on
        # the queue before the A tiles at every grid step
        # (repro.analysis.order's dma-priority rule asserts this order; for
        # square tiles the rule is vacuous and either order is fine, but
        # the kernels keep one convention).
        @pl.when(s == 0)
        def _prologue_b():
            for g in range(unroll):
                issue_b(lane_base + g)

        @pl.when(s + 1 < n_steps)
        def _pipeline_b():
            for g in range(unroll):
                issue_b(base + unroll + g)

        @pl.when(s == 0)
        def _prologue_a():
            for g in range(unroll):
                issue_a(lane_base + g)

        @pl.when(s + 1 < n_steps)
        def _pipeline_a():
            for g in range(unroll):
                issue_a(base + unroll + g)

        for g in range(unroll):
            i = base + g

            @pl.when(seg_start[i] == 1)
            def _init(i=i):
                @pl.when(accum_prev[i] == 1)
                def _load():
                    acc[...] = out[0].astype(jnp.float32)

                @pl.when(accum_prev[i] == 0)
                def _zero():
                    acc[...] = jnp.zeros_like(acc)

            @pl.when(a_fetch[i] == 1)
            def _wait_a(i=i):
                a_copy(i, a_slot[i]).wait()

            @pl.when(b_fetch[i] == 1)
            def _wait_b(i=i):
                b_copy(i, b_slot[i]).wait()

            a_tile = a_buf[a_slot[i]].astype(jnp.float32)
            b_tile = b_buf[b_slot[i]].astype(jnp.float32)
            # Rowwise scales (A rows → output rows, B rows → the contraction
            # axis) do not factor out of the dot, so those tiles dequantize
            # *before* the MXU contraction; the step's scale rows arrive as
            # one (unroll, rows) VMEM window each.
            if quant_a == "rowwise":
                a_tile = a_tile * a_scale_ref[0, g][:, None]
            if quant_b == "rowwise":
                b_tile = b_tile * b_scale_ref[0, g][:, None]
            contrib = jax.lax.dot_general(
                a_tile, b_tile,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            # per-block scales are scalar tile factors — applying them to
            # the fp32 product (after the dot, before accumulation) is
            # exact; the step's scales arrive as one VMEM vector each
            if quant_a == "block":
                contrib = contrib * a_scale_ref[0, :, g:g + 1]
            if quant_b == "block":
                contrib = contrib * b_scale_ref[0, :, g:g + 1]
            if masked:
                contrib = jnp.where(valid[i] == 1, contrib, 0.0)
            acc[...] += contrib

            @pl.when(seg_write[i] == 1)
            def _write(i=i):
                out[0] = acc[...].astype(out.dtype)

    return _kernel


@functools.partial(jax.jit, static_argnames=(
    "n_c_blocks", "n_lanes", "unroll", "masked", "interpret", "out_dtype",
    "prefetch"))
def segment_spgemm(a_blocks, b_blocks, a_idx, b_idx, c_idx, seg_start,
                   seg_write, accum_prev, valid, *, n_c_blocks: int,
                   n_lanes: int = 1, unroll: int = 1, masked: bool = True,
                   interpret: bool = False, out_dtype=jnp.float32,
                   a_scales=None, b_scales=None, a_fetch=None, b_fetch=None,
                   a_slot=None, b_slot=None, prefetch: str | None = None):
    """Numeric SpGEMM phase.

    Args:
      a_blocks: (na, bm, bk) BSR A tiles (original order; fp32 or a
        quantized payload — pass ``a_scales``).
      b_blocks: (nb, bk, bn) BSR B tiles (original order; ditto
        ``b_scales``).
      a_idx/b_idx/c_idx: (n_items,) int32 — triple → block-slot maps,
        flattened lane-major schedule order.
      seg_start/seg_write/accum_prev/valid: (n_items,) int32 schedule flags.
      n_c_blocks: number of symbolic C blocks.
      n_lanes/unroll: lane-parallel grid shape (see module docstring).
      a_scales/b_scales: fp32 dequantization scales — per-block
        (``(na,)`` / ``(nb,)``, applied to the fp32 product) or per block
        row (``(na, bm)`` / ``(nb, bk)``, rowwise mode: tiles dequantize
        before the dot since B-row scales ride the contraction axis).
        Gathered per item and streamed as per-step VMEM windows.
      a_fetch/b_fetch: (n_items,) int32 DMA fetch flags — 1 where the item
        must copy its A/B tile from HBM, 0 where the resident ring slot is
        reused (see ``repro.core.schedule.fetch_flags``).
      a_slot/b_slot: (n_items,) int32 resident ring-buffer slot per item.
      prefetch: accepted for knob-grid uniformity with ``segment_spmm``;
        the SpGEMM grid has no N-tile pass axis, so ``"cross_pass"``
        degenerates to the drained schedule (validated, kernel-side no-op).
    Returns:
      (n_c_blocks, bm, bn) C blocks, ordered as the symbolic pattern.
    """
    if prefetch not in (None, "cross_pass"):
        raise ValueError(
            f"prefetch={prefetch!r}: expected None or 'cross_pass' "
            f"(see repro.core.schedule.PREFETCH_MODES)")
    n_items = seg_start.shape[0]
    bm, bk = a_blocks.shape[1:]
    bn = b_blocks.shape[2]
    if b_blocks.shape[1] != bk:
        raise ValueError(
            f"contraction blocks disagree: a_blocks {tuple(a_blocks.shape)} "
            f"contracts over bk={bk} but b_blocks {tuple(b_blocks.shape)} "
            f"has row blocks of {b_blocks.shape[1]} — A tiles are (bm, bk), "
            f"so B tiles must be (bk, bn)")
    if n_c_blocks < 1 and n_items > 0:
        raise ValueError(
            f"n_c_blocks={n_c_blocks} with a non-empty schedule "
            f"(n_items={n_items}): every schedule item accumulates into a "
            f"symbolic C block, so the output needs at least one "
            f"(all-masked patterns short-circuit before the kernel — see "
            f"repro.api.executor)")
    if a_scales is not None and a_scales.shape not in (
            (a_blocks.shape[0],), (a_blocks.shape[0], bm)):
        raise ValueError(
            f"a_scales has shape {a_scales.shape}, expected one fp32 scale "
            f"per stored block ({a_blocks.shape[0]},) or per block row "
            f"({a_blocks.shape[0]}, {bm})")
    if b_scales is not None and b_scales.shape not in (
            (b_blocks.shape[0],), (b_blocks.shape[0], bk)):
        raise ValueError(
            f"b_scales has shape {b_scales.shape}, expected one fp32 scale "
            f"per stored block ({b_blocks.shape[0]},) or per block row "
            f"({b_blocks.shape[0]}, {bk})")
    validate_schedule_args(
        n_items, n_lanes, unroll,
        {"a_idx": a_idx, "b_idx": b_idx, "c_idx": c_idx,
         "seg_write": seg_write, "accum_prev": accum_prev, "valid": valid,
         "a_fetch": a_fetch, "b_fetch": b_fetch, "a_slot": a_slot,
         "b_slot": b_slot})
    lane_len = n_items // n_lanes
    quant_a = None if a_scales is None else (
        "rowwise" if a_scales.ndim == 2 else "block")
    quant_b = None if b_scales is None else (
        "rowwise" if b_scales.ndim == 2 else "block")
    out_shape = jax.ShapeDtypeStruct((n_c_blocks, bm, bn), out_dtype)

    depth = 2 * unroll
    n_steps = lane_len // unroll
    scalars = (a_idx, b_idx, c_idx, seg_start, seg_write, accum_prev,
               valid, a_fetch, b_fetch, a_slot, b_slot)
    in_specs = [pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [a_blocks, b_blocks]
    # (steps, 1, unroll) per-block scales: see segment_spmm for the layout
    scale_spec = pl.BlockSpec(
        (1, 1, unroll), lambda l, s, *rest: (l * n_steps + s, 0, 0))

    def row_spec(rows):
        return pl.BlockSpec(
            (1, unroll, rows), lambda l, s, *rest: (l * n_steps + s, 0, 0))

    if quant_a == "block":
        in_specs.append(scale_spec)
        operands.append(jnp.take(a_scales, a_idx).reshape(-1, 1, unroll))
    elif quant_a == "rowwise":
        in_specs.append(row_spec(bm))
        operands.append(
            jnp.take(a_scales, a_idx, axis=0).reshape(-1, unroll, bm))
    if quant_b == "block":
        in_specs.append(scale_spec)
        operands.append(jnp.take(b_scales, b_idx).reshape(-1, 1, unroll))
    elif quant_b == "rowwise":
        in_specs.append(row_spec(bk))
        operands.append(
            jnp.take(b_scales, b_idx, axis=0).reshape(-1, unroll, bk))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(n_lanes, n_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, bm, bn),
            lambda l, s, ai, bi, ci, *rest: (
                ci[l * lane_len + s * unroll], 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((depth, bm, bk), a_blocks.dtype),
            pltpu.VMEM((depth, bk, bn), b_blocks.dtype),
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SemaphoreType.DMA((depth,)),
        ],
    )
    kernel = _make_pipeline_kernel(lane_len, unroll, masked, quant_a, quant_b)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name=kernel_name("segment_spgemm", quant_a=quant_a,
                         quant_b=quant_b),
    )(*scalars, *operands)

