"""Segment-scheduled block-sparse × dense matmul (BSR(A) @ B) — Pallas TPU.

The TPU realization of the paper's dynamic dataflow for sparse-weight
layers.  The kernel runs a **lane-parallel work list** of nonzero A-block
multiplies whose *order is the reuse mechanism*, and moves its operands
through an **explicit double-buffered DMA pipeline**: A and B live in HBM
(``pl.ANY`` refs) and the kernel issues ``pltpu.make_async_copy`` for
item *i+1*'s tiles into a ``2·unroll``-slot VMEM ring buffer while item *i*
runs on the MXU, waiting on a copy only at consumption — the SpArch-style
fetch/merge overlap, scheduled ahead of time instead of reactively:

* per-item ``a_fetch``/``b_fetch`` flags (precomputed by
  ``repro.core.schedule.fetch_flags`` from the same schedule the traffic
  model prices — predicted fetch counts are kernel reality by construction)
  gate every copy: consecutive items sharing ``k`` (SELECTA's row-wise
  intersection, boundary-chained between segments) skip the B re-fetch and
  read the resident ring slot, lane-padding no-ops move no data, and a
  lane's first item always fetches (lane cuts break residency);
* ``a_slot``/``b_slot`` give each item's resident ring slot — the ring
  advances one slot per *fetch*, so a reused tile is always the most
  recently copied one and an in-flight copy never lands on a slot that is
  still being read;
* consecutive items with the same output block row ``m`` accumulate the C
  tile in VMEM and write it back once per segment (output revisiting);
  folded segments re-enter with ``accum_prev=1`` and read-modify-write the
  C tile — the temporal-fold partial-sum merge.

Grid: ``(n_lanes, n_tiles_n, lane_len // unroll)``.  The lane axis is
**parallel** — the schedule is cut into load-balanced lanes at segment-chain
boundaries (``repro.core.schedule.partition_lanes``), so independent output
chains run concurrently (megacore / multi-core).  The item axis stays
innermost/sequential so segment accumulation is ordered and the pipeline's
issue-one-step-ahead discipline holds; each grid step executes ``unroll``
items against the resident ring slots.

A blocks stay in **original BSR storage order**: the scalar-prefetched
``slot_idx`` addresses each item's tile directly in HBM (the IPM analogue —
exact positions ahead of time), so no schedule-order gather of the block
values ever happens.  ``transpose_lhs`` contracts along the block's row axis
instead, computing ``Aᵀ`` tiles from the same storage — the backward pass
reads the forward weight array with zero copies.

Scalar-prefetch operands (``PrefetchScalarGridSpec``) carry the schedule:
``slot_idx, m_idx, k_idx, seg_start, seg_write, accum_prev, valid,
a_fetch, b_fetch, a_slot, b_slot`` (``valid=0`` marks lane-padding no-ops
whose contribution is masked out).  For quantized block storage the
per-block fp32 scales are gathered per item and ride a regular VMEM operand
blocked per grid step — one vector load per step instead of ``unroll``
serialized SMEM scalar reads — and are applied to the fp32 accumulator
(dequantization is a kernel-local concern; storage format never leaks into
the schedule).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _make_pipeline_kernel(lane_len: int, unroll: int, transpose_lhs: bool,
                          masked: bool, quant: str | None, contract_blk: int,
                          bn: int, prefetch: str | None = None):
    contract = (((0,), (0,)), ((), ())) if transpose_lhs \
        else (((1,), (0,)), ((), ()))

    def _kernel(slot_idx, m_idx, k_idx, seg_start, seg_write, accum_prev,
                valid, a_fetch, b_fetch, a_slot, b_slot, *refs):
        a_hbm, b_hbm, refs = refs[0], refs[1], refs[2:]
        if quant is not None:
            scale_ref, refs = refs[0], refs[1:]
        out, acc, a_buf, b_buf, a_sem, b_sem = refs
        # grid coordinates are read once here: pl.program_id must not be
        # bound inside a pl.when branch (interpret mode only substitutes it
        # in the top-level kernel jaxpr) — statically enforced by
        # repro.analysis.jaxpr_lint's program-id-in-when rule in CI
        j = pl.program_id(1)
        s = pl.program_id(2)
        n_tiles_n = pl.num_programs(1)
        n_steps = pl.num_programs(2)
        lane_base = pl.program_id(0) * lane_len
        base = lane_base + s * unroll

        # The copy descriptors are reconstructed identically at issue and
        # wait time — Pallas pairs them through the per-slot DMA semaphore.
        def a_copy(i, slot):
            return pltpu.make_async_copy(
                a_hbm.at[slot_idx[i]], a_buf.at[slot], a_sem.at[slot])

        def b_copy(i, slot, jj):
            # jj is the N-tile the copy serves: the grid's own j everywhere
            # except the cross-pass tail, which fills for tile j + 1.  Waits
            # always run in the target pass, so the descriptor reconstructed
            # there (with jj == j) matches the one started here.
            return pltpu.make_async_copy(
                b_hbm.at[pl.ds(k_idx[i] * contract_blk, contract_blk),
                         pl.ds(jj * bn, bn)],
                b_buf.at[slot], b_sem.at[slot])

        def issue_a(i):
            @pl.when(a_fetch[i] == 1)
            def _():
                a_copy(i, a_slot[i]).start()

        def issue_b(i, jj):
            @pl.when(b_fetch[i] == 1)
            def _():
                b_copy(i, b_slot[i], jj).start()

        # Every step issues the *next* step's copies before touching its own
        # tiles: the DMA engine fills the other ring slots while the MXU
        # contracts the resident ones.  Issue order is the DMA priority
        # mechanism — the bulky B row-tiles (contract_blk × bn) are put on
        # the queue before the small A tiles at every grid step, so the
        # copies on the critical path start first
        # (repro.analysis.order's dma-priority rule asserts this order).
        # The pass prologue fetches the first step's own items; a lane's
        # first item always has its fetch flags set, so nothing stale
        # survives a pass restart.  Under cross-pass prefetch the tail of
        # the previous pass already issued those copies, so the prologue
        # only runs for the very first pass (j == 0).
        first_step = (s == 0) & (j == 0) if prefetch == "cross_pass" \
            else (s == 0)

        @pl.when(first_step)
        def _prologue_b():
            for g in range(unroll):
                issue_b(lane_base + g, j)

        @pl.when(s + 1 < n_steps)
        def _pipeline_b():
            for g in range(unroll):
                issue_b(base + unroll + g, j)

        @pl.when(first_step)
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
                def _load():    # folded continuation: merge with prior partial
                    acc[...] = out[...].astype(jnp.float32)

                @pl.when(accum_prev[i] == 0)
                def _zero():
                    acc[...] = jnp.zeros_like(acc)

            # Wait only at consumption, only when this item actually fetched
            # — a reused tile's copy was already awaited by the item that
            # brought it in.
            @pl.when(a_fetch[i] == 1)
            def _wait_a(i=i):
                a_copy(i, a_slot[i]).wait()

            @pl.when(b_fetch[i] == 1)
            def _wait_b(i=i):
                b_copy(i, b_slot[i], j).wait()

            a_tile = a_buf[a_slot[i]].astype(jnp.float32)
            if quant == "rowwise":
                # Per-row scales do NOT commute with a contraction over the
                # tile's row axis (transpose_lhs), so the tile is dequantized
                # *before* the dot — exact in both orientations.  The step's
                # (unroll, bm) scale rows arrive as one VMEM window (gathered
                # through slot_idx at call time).
                a_tile = a_tile * scale_ref[0, g][:, None]
            contrib = jax.lax.dot_general(
                a_tile,
                b_buf[b_slot[i]].astype(jnp.float32),
                dimension_numbers=contract,
                preferred_element_type=jnp.float32)
            if quant == "block":
                # Per-block scale is a scalar factor of the whole tile, so
                # applying it to the fp32 product (after the MXU dot) is
                # algebraically exact: (s·Aq) @ B == s · (Aq @ B).  The
                # step's scales arrive as one VMEM vector (gathered through
                # slot_idx at call time) — no per-item SMEM scalar loads.
                # The (1, 1) lane slice broadcasts over the tile.
                contrib = contrib * scale_ref[0, :, g:g + 1]
            if masked:
                contrib = jnp.where(valid[i] == 1, contrib, 0.0)
            acc[...] += contrib

            @pl.when(seg_write[i] == 1)
            def _write(i=i):
                out[...] = acc[...].astype(out.dtype)

        if prefetch == "cross_pass":
            # Cross-pass tail: the last step of pass j issues pass j + 1's
            # first copies while this pass's final contractions retire, so
            # the next pass never drains the pipeline.  Placement at the
            # *end* of the body matters — the lane-first ring slots may
            # still be read by this very step (an all-same-k lane reuses
            # slot 0 throughout), so the overwriting copies must start
            # after this step's consumption.  B row-tiles first (DMA
            # priority), for tile j + 1; A tiles are N-independent but
            # their ring slots were recycled during this pass, so they are
            # re-fetched exactly as a drained prologue would.
            # repro.analysis.order's cross-pass-war / sem-carryover /
            # prefetch-raw rules certify this tail hazard-free for every
            # shipped variant before CI lets it execute.
            tail = (s + 1 == n_steps) & (j + 1 < n_tiles_n)

            @pl.when(tail)
            def _tail_b():
                for g in range(unroll):
                    issue_b(lane_base + g, j + 1)

            @pl.when(tail)
            def _tail_a():
                for g in range(unroll):
                    issue_a(lane_base + g)

    return _kernel


def kernel_name(op: str, *, transpose_lhs: bool = False,
                quant_a: str | None = None, quant_b: str | None = None,
                prefetch: str | None = None) -> str:
    """The ``pallas_call`` name of one kernel variant: the op, then
    ``pipeline``, ``tlhs`` for ``transpose_lhs``, ``qa<mode>`` / ``qb<mode>``
    for a quantized A / B operand and ``xpass`` for cross-pass prefetch
    (``segment_spmm_pipeline_qablock``).  It names the kernel's custom call
    in the compiled program, and so its profiler events."""
    parts = [op, "pipeline"]
    if transpose_lhs:
        parts.append("tlhs")
    if quant_a is not None:
        parts.append("qa" + quant_a)
    if quant_b is not None:
        parts.append("qb" + quant_b)
    if prefetch == "cross_pass":
        parts.append("xpass")
    return "_".join(parts)


def validate_schedule_args(n_items, n_lanes, unroll, arrays):
    """Shared scalar-prefetch schedule validation for both Segment kernels."""
    for name, arr in arrays.items():
        if arr is None:
            raise ValueError(
                f"{name} is missing: the Segment kernels need every schedule "
                f"array (precompute the fetch arrays via "
                f"repro.core.schedule.fetch_flags, or build the schedule "
                f"through repro.api.plan_matmul)")
        if arr.shape != (n_items,):
            raise ValueError(
                f"{name} has shape {arr.shape}, expected ({n_items},) to "
                f"match the schedule's n_items (seg_start length)")
    if n_items % n_lanes != 0:
        raise ValueError(f"n_items={n_items} is not divisible by "
                         f"n_lanes={n_lanes}; lanes must be equal length "
                         f"(pad via partition_lanes)")
    if (n_items // n_lanes) % unroll != 0:
        raise ValueError(f"lane length {n_items // n_lanes} is not divisible "
                         f"by unroll={unroll}")


@functools.partial(
    jax.jit,
    static_argnames=("grid_m", "n_lanes", "bn", "unroll", "transpose_lhs",
                     "masked", "interpret", "out_dtype", "prefetch"))
def segment_spmm(a_blocks, slot_idx, m_idx, k_idx, seg_start, seg_write,
                 accum_prev, valid, b_dense, *, grid_m: int, n_lanes: int = 1,
                 bn: int = 512, unroll: int = 1, transpose_lhs: bool = False,
                 masked: bool = True, interpret: bool = False,
                 out_dtype=jnp.float32, a_scales=None, a_fetch=None,
                 b_fetch=None, a_slot=None, b_slot=None,
                 prefetch: str | None = None):
    """Compute ``C = BSR(A) @ B`` (or ``BSR(A)ᵀ @ B``) under a lane-parallel
    Segment schedule with an explicit double-buffered DMA pipeline.

    Args:
      a_blocks: (n_blocks, bm, bk) A tiles in **original BSR storage order**.
        May be a quantized payload (int8 / fp8) — pass ``a_scales``.
      slot_idx: (n_items,) int32 — per-item index into ``a_blocks``.
      m_idx/k_idx: (n_items,) int32 output/contraction block coordinates,
        flattened lane-major schedule order.
      seg_start/seg_write/accum_prev/valid: (n_items,) int32 schedule flags
        (``valid=0`` on lane-padding no-ops).
      b_dense: (K, N) dense right-hand side; K = grid_k * bk (bm when
        ``transpose_lhs``).
      grid_m: number of output block rows.
      n_lanes: parallel lanes; ``n_items`` must be ``n_lanes * lane_len``.
      bn: N-tile width.  The VMEM working set this implies is computed by
        :func:`repro.analysis.spmm_vmem_bytes` (the analyzer's budget is
        pinned byte-for-byte to this kernel's scratch + block windows by
        ``tests/test_kernel_analysis.py``, so consult it rather than a
        hand-derived formula; the planner's ``vmem_limit_bytes`` knob
        enforces it at plan time).
      unroll: items executed per grid step (scheduler must have aligned
        segment chains to ``unroll``).
      transpose_lhs: contract along each A tile's row axis (``Aᵀ @ B``) —
        the backward pass reads forward storage directly.
      masked: skip the validity mask when the schedule has no pads.
      a_scales: fp32 dequantization scales, or None for fp32 blocks.
        ``(n_blocks,)`` applies one scale per block to the fp32 product;
        ``(n_blocks, bm)`` (rowwise mode) dequantizes each A tile row
        *before* the dot, which stays exact under ``transpose_lhs``.
        Gathered per item and streamed as a per-step VMEM window.
      a_fetch/b_fetch: (n_items,) int32 DMA fetch flags — 1 where the item
        must copy its A tile / B row-tile from HBM, 0 where the resident
        ring slot is reused (see ``repro.core.schedule.fetch_flags``).
      a_slot/b_slot: (n_items,) int32 resident ring-buffer slot per item.
      prefetch: ``None`` drains the DMA pipeline at every (lane, N-tile)
        pass boundary; ``"cross_pass"`` issues pass ``j+1``'s first copies
        (B row-tiles before A tiles) during pass ``j``'s tail step, so a
        multi-N-tile grid never stalls on a pass restart.  The mode changes
        only *when* lane-first copies issue, never which items fetch, so
        results are bit-identical.
        Certified hazard-free per variant by ``repro.analysis.order``.
    Returns:
      (grid_m * row_block, N) dense output.
    """
    if prefetch not in (None, "cross_pass"):
        raise ValueError(
            f"prefetch={prefetch!r}: expected None or 'cross_pass' "
            f"(see repro.core.schedule.PREFETCH_MODES)")
    _, bm, bk = a_blocks.shape
    if a_scales is not None and a_scales.shape not in (
            (a_blocks.shape[0],), (a_blocks.shape[0], bm)):
        raise ValueError(
            f"a_scales has shape {a_scales.shape}, expected one fp32 scale "
            f"per stored block ({a_blocks.shape[0]},) or per block row "
            f"({a_blocks.shape[0]}, {bm})")
    row_blk, contract_blk = (bk, bm) if transpose_lhs else (bm, bk)
    k_dim, n_dim = b_dense.shape
    if k_dim % contract_blk != 0:
        raise ValueError(f"rhs K={k_dim} is not a multiple of the "
                         f"contraction block {contract_blk} "
                         f"(a_blocks {a_blocks.shape}, "
                         f"transpose_lhs={transpose_lhs})")
    if n_dim % bn != 0:
        raise ValueError(
            f"dense rhs width N={n_dim} (b_dense shape {b_dense.shape}) is "
            f"not divisible by the N-tile width bn={bn}; pad N or pick a "
            f"divisor (see repro.api.pick_bn)")
    validate_schedule_args(
        seg_start.shape[0], n_lanes, unroll,
        {"slot_idx": slot_idx, "m_idx": m_idx, "k_idx": k_idx,
         "seg_write": seg_write, "accum_prev": accum_prev, "valid": valid,
         "a_fetch": a_fetch, "b_fetch": b_fetch, "a_slot": a_slot,
         "b_slot": b_slot})
    n_items = seg_start.shape[0]
    lane_len = n_items // n_lanes
    n_tiles_n = n_dim // bn
    quant = None if a_scales is None else (
        "rowwise" if a_scales.ndim == 2 else "block")
    out_shape = jax.ShapeDtypeStruct((grid_m * row_blk, n_dim), out_dtype)

    depth = 2 * unroll
    n_steps = lane_len // unroll
    scalars = (slot_idx, m_idx, k_idx, seg_start, seg_write, accum_prev,
               valid, a_fetch, b_fetch, a_slot, b_slot)
    in_specs = [pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [a_blocks, b_dense]
    if quant == "block":
        # one fp32 scale per item, laid out per grid step — the kernel reads
        # its step's scales as a single VMEM vector.  The unit middle axis
        # makes the block's last two dims equal the array's, which is what
        # Mosaic's (8, 128) tiling rule accepts for a (1, unroll) window.
        scale_items = jnp.take(a_scales, slot_idx).reshape(-1, 1, unroll)
        in_specs.append(pl.BlockSpec(
            (1, 1, unroll), lambda l, j, s, *rest: (l * n_steps + s, 0, 0)))
        operands.append(scale_items)
    elif quant == "rowwise":
        # one (bm,) scale row per item — the step's window is (unroll, bm)
        scale_items = jnp.take(a_scales, slot_idx,
                               axis=0).reshape(-1, unroll, bm)
        in_specs.append(pl.BlockSpec(
            (1, unroll, bm), lambda l, j, s, *rest: (l * n_steps + s, 0, 0)))
        operands.append(scale_items)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(n_lanes, n_tiles_n, n_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (row_blk, bn),
            lambda l, j, s, slot, m, *rest: (
                m[l * lane_len + s * unroll], j)),
        scratch_shapes=[
            pltpu.VMEM((row_blk, bn), jnp.float32),
            pltpu.VMEM((depth, bm, bk), a_blocks.dtype),
            pltpu.VMEM((depth, contract_blk, bn), b_dense.dtype),
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SemaphoreType.DMA((depth,)),
        ],
    )
    kernel = _make_pipeline_kernel(lane_len, unroll, transpose_lhs, masked,
                                   quant, contract_blk, bn, prefetch)
    # Under cross-pass prefetch the N-tile axis carries live DMA state
    # across its boundary (the tail's in-flight copies), so it must be
    # declared sequential — only the lane axis stays parallel.
    semantics = ("parallel", "arbitrary", "arbitrary") \
        if prefetch == "cross_pass" \
        else ("parallel", "parallel", "arbitrary")
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        name=kernel_name("segment_spmm", transpose_lhs=transpose_lhs,
                         quant_a=quant, prefetch=prefetch),
    )(*scalars, *operands)

