"""Plan execution: backend dispatch + the one differentiable matmul path.

``execute_plan`` runs a :class:`~repro.api.plan.SegmentPlan` on any backend
(compiled Pallas, Pallas interpret, or the pure-jnp reference oracle).
``apply_plan`` is the trainable entry point: a ``custom_vjp`` lifted out of
the old ``models/sparse_ffn.py`` so serving and training share one executor —

* forward:  ``y = W @ x``   (lane-parallel Segment SpMM under the plan's
  schedule; block values read in original BSR storage order via the
  ``slot_idx`` prefetch array);
* ``dx = Wᵀ @ dy``          — another Segment SpMM under the plan's nested
  transposed schedule (``plan.grad_plan``, built once, static), executed in
  the kernel's ``transpose_lhs`` mode against the *forward* weight array —
  no transposed or gathered copy of W is ever materialized;
* ``dW[s] = dy[rowₛ] @ x[colₛ]ᵀ`` — block-sampled SDDMM, pure jnp, emitted
  directly in storage order via ``a_brow``/``a_bcol``.

The N-tile width is normalized in one place (:func:`pick_bn`): the executor
either shrinks ``bn`` to the largest divisor of N or pads N up to a tile
multiple and slices the result — arbitrary N is legal (the old
``SpmmPlan.__call__`` crashed on any N not divisible by the tile width).
On the compiled ``pallas`` backend the tile is always a multiple of the
128-wide lane dimension (Mosaic refuses narrower B/C windows), so a decode
batch of N = 8 runs as one padded 128-wide tile.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import ref
from repro.kernels.segment_spgemm import segment_spgemm
from repro.kernels.segment_spmm import segment_spmm

from .backends import (LANE, backend_interpret_flag, check_block_shape,
                       resolve_backend)
from .plan import SPGEMM, SPMM, SegmentPlan


def pick_bn(n: int, bn: int, *, align: int = 1) -> Tuple[int, int]:
    """Normalize the N-tile width for an ``(…, N)`` right-hand side.

    Returns ``(bn_eff, pad)`` with ``(n + pad) % bn_eff == 0``.  Prefers the
    largest divisor of ``n`` that is ≤ ``bn`` when it keeps tiles reasonably
    wide (at least half the request, or the full lane width); otherwise keeps
    the requested width and zero-pads N (padded C columns are sliced off).

    ``align > 1`` makes ``bn_eff`` a multiple of ``align`` (the compiled
    backend passes :data:`~repro.api.backends.LANE`): N is first padded up
    to a multiple of ``align`` and the same rule runs in units of ``align``.
    """
    if align > 1:
        units = -(-n // align)
        bu = max(1, min(bn // align, units))
        if units % bu:
            div = max(d for d in range(1, bu + 1) if units % d == 0)
            bu = div if 2 * div >= bu else bu
        return bu * align, -(-units // bu) * bu * align - n
    bn = max(1, min(bn, n))
    if n % bn == 0:
        return bn, 0
    div = max(d for d in range(1, bn + 1) if n % d == 0)
    if div >= max(bn // 2, min(128, n)):
        return div, 0
    return bn, (-n) % bn


def _resolve_bn(plan: SegmentPlan, bn: Optional[int]) -> int:
    """Executor N-tile width: explicit argument > the plan's tuned
    ``bn_hint`` (recorded by the ``repro.tune`` search) > the default 512."""
    if bn is not None:
        return bn
    hint = getattr(plan, "bn_hint", None)
    return int(hint) if hint else 512


def _mask_dead_rows(plan: SegmentPlan, out: jax.Array) -> jax.Array:
    # block rows with no nonzero A blocks are never visited by the grid —
    # their output is undefined (may be NaN); zero them via where.
    row_blk = plan.block_shape[0]
    live = jnp.repeat(plan.row_mask > 0, row_blk)[:, None]
    return jnp.where(live, out, jnp.zeros((), out.dtype))


def _run_spmm(plan: SegmentPlan, x: jax.Array, *, backend: str,
              blocks: Optional[jax.Array] = None,
              scales: Optional[jax.Array] = None, bn: int = 512,
              out_dtype=jnp.float32) -> jax.Array:
    """Execute an spmm plan (optionally with substituted block values).

    ``blocks`` are always the *stored* tiles (original BSR order); a
    ``transpose_lhs`` plan (the nested backward schedule) contracts along
    their row axis instead of copying a transposed array.  ``scales`` are
    the per-block dequantization factors when ``blocks`` is a quantized
    payload (the nested backward plan carries none of its own — the caller
    threads the forward plan's).
    """
    blocks = plan.lhs_blocks if blocks is None else blocks
    scales = plan.lhs_scales if scales is None else scales
    gm, gk = plan.grid
    bm, bk = blocks.shape[1], blocks.shape[2]
    contract_blk = bm if plan.transpose_lhs else bk
    if x.ndim != 2 or x.shape[0] != gk * contract_blk:
        raise ValueError(f"rhs must be (K={gk * contract_blk}, N) dense, "
                         f"got {x.shape}")
    if backend == "reference":
        if plan.transpose_lhs:
            # a_brow/a_bcol describe the *forward* storage; its grid is the
            # plan's grid reversed.
            out = ref.spmm_ref(blocks, plan.a_brow, plan.a_bcol,
                               plan.grid[1], plan.grid[0], x,
                               transpose_lhs=True, scales=scales)
        else:
            out = ref.spmm_ref(blocks, plan.a_brow, plan.a_bcol, gm, gk, x,
                               scales=scales)
        obs.report_spmm(x.shape[1], x.shape[1])
        return out.astype(out_dtype)
    check_block_shape(blocks.shape[1:], backend)
    n = x.shape[1]
    bn_eff, pad = pick_bn(n, bn, align=LANE if backend == "pallas" else 1)
    obs.report_spmm(n, n + pad)
    xp = jnp.pad(x, ((0, 0), (0, pad))) if pad else x
    out = segment_spmm(
        blocks, plan.slot_idx, plan.m_idx, plan.k_idx, plan.seg_start,
        plan.seg_write, plan.accum_prev, plan.valid, xp, grid_m=gm,
        n_lanes=plan.n_lanes, bn=bn_eff, unroll=plan.unroll,
        transpose_lhs=plan.transpose_lhs,
        # mask exactly when the schedule carries valid=0 items — lane count
        # and unroll are the wrong proxy: a single-lane unroll=1 schedule
        # can legally carry pads (custom policies, hand-extended plans),
        # and a multi-lane schedule that packs perfectly has none
        masked=plan.has_pads,
        interpret=backend_interpret_flag(backend), out_dtype=out_dtype,
        a_scales=scales, a_fetch=plan.a_fetch, b_fetch=plan.b_fetch,
        a_slot=plan.a_slot, b_slot=plan.b_slot,
        prefetch=getattr(plan, "prefetch", None))
    if pad:
        out = out[:, :n]
    return _mask_dead_rows(plan, out)


def _run_spgemm(plan: SegmentPlan, *, backend: str,
                out_dtype=jnp.float32) -> jax.Array:
    if plan.n_out_blocks == 0:
        # all-masked symbolic pattern (no A column meets a B row): the grid
        # would be empty — return the empty C block array directly.
        bm = plan.block_shape[0]
        bn = plan.rhs_blocks.shape[2]
        return jnp.zeros((0, bm, bn), out_dtype)
    if backend == "reference":
        out = ref.spgemm_ref(
            plan.lhs_blocks, plan.a_brow, plan.a_bcol, plan.grid,
            plan.rhs_blocks, plan.b_brow, plan.b_bcol, plan.rhs_grid,
            plan.c_brow_arr, plan.c_bcol_arr,
            a_scales=plan.lhs_scales, b_scales=plan.rhs_scales)
        return out.astype(out_dtype)
    check_block_shape(plan.lhs_blocks.shape[1:], backend)
    check_block_shape(plan.rhs_blocks.shape[1:], backend)
    return segment_spgemm(
        plan.lhs_blocks, plan.rhs_blocks, plan.a_idx, plan.b_idx, plan.c_idx,
        plan.seg_start, plan.seg_write, plan.accum_prev, plan.valid,
        n_c_blocks=plan.n_out_blocks, n_lanes=plan.n_lanes,
        unroll=plan.unroll,
        masked=plan.has_pads,   # see _run_spmm: pads, not lanes/unroll
        interpret=backend_interpret_flag(backend), out_dtype=out_dtype,
        a_scales=plan.lhs_scales, b_scales=plan.rhs_scales,
        a_fetch=plan.a_fetch, b_fetch=plan.b_fetch,
        a_slot=plan.a_slot, b_slot=plan.b_slot,
        prefetch=getattr(plan, "prefetch", None))


def execute_plan(plan: SegmentPlan, rhs=None, *, bn: Optional[int] = None,
                 backend: Optional[str] = None, out_dtype=None,
                 verify=None) -> jax.Array:
    """Forward-only plan execution (``plan(...)`` delegates here).

    ``bn`` resolution order: explicit argument > the plan's tuned
    ``bn_hint`` (set by the :mod:`repro.tune` search) > 512.
    Backend resolution order: explicit argument > ``plan.backend`` > the
    process default (:func:`repro.api.backends.default_backend`).
    ``out_dtype`` resolves the same way: explicit argument >
    ``plan.out_dtype`` (set via ``plan_matmul(..., out_dtype=...)``) >
    float32.  Accumulation is always fp32; the dtype only affects the
    written output tiles.

    ``verify`` (``True``/``"fast"``/``"full"``) runs the static schedule
    verifier before any kernel launches and raises
    :class:`~repro.analysis.PlanVerificationError` on a finding — the
    debug hook for hand-edited or externally-deserialized plans (planner
    output is better verified once via ``plan_matmul(..., verify=...)``,
    which amortizes through the plan cache).

    The call is the host span ``segfold.execute``; inside it,
    ``segfold.execute.launch`` covers the kernel call until it returns
    (dispatch, not completion), and the rest is the host's own resolution.
    """
    with obs.span("execute"):
        if verify:
            from repro.analysis.invariants import verify_plan
            level = "fast" if verify is True else verify
            verify_plan(plan, level=level).raise_if_findings()
        backend = resolve_backend(backend if backend is not None
                                  else plan.backend)
        bn = _resolve_bn(plan, bn)
        if out_dtype is None:
            out_dtype = plan.out_dtype
        out_dtype = jnp.float32 if out_dtype is None else jnp.dtype(out_dtype)
        if plan.kind == SPMM:
            if rhs is None:
                raise ValueError("spmm plan needs a dense right-hand side")
            with obs.span("execute.launch"):
                return _run_spmm(plan, rhs, backend=backend, bn=bn,
                                 out_dtype=out_dtype)
        if plan.kind == SPGEMM:
            if rhs is not None:
                raise ValueError("spgemm plan takes no right-hand side "
                                 "(B is frozen into the plan)")
            with obs.span("execute.launch"):
                return _run_spgemm(plan, backend=backend,
                                   out_dtype=out_dtype)
        raise ValueError(f"unknown plan kind {plan.kind!r}")


# ---------------------------------------------------------------------------
# Differentiable path (custom VJP over the plan pytree)
# ---------------------------------------------------------------------------


def _zero_cotangent(tree):
    """Structure-matching zero cotangent: float0 for integer leaves."""
    def z(leaf):
        if jnp.issubdtype(jnp.result_type(leaf), jnp.inexact):
            return jnp.zeros_like(leaf)
        return np.zeros(np.shape(leaf), jax.dtypes.float0)
    return jax.tree_util.tree_map(z, tree)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _apply(backend: str, bn: int, plan: SegmentPlan, x: jax.Array):
    out = _run_spmm(plan, x, backend=backend, bn=bn, out_dtype=jnp.float32)
    return out.astype(x.dtype)


def _apply_fwd(backend, bn, plan, x):
    return _apply(backend, bn, plan, x), (plan, x)


def _apply_bwd(backend, bn, res, dy):
    plan, x = res
    g = plan.grad_plan
    if g is None:
        raise ValueError("plan was built without with_grad=True; "
                         "no transposed schedule available for the backward "
                         "pass — rebuild via plan_matmul(..., with_grad=True)")
    dyf = dy.astype(jnp.float32)
    # dx = Wᵀ @ dy under the transposed schedule; the grad plan's slot_idx
    # addresses the forward weight storage (payload + scales for quantized
    # plans) and the kernel contracts along block rows (transpose_lhs) —
    # zero copies of W.
    dx = _run_spmm(g, dyf, backend=backend, blocks=plan.lhs_blocks,
                   scales=plan.lhs_scales, bn=bn,
                   out_dtype=jnp.float32).astype(x.dtype)
    dplan = _zero_cotangent(plan)
    if not plan.quantized:
        # dW[s] = dy[brow_s·bm:(brow_s+1)·bm] @ x[bcol_s·bk:(bcol_s+1)·bk]ᵀ —
        # block SDDMM, emitted directly in the plan's (original BSR) storage
        # order via the stored block coordinates.  Quantized payloads are
        # frozen inference storage: their cotangent stays the symbolic zero
        # (float0 for int8) — gradients still flow to x.
        bm, bk = plan.block_shape
        gm, gk = plan.grid
        dyb = dyf.reshape(gm, bm, -1)
        xb = x.astype(jnp.float32).reshape(gk, bk, -1)
        dW = jnp.einsum("imn,ikn->imk", dyb[plan.a_brow], xb[plan.a_bcol])
        dplan = dplan.replace(lhs_blocks=dW.astype(plan.lhs_blocks.dtype))
    return dplan, dx


_apply.defvjp(_apply_fwd, _apply_bwd)


def apply_plan(plan: SegmentPlan, x: jax.Array, *, bn: Optional[int] = None,
               backend: Optional[str] = None) -> jax.Array:
    """Differentiable ``y = W @ x`` for an spmm plan (``x``: ``(K, N)``).

    Gradients flow to ``plan.lhs_blocks`` (the trainable block values, in
    original BSR storage order) and to ``x``; all schedule/index leaves get
    symbolic-zero cotangents.  Requires the plan to carry a ``grad_plan``
    (built by ``plan_matmul(..., with_grad=True)``).  ``bn=None`` resolves
    like :func:`execute_plan`: the plan's tuned ``bn_hint``, else 512.
    """
    if plan.kind != SPMM:
        raise ValueError("apply_plan supports spmm plans; execute spgemm "
                         "plans via plan() / execute_plan")
    backend = resolve_backend(backend if backend is not None else plan.backend)
    return _apply(backend, _resolve_bn(plan, bn), plan, x)
