"""Static VMEM budgeting for the Pallas kernel variants.

Two faces of the same accounting:

* :func:`kernel_vmem_bytes` — derived from a traced kernel's
  :class:`~.accesses.KernelIR`: VMEM ``scratch_shapes`` at full size plus
  every BlockSpec-windowed operand at block size × 2 (Mosaic
  double-buffers blocked operands across grid steps); ``ANY``-space
  operands stay in HBM and SMEM prefetch / DMA semaphores are not VMEM.
* :func:`spmm_vmem_bytes` / :func:`spgemm_vmem_bytes` — closed-form
  formulas over the plan knobs (block shape, ``bn``, ``unroll``, dtypes),
  used by the planner's plan-time gate where no kernel has been traced
  yet.  ``tests/test_kernel_analysis.py`` pins the two faces equal
  byte-for-byte on every shipped variant, so the formulas cannot drift
  from the kernels the way the old hand-maintained docstring did.

The per-core limit default follows the TPU VMEM size (~16 MiB/core); a
knob combination that cannot fit raises :class:`VmemBudgetError` — a named
error at plan time, not an OOM at launch.

Rule id: ``vmem-budget``.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from .accesses import KernelIR
from .jaxpr_lint import LintFinding

RULE = "vmem-budget"

#: per-core VMEM capacity the budget is checked against by default (TPU
#: cores carry ~16 MiB of VMEM; see the accelerator notes in docs/API.md)
DEFAULT_VMEM_LIMIT_BYTES = 16 * 2 ** 20

#: Mosaic double-buffers BlockSpec-windowed operands across grid steps
_BLOCK_BUFFERS = 2

_ITEMSIZE_FALLBACK = {
    "bfloat16": 2,
    "float8_e4m3fn": 1, "float8_e4m3": 1, "float8_e5m2": 1,
    "float8_e4m3fnuz": 1, "float8_e5m2fnuz": 1,
}


class VmemBudgetError(ValueError):
    """A kernel variant's VMEM working set exceeds the per-core limit."""


def _itemsize(dtype) -> int:
    s = str(dtype)
    try:
        return int(np.dtype(s).itemsize)
    except TypeError:
        pass
    if s in _ITEMSIZE_FALLBACK:
        return _ITEMSIZE_FALLBACK[s]
    raise ValueError(f"unknown dtype for VMEM accounting: {dtype!r}")


def kernel_vmem_bytes(ir: KernelIR) -> Dict[str, int]:
    """Per-ref VMEM bytes of one traced kernel, plus a ``"total"`` entry."""
    out: Dict[str, int] = {}
    total = 0
    for ref in ir.refs:
        if ref.role == "scratch" and ref.memspace == "vmem":
            b = int(np.prod(ref.shape, dtype=np.int64)) * _itemsize(ref.dtype)
        elif (ref.role in ("input", "output") and ref.memspace == "blocked"
              and ref.block_shape is not None):
            b = (int(np.prod(ref.block_shape, dtype=np.int64))
                 * _itemsize(ref.dtype) * _BLOCK_BUFFERS)
        else:
            continue
        out[ref.name] = b
        total += b
    out["total"] = total
    return out


def check_vmem_budget(ir: KernelIR,
                      limit: int = DEFAULT_VMEM_LIMIT_BYTES
                      ) -> List[LintFinding]:
    """The ``vmem-budget`` rule: a finding when the traced kernel's working
    set exceeds ``limit`` bytes."""
    budget = kernel_vmem_bytes(ir)
    if budget["total"] <= limit:
        return []
    parts = ", ".join(f"{k}={v}" for k, v in sorted(budget.items())
                      if k != "total")
    return [LintFinding(
        rule=RULE,
        message=(f"VMEM working set {budget['total']} bytes exceeds the "
                 f"{limit}-byte per-core limit ({parts})"),
        kernel=ir.name)]


# ---------------------------------------------------------------------------
# closed-form budgets over the plan knobs (mirrors of the kernel layouts in
# kernels/segment_spmm.py and kernels/segment_spgemm.py — pinned equal to
# the traced totals by tests/test_kernel_analysis.py)
# ---------------------------------------------------------------------------


def spmm_vmem_bytes(*, bm: int, bk: int, bn: int, unroll: int,
                    transpose_lhs: bool = False,
                    block_dtype="float32", rhs_dtype="float32",
                    out_dtype="float32", quantized: bool = False,
                    rowwise: bool = False, pipelined: bool = True) -> int:
    """VMEM bytes of one ``segment_spmm`` kernel instance.

    Pipelined: ``acc(row·bn·4) + out window(row·bn·2) + A ring
    (2·unroll·bm·bk) + B ring (2·unroll·contract·bn)`` plus, when
    quantized, the per-step scale window — ``(1, 1, unroll)`` fp32 per-block,
    ``(1, unroll, bm)`` in rowwise mode.  Legacy: the BlockSpec
    auto-pipeline double-buffers ``unroll`` A tiles and ``unroll`` B
    stripes instead of the explicit rings (per-block scales ride the SMEM
    prefetch path there — no VMEM — but rowwise scale rows are ``unroll``
    windowed ``(1, bm)`` VMEM operands).
    """
    row_blk, contract_blk = (bk, bm) if transpose_lhs else (bm, bk)
    a_item = _itemsize(block_dtype)
    b_item = _itemsize(rhs_dtype)
    scale_elems = bm if rowwise else 1   # rowwise runs over storage rows
    total = row_blk * bn * 4                                     # acc
    total += row_blk * bn * _itemsize(out_dtype) * _BLOCK_BUFFERS  # out win
    if pipelined:
        depth = 2 * unroll
        total += depth * bm * bk * a_item                        # A ring
        total += depth * contract_blk * bn * b_item              # B ring
        if quantized:
            total += unroll * scale_elems * 4 * _BLOCK_BUFFERS   # scale win
    else:
        total += unroll * (1 * bm * bk) * a_item * _BLOCK_BUFFERS
        total += unroll * (contract_blk * bn) * b_item * _BLOCK_BUFFERS
        if quantized and rowwise:
            total += unroll * (1 * bm) * 4 * _BLOCK_BUFFERS
    return total


def spgemm_vmem_bytes(*, bm: int, bk: int, bn: int, unroll: int,
                      block_dtype="float32", rhs_dtype=None,
                      out_dtype="float32", quant_a: bool = False,
                      quant_b: bool = False, rowwise: bool = False,
                      pipelined: bool = True) -> int:
    """VMEM bytes of one ``segment_spgemm`` kernel instance (same
    accounting as :func:`spmm_vmem_bytes`, block×block operand streams;
    rowwise scale windows span A's ``bm`` rows and B's ``bk`` rows)."""
    a_item = _itemsize(block_dtype)
    b_item = _itemsize(rhs_dtype if rhs_dtype is not None else block_dtype)
    a_scale = bm if rowwise else 1
    b_scale = bk if rowwise else 1
    total = bm * bn * 4                                          # acc
    total += 1 * bm * bn * _itemsize(out_dtype) * _BLOCK_BUFFERS   # out win
    if pipelined:
        depth = 2 * unroll
        total += depth * bm * bk * a_item
        total += depth * bk * bn * b_item
        total += (int(quant_a) * a_scale
                  + int(quant_b) * b_scale) * unroll * 4 * _BLOCK_BUFFERS
    else:
        total += unroll * (1 * bm * bk) * a_item * _BLOCK_BUFFERS
        total += unroll * (1 * bk * bn) * b_item * _BLOCK_BUFFERS
        if rowwise:
            total += (int(quant_a) * a_scale + int(quant_b) * b_scale) \
                * unroll * 4 * _BLOCK_BUFFERS
    return total


#: plan ``block_dtype`` names → payload bytes per element (the plan stores
#: the short quantization mode, not a numpy dtype string)
_PLAN_DTYPE_BYTES = {"fp32": 4, "int8": 1, "fp8": 1,
                     "int8.rowwise": 1, "fp8.rowwise": 1}


def _plan_block_dtype(plan) -> str:
    name = str(getattr(plan, "block_dtype", "fp32") or "fp32")
    name = name.split(".", 1)[0]   # strip a scale-granularity suffix
    return {"fp32": "float32", "int8": "int8",
            "fp8": "float8_e4m3fn"}.get(name, name)


def plan_vmem_bytes(plan, *, bn: int = 512, pipelined: Optional[bool] = None
                    ) -> int:
    """Worst-case VMEM bytes across the kernel instances a ``SegmentPlan``
    will launch through the executor: the forward kernel plus, when the
    plan carries a gradient schedule, the transposed backward kernel.

    ``bn`` is the executor's N-tile width *after* ``pick_bn`` clamping —
    pass the effective value, not the raw knob.
    """
    bm, bk = plan.block_shape
    dt = _plan_block_dtype(plan)
    quantized = plan.lhs_scales is not None
    rowwise = quantized and getattr(plan.lhs_scales, "ndim", 1) == 2
    unroll = max(1, int(plan.unroll or 1))
    if pipelined is None:
        # a plan built with pipeline=False carries the fetch-flag leaves
        # (their contract is pipeline-independent) but executes the legacy
        # BlockSpec path — budget what the executor will actually launch
        pipelined = (plan.a_fetch is not None
                     and bool(getattr(plan, "pipeline", True)))
    if plan.kind == "spgemm":
        bn_eff = (plan.rhs_blocks.shape[2] if plan.rhs_blocks is not None
                  else bk)
        rhs_dt = (str(plan.rhs_blocks.dtype) if plan.rhs_blocks is not None
                  else dt)
        total = spgemm_vmem_bytes(
            bm=bm, bk=bk, bn=bn_eff, unroll=unroll, block_dtype=dt,
            rhs_dtype=rhs_dt,
            quant_a=quantized, quant_b=plan.rhs_scales is not None,
            rowwise=rowwise, pipelined=pipelined)
    else:
        total = spmm_vmem_bytes(bm=bm, bk=bk, bn=bn, unroll=unroll,
                                transpose_lhs=plan.transpose_lhs,
                                block_dtype=dt, quantized=quantized,
                                rowwise=rowwise, pipelined=pipelined)
    grad = plan.grad_plan
    if grad is not None:
        total = max(total, plan_vmem_bytes(grad, bn=bn, pipelined=pipelined))
    return total


def check_plan_vmem(plan, *, bn: int = 512,
                    limit: int = DEFAULT_VMEM_LIMIT_BYTES,
                    label: str = "plan") -> int:
    """Raise :class:`VmemBudgetError` when a plan's worst kernel instance
    cannot fit in ``limit`` bytes of VMEM; returns the computed bytes."""
    total = plan_vmem_bytes(plan, bn=bn)
    if total > limit:
        bm, bk = plan.block_shape
        raise VmemBudgetError(
            f"{label}: kernel VMEM working set {total} bytes exceeds the "
            f"{limit}-byte limit (block ({bm}, {bk}), bn={bn}, "
            f"unroll={getattr(plan, 'unroll', 1)}, "
            f"dtype={getattr(plan, 'block_dtype', 'float32')}); choose a "
            f"smaller bn/unroll/block or raise vmem_limit_bytes")
    return total
