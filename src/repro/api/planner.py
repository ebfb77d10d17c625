"""``plan_matmul`` — the front door: pattern → :class:`SegmentPlan`.

Planning is host-side numpy work (ordering, folding, lane partitioning,
finalization) that only depends on the *sparsity pattern*, not the block
values — so plans are cached by a pattern fingerprint and re-realized with
fresh values per call.  Static weight sparsity amortizes the scheduling cost
exactly as DESIGN.md §2 argues; the cache makes that amortization automatic
instead of manual.  Realization is **zero-copy**: block values ride along in
original BSR storage order and the schedule addresses them through a
``slot_idx`` scalar-prefetch array, so a cache hit never gathers O(nnz)
data on the host.

``plan_matmul(A, B_or_shape)`` dispatches on the right-hand side:

* ``BSR``                    → SpGEMM plan (B frozen into the plan);
* dense array / shape / int  → SpMM plan (the dense N is only a traffic
  hint; any dense rhs with matching K can be passed at execution time);
* ``with_grad=True``         → the plan additionally carries the transposed
  schedule (``grad_plan``) so :func:`repro.api.executor.apply_plan` can run
  the backward pass against the *forward* weight storage (the kernel's
  ``transpose_lhs`` mode — no transposed copy of W exists);
* ``n_lanes > 1``            → the schedule is split into load-balanced
  parallel lanes at segment-chain boundaries (see
  :func:`repro.core.schedule.partition_lanes`); ``unroll`` additionally
  groups items per grid step;
* ``quantize="int8"|"fp8"``  → block values are stored as a quantized
  payload + per-block fp32 scales (dequantized in-kernel at the fp32
  accumulator); the fingerprint carries the storage dtype, so quantized
  and fp32 plans of one pattern never collide in the cache.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.analysis.invariants import (PlanVerificationError, VerifyResult,
                                       check_scale_agreement, verify_plan)
from repro.core.formats import (BSR, QUANT_DTYPES, QUANT_MODES,
                                QuantizedBlocks, quant_base_dtype,
                                quant_is_rowwise, quantize_blocks)
from repro.core.policies import get_policy
from repro.core.schedule import (PREFETCH_MODES, LaneLayout,
                                 build_spgemm_schedule, build_spmm_schedule,
                                 fetch_flags, finalize_schedule, lane_select,
                                 lane_traffic_spgemm, lane_traffic_spmm,
                                 partition_lanes)

from .backends import LANE, check_block_shape, resolve_backend
from .plan import SPGEMM, SPMM, SegmentPlan


def _freeze_traffic(traffic: dict) -> Tuple[Tuple[str, float], ...]:
    return tuple(sorted(traffic.items()))


def _scale_spmm_traffic(basis: dict, n_cols: int) -> dict:
    """Re-price a unit-N traffic basis for a concrete dense width.

    A-tile bytes are N-independent; B and C bytes scale linearly with the
    dense column count (the basis is evaluated at ``n_cols=1``), so the
    *schedule* — and therefore the plan cache entry — never depends on N.
    """
    out = dict(basis)
    out["b_bytes"] = basis["b_bytes"] * n_cols
    out["c_bytes"] = basis["c_bytes"] * n_cols
    out["total"] = basis["a_bytes"] + out["b_bytes"] + out["c_bytes"]
    return out


def _pattern_bytes(h, m: BSR) -> None:
    h.update(np.asarray(m.shape, np.int64).tobytes())
    h.update(np.asarray(m.block_shape, np.int64).tobytes())
    h.update(np.ascontiguousarray(m.brow, np.int64).tobytes())
    h.update(np.ascontiguousarray(m.bcol, np.int64).tobytes())


def _bucket_hint(n: Optional[int]) -> Optional[int]:
    """Power-of-two ceiling bucket for the dense-N traffic hint.

    The *schedule* never depends on N, but the cached unit-N traffic basis
    is re-priced per realize and downstream consumers (the ``repro.tune``
    cost model, the plan-time VMEM gate's ``pick_bn`` clamp) read the
    realized numbers — so plans for wildly different widths must not share
    a cache identity.  Bucketing to the next power of two keeps nearby
    widths (e.g. 640 and 768 → 1024) on one entry while separating 64 from
    640."""
    if n is None:
        return None
    n = int(n)
    return 1 << max(0, (n - 1).bit_length())


def pattern_fingerprint(kind: str, policy_key: str, fold_len: Optional[int],
                        with_grad: bool, *mats: BSR, n_lanes: int = 1,
                        unroll: int = 1, block_dtype: str = "fp32",
                        n_bucket: Optional[int] = None,
                        bn_hint: Optional[int] = None,
                        prefetch: Optional[str] = None) -> str:
    """Digest of everything the *schedule* and the cached pricing depend on
    (never block values).  ``policy_key`` should include the policy's
    registration serial so re-registering a name under a different ordering
    can't be served a stale schedule.  ``block_dtype`` is part of the
    digest: a quantized plan carries scale leaves and dtype-scaled traffic
    that an fp32 plan of the same pattern must never be served.
    ``n_bucket`` is the *bucketed* dense-N hint (see :func:`_bucket_hint`)
    — the raw hint stays out so nearby widths share one template, but
    orders-of-magnitude-different widths no longer collide.  ``bn_hint``
    and ``prefetch`` are part of the key because they change the recorded
    traffic pricing and the executor behaviour baked into the template."""
    h = hashlib.sha1()
    h.update(f"{kind}|{policy_key}|{fold_len}|{with_grad}"
             f"|lanes={n_lanes}|unroll={unroll}"
             f"|dtype={block_dtype}|nbkt={n_bucket}"
             f"|bn={bn_hint}|pf={prefetch}".encode())
    for m in mats:
        _pattern_bytes(h, m)
    return h.hexdigest()


def _scale_fetch_bytes(block_dtype: str, rows: int) -> int:
    """fp32 scale bytes a quantized tile fetch drags along: one scalar per
    block, or one per block row in rowwise mode."""
    return (rows if quant_is_rowwise(block_dtype) else 1) * 4


def _quantize_a_traffic(basis: dict, block_dtype: str, bm: int,
                        bk: int) -> dict:
    """Re-price a traffic estimate's A-tile bytes for a quantized payload.

    An A fetch moves ``bm·bk`` payload bytes plus the fp32 scales (one per
    block, or ``bm`` per block in rowwise mode) instead of ``bm·bk`` fp32
    elements; B/C stay fp32 (the dense rhs and the fp32 accumulator output
    are not quantized)."""
    if block_dtype == "fp32":
        return basis
    itemsize = QUANT_DTYPES[quant_base_dtype(block_dtype)].itemsize
    out = dict(basis)
    out["a_bytes"] = basis["a_fetches"] * (
        bm * bk * itemsize + _scale_fetch_bytes(block_dtype, bm))
    out["total"] = out["a_bytes"] + out["b_bytes"] + out["c_bytes"]
    return out


def _quantize_spgemm_traffic(traffic: dict, block_dtype: str, bm: int,
                             bk: int, bn: int) -> dict:
    """Same re-pricing for SpGEMM, where both operands are quantized
    (B's rowwise scales run over its ``bk`` rows)."""
    if block_dtype == "fp32":
        return traffic
    itemsize = QUANT_DTYPES[quant_base_dtype(block_dtype)].itemsize
    out = dict(traffic)
    out["a_bytes"] = traffic["a_fetches"] * (
        bm * bk * itemsize + _scale_fetch_bytes(block_dtype, bm))
    out["b_bytes"] = traffic["b_fetches"] * (
        bk * bn * itemsize + _scale_fetch_bytes(block_dtype, bk))
    out["total"] = out["a_bytes"] + out["b_bytes"] + out["c_bytes"]
    return out


def _realize_values(blocks, block_dtype: str):
    """Device ``(payload, scales)`` for a plan's value leaves.

    fp32 plans upload the caller's buffer as-is (identity when it already
    lives on device).  Quantized plans accept either a pre-quantized
    :class:`~repro.core.formats.QuantizedBlocks` — payload + scales upload
    verbatim, the zero-copy path for weights quantized once at load time —
    or an fp32 array, quantized here per block (elementwise, storage order
    preserved: still no schedule-order gather)."""
    if isinstance(blocks, QuantizedBlocks):
        if blocks.dtype != block_dtype:
            raise ValueError(
                f"pre-quantized blocks are {blocks.dtype!r} but the plan "
                f"was requested with quantize={block_dtype!r}")
        return jnp.asarray(blocks.payload), jnp.asarray(blocks.scales)
    if block_dtype == "fp32":
        return jnp.asarray(blocks), None
    q = quantize_blocks(np.asarray(blocks), block_dtype)
    return jnp.asarray(q.payload), jnp.asarray(q.scales)


@dataclasses.dataclass
class _PlanTemplate:
    """A value-free plan; realization attaches fresh block values verbatim.

    There is deliberately no permutation here: the schedule addresses block
    storage through ``slot_idx``, so ``realize`` is a device upload of the
    caller's arrays (identity when they already live on device) — never an
    O(nnz) gather.  Traffic is stored as a unit-N basis and re-priced per
    realize so one template serves every dense width of the same pattern."""

    plan: SegmentPlan                           # lhs/rhs_blocks are None
    traffic_basis: Optional[dict] = None        # spmm fwd, at n_cols=1
    grad_traffic_basis: Optional[dict] = None   # spmm bwd, at n_cols=1
    verified_level: Optional[str] = None        # deepest verify_plan run yet

    def realize(self, a: BSR, b: Optional[BSR], backend: Optional[str],
                n_cols_hint: int, out_dtype: Optional[str]) -> SegmentPlan:
        dtype = self.plan.block_dtype
        lhs_blocks, lhs_scales = _realize_values(a.blocks, dtype)
        if self.plan.kind == SPMM:
            grad = self.plan.grad_plan
            if grad is not None and self.grad_traffic_basis is not None:
                grad = grad.replace(traffic_items=_freeze_traffic(
                    _scale_spmm_traffic(self.grad_traffic_basis, n_cols_hint)))
            return self.plan.replace(
                lhs_blocks=lhs_blocks, lhs_scales=lhs_scales,
                traffic_items=_freeze_traffic(
                    _scale_spmm_traffic(self.traffic_basis, n_cols_hint)),
                grad_plan=grad, backend=backend, out_dtype=out_dtype)
        rhs_blocks, rhs_scales = _realize_values(b.blocks, dtype)
        return self.plan.replace(lhs_blocks=lhs_blocks, lhs_scales=lhs_scales,
                                 rhs_blocks=rhs_blocks, rhs_scales=rhs_scales,
                                 backend=backend, out_dtype=out_dtype)


_CACHE: Dict[str, _PlanTemplate] = {}
# hits/misses: template cache; searched/search_cache_hits/dataflow_fallbacks:
# autotune counters incremented by repro.tune.search (kept here so
# plan_cache_stats is the one stats surface and clear_plan_cache the one
# reset)
_STATS = {"hits": 0, "misses": 0,
          "searched": 0, "search_cache_hits": 0, "dataflow_fallbacks": 0}


def clear_plan_cache() -> None:
    """Drop every cached template — all ``block_dtype`` variants included
    (fp32 and quantized plans of one pattern are distinct entries) — and
    the :mod:`repro.tune` schedule-search cache alongside it."""
    import sys
    _CACHE.clear()
    for k in _STATS:
        _STATS[k] = 0
    # only if the tuner was ever imported — never import it from here (the
    # tune package imports this module at top level)
    ts = sys.modules.get("repro.tune.search")
    if ts is not None:
        ts._SEARCH_CACHE.clear()


def plan_cache_stats() -> Dict[str, int]:
    """Hit/miss counters + cache size, with entries broken out per
    ``block_dtype`` (``by_dtype``) — quantized plans of a pattern are
    separate cache entries from the fp32 plan of the same pattern.

    Also carries the autotune counters: ``searched`` (schedule searches
    actually run), ``search_cache_hits`` (searches answered from the tuned
    fingerprint cache at zero cost), and ``dataflow_fallbacks`` (times the
    analytically best dataflow had no registered policy and the tuner fell
    back to the best dispatchable one)."""
    by_dtype: Dict[str, int] = {}
    for tpl in _CACHE.values():
        d = tpl.plan.block_dtype
        by_dtype[d] = by_dtype.get(d, 0) + 1
    return dict(_STATS, size=len(_CACHE), by_dtype=by_dtype)


def _lane_flags(layout: LaneLayout, seg_start, seg_write, accum_prev) -> dict:
    """Lane-major schedule flag arrays — host numpy; the build path feeds
    them to the traffic model before :func:`_flag_leaves` uploads them."""
    return dict(
        seg_start=lane_select(layout, seg_start, zero_pads=True),
        seg_write=lane_select(layout, seg_write, zero_pads=True),
        accum_prev=lane_select(layout, accum_prev, zero_pads=True),
        valid=layout.valid.reshape(-1).astype(np.int32))


def _fetch_schedule(layout: LaneLayout, a_stream: np.ndarray,
                    b_stream: np.ndarray, unroll: int) -> dict:
    """DMA-pipeline fetch flags + ring-buffer slots for both operand streams.

    ``a_stream``/``b_stream`` are the *lane-major* operand index arrays the
    kernel addresses HBM with (A block slot, and ``k`` / B block slot).
    The ring depth is ``2·unroll`` — one slot set computing, one filling —
    matching the kernels' scratch allocation.
    """
    valid = layout.valid.reshape(-1)
    depth = 2 * unroll
    a_f, a_s = fetch_flags(a_stream, valid, layout.n_lanes, depth=depth)
    b_f, b_s = fetch_flags(b_stream, valid, layout.n_lanes, depth=depth)
    return dict(a_fetch=a_f, b_fetch=b_f, a_slot=a_s, b_slot=b_s)


def _flag_leaves(flags: dict) -> dict:
    """jnp device leaves for a plan's flag arrays (one upload, at the end of
    the build — never a device→host round trip on the build path)."""
    return {k: jnp.asarray(v) for k, v in flags.items()}


def _build_spmm_template(a: BSR, policy: str, fold_len: Optional[int],
                         with_grad: bool, n_lanes: int, unroll: int,
                         fingerprint: str, block_dtype: str = "fp32",
                         bn_hint: Optional[int] = None,
                         prefetch: Optional[str] = None) -> _PlanTemplate:
    sched = build_spmm_schedule(a, policy=policy, fold_len=fold_len)
    fin = finalize_schedule(sched.seg_start, sched.m, n_slots=sched.n_m_blocks)
    bm, bk = a.block_shape
    layout = partition_lanes(sched.m, n_lanes, unroll=unroll, policy=policy,
                             seg_start=sched.seg_start,
                             seg_write=sched.seg_write,
                             accum_prev=fin.accum_prev)
    lane_m = lane_select(layout, sched.m)
    lane_k = lane_select(layout, sched.k)
    lane_slot = lane_select(layout, sched.a_idx)
    flags = _lane_flags(layout, sched.seg_start, sched.seg_write,
                        fin.accum_prev)
    fetch = _fetch_schedule(layout, lane_slot, lane_k, unroll)
    basis = _quantize_a_traffic(lane_traffic_spmm(
        lane_m, lane_k, flags["seg_start"],
        layout.valid.reshape(-1), layout.n_lanes, bm, bk, 1, unroll=unroll,
        prefetch=prefetch),
        block_dtype, bm, bk)
    basis.update(layout.stats)

    grad_plan = None
    grad_basis = None
    if with_grad:
        # Transposed matrix Wᵀ: same stored blocks, coords swapped, re-sorted
        # row-major; schedule it independently, then address each item's
        # block in the *forward storage order* via slot_idx — the kernel's
        # transpose_lhs mode contracts along block rows, so the backward
        # pass reads the forward weight array with no transposed copy.
        t_order = np.lexsort((a.brow, a.bcol)).astype(np.int64)
        wt = BSR(shape=(a.shape[1], a.shape[0]), block_shape=(bk, bm),
                 brow=a.bcol[t_order].copy(), bcol=a.brow[t_order].copy(),
                 blocks=np.empty((a.nblocks, 1, 1), np.float32))
        t_sched = build_spmm_schedule(wt, policy=policy, fold_len=fold_len)
        t_fin = finalize_schedule(t_sched.seg_start, t_sched.m,
                                  n_slots=t_sched.n_m_blocks)
        t_layout = partition_lanes(t_sched.m, n_lanes, unroll=unroll,
                                   policy=policy,
                                   seg_start=t_sched.seg_start,
                                   seg_write=t_sched.seg_write,
                                   accum_prev=t_fin.accum_prev)
        t_slot = t_order[t_sched.a_idx.astype(np.int64)]
        t_lane_m = lane_select(t_layout, t_sched.m)
        t_lane_k = lane_select(t_layout, t_sched.k)
        t_lane_slot = lane_select(t_layout, t_slot)
        t_flags = _lane_flags(t_layout, t_sched.seg_start, t_sched.seg_write,
                              t_fin.accum_prev)
        t_fetch = _fetch_schedule(t_layout, t_lane_slot, t_lane_k, unroll)
        grad_basis = _quantize_a_traffic(lane_traffic_spmm(
            t_lane_m, t_lane_k, t_flags["seg_start"],
            t_layout.valid.reshape(-1), t_layout.n_lanes, bk, bm, 1,
            unroll=unroll, prefetch=prefetch),
            block_dtype, bk, bm)
        grad_basis.update(t_layout.stats)
        grad_plan = SegmentPlan(
            kind=SPMM, policy=policy, block_shape=(bk, bm),
            grid=(t_sched.n_m_blocks, t_sched.n_k_blocks), rhs_grid=None,
            n_out_blocks=t_sched.n_m_blocks,
            traffic_items=(),   # re-priced per realize from grad_basis
            fingerprint=fingerprint + ":grad",
            block_dtype=block_dtype,
            n_lanes=t_layout.n_lanes, unroll=unroll, transpose_lhs=True,
            bn_hint=bn_hint, prefetch=prefetch,
            has_pads=bool(not t_layout.valid.all()),
            m_idx=jnp.asarray(t_lane_m.astype(np.int32)),
            k_idx=jnp.asarray(t_lane_k.astype(np.int32)),
            slot_idx=jnp.asarray(t_lane_slot.astype(np.int32)),
            row_mask=jnp.asarray(t_fin.row_mask),
            a_brow=jnp.asarray(a.brow), a_bcol=jnp.asarray(a.bcol),
            **_flag_leaves(t_flags), **_flag_leaves(t_fetch))

    plan = SegmentPlan(
        kind=SPMM, policy=policy, block_shape=(bm, bk),
        grid=(sched.n_m_blocks, sched.n_k_blocks), rhs_grid=None,
        n_out_blocks=sched.n_m_blocks,
        traffic_items=(),   # re-priced per realize from traffic_basis
        fingerprint=fingerprint, block_dtype=block_dtype,
        n_lanes=layout.n_lanes, unroll=unroll,
        bn_hint=bn_hint, prefetch=prefetch,
        has_pads=bool(not layout.valid.all()),
        m_idx=jnp.asarray(lane_m.astype(np.int32)),
        k_idx=jnp.asarray(lane_k.astype(np.int32)),
        slot_idx=jnp.asarray(lane_slot.astype(np.int32)),
        row_mask=jnp.asarray(fin.row_mask),
        a_brow=jnp.asarray(a.brow), a_bcol=jnp.asarray(a.bcol),
        grad_plan=grad_plan, **_flag_leaves(flags), **_flag_leaves(fetch))
    return _PlanTemplate(plan=plan, traffic_basis=basis,
                         grad_traffic_basis=grad_basis)


def _build_spgemm_template(a: BSR, b: BSR, policy: str,
                           fold_len: Optional[int], n_lanes: int, unroll: int,
                           fingerprint: str, block_dtype: str = "fp32",
                           bn_hint: Optional[int] = None,
                           prefetch: Optional[str] = None) -> _PlanTemplate:
    sched = build_spgemm_schedule(a, b, policy=policy, fold_len=fold_len)
    fin = finalize_schedule(sched.seg_start, sched.c_idx)
    bm, bk = a.block_shape
    bn = b.block_shape[1]
    layout = partition_lanes(sched.c_idx, n_lanes, unroll=unroll,
                             policy=policy, seg_start=sched.seg_start,
                             seg_write=sched.seg_write,
                             accum_prev=fin.accum_prev)
    lane_a = lane_select(layout, sched.a_idx)
    lane_b = lane_select(layout, sched.b_idx)
    lane_c = lane_select(layout, sched.c_idx)
    flags = _lane_flags(layout, sched.seg_start, sched.seg_write,
                        fin.accum_prev)
    fetch = _fetch_schedule(layout, lane_a, lane_b, unroll)
    traffic = _quantize_spgemm_traffic(lane_traffic_spgemm(
        lane_a, lane_b, lane_c, flags["seg_start"],
        layout.valid.reshape(-1), layout.n_lanes, bm, bk, bn,
        prefetch=prefetch),
        block_dtype, bm, bk, bn)
    traffic.update(layout.stats)
    plan = SegmentPlan(
        kind=SPGEMM, policy=policy, block_shape=(bm, bk),
        grid=a.grid, rhs_grid=b.grid, n_out_blocks=sched.n_c_blocks,
        traffic_items=_freeze_traffic(traffic),
        fingerprint=fingerprint, block_dtype=block_dtype,
        n_lanes=layout.n_lanes, unroll=unroll,
        bn_hint=bn_hint, prefetch=prefetch,
        has_pads=bool(not layout.valid.all()),
        a_idx=jnp.asarray(lane_a.astype(np.int32)),
        b_idx=jnp.asarray(lane_b.astype(np.int32)),
        c_idx=jnp.asarray(lane_c.astype(np.int32)),
        a_brow=jnp.asarray(a.brow), a_bcol=jnp.asarray(a.bcol),
        b_brow=jnp.asarray(b.brow), b_bcol=jnp.asarray(b.bcol),
        c_brow_arr=jnp.asarray(sched.c_brow),
        c_bcol_arr=jnp.asarray(sched.c_bcol),
        **_flag_leaves(flags), **_flag_leaves(fetch))
    return _PlanTemplate(plan=plan)


def _resolve_verify(verify) -> Optional[str]:
    """Normalize the ``verify`` knob: None/False off, True → "fast"."""
    if verify is None or verify is False:
        return None
    if verify is True:
        return "fast"
    if verify in ("fast", "full"):
        return verify
    raise ValueError(f"verify must be None/False/True/'fast'/'full', "
                     f"got {verify!r}")


def _rhs_to_hint(a: BSR, b) -> Tuple[Optional[BSR], int]:
    """Normalize ``B_or_shape`` → (BSR | None, n_cols_hint)."""
    if b is None:
        return None, 1024
    if isinstance(b, BSR):
        return b, b.shape[1]
    if isinstance(b, int):
        shape: Tuple[int, ...] = (a.shape[1], b)
    elif isinstance(b, tuple):
        shape = b
    elif hasattr(b, "shape"):
        shape = tuple(b.shape)
    else:
        raise TypeError(f"B_or_shape must be a BSR, dense array, shape tuple "
                        f"or int N, got {type(b).__name__}")
    if len(shape) != 2:
        raise ValueError(f"dense rhs must be 2-D (K, N), got shape {shape}")
    if shape[0] != a.shape[1]:
        raise ValueError(f"rhs K={shape[0]} does not match A K={a.shape[1]}")
    return None, int(shape[1])


def plan_matmul(a: BSR, b_or_shape=None, *, policy: str = "segment",
                backend: Optional[str] = None, fold_len: Optional[int] = None,
                with_grad: bool = False, n_cols_hint: Optional[int] = None,
                n_lanes: int = 1, unroll: int = 1, cache: bool = True,
                quantize: Optional[str] = None,
                out_dtype=None, verify=None,
                vmem_limit_bytes: Optional[int] = None,
                bn_hint: Optional[int] = None,
                prefetch: Optional[str] = None) -> SegmentPlan:
    """Plan a Segment-dataflow matmul for the sparsity pattern of ``a``.

    Args:
      a: the BSR left operand (pattern + values).
      b_or_shape: ``BSR`` (SpGEMM), or the dense rhs / its ``(K, N)`` shape /
        ``N`` (SpMM; only used as a traffic hint), or None.
      policy: any name in the policy registry, or ``"auto"`` — run the
        :mod:`repro.tune` schedule search over the knob grid and the
        registered dataflows and plan with the winning (policy, fold_len,
        n_lanes, unroll, bn, prefetch) combination.  Knobs passed
        explicitly alongside ``policy="auto"`` are treated as pins the
        search must honour.  Winning schedules are cached by pattern
        fingerprint, so repeat patterns pay zero search cost.
      backend: preferred execution backend recorded on the plan (resolvable
        later; ``None`` defers to the process default).  ``"pallas"``
        refuses block dimensions that are not multiples of 128 here, with a
        :class:`~repro.api.backends.BlockShapeError`.
      fold_len: temporal-fold cap on segment length (fold-capable policies).
      with_grad: also build the transposed schedule so ``apply_plan`` can run
        the backward pass (SpMM only).
      n_cols_hint: overrides the traffic model's dense-N estimate.
      n_lanes: split the schedule into this many load-balanced parallel
        lanes (clamped to the number of output segments).
      unroll: schedule items executed per kernel grid step (aligned at
        plan time; amortizes grid overhead on small blocks).
      cache: reuse the pattern-fingerprint plan cache.
      quantize: ``"int8"`` / ``"fp8"`` store block values as a quantized
        payload + per-block fp32 scales, dequantized in-kernel at the fp32
        accumulator (both operands for SpGEMM; the dense rhs stays fp32).
        ``"int8.rowwise"`` / ``"fp8.rowwise"`` carry one fp32 scale per
        *block row* instead — better resolution on outlier-heavy weights,
        dequantized before the MXU dot.  ``None`` keeps fp32 storage.
        Quantized and fp32 plans of one pattern never share a cache entry
        or fingerprint (the mode string is the plan's ``block_dtype``).
      out_dtype: default dtype of the written output tiles (resolved at
        execution; overridable per call).  Accumulation stays fp32.
      verify: run the static schedule verifier
        (:func:`repro.analysis.verify_plan`) and raise
        :class:`~repro.analysis.PlanVerificationError` on any finding.
        ``True``/``"fast"`` runs the structural catalog, ``"full"`` adds
        the independent traffic-model count recomputation.  The expensive
        pass runs once per cached *template* (remembered on the cache
        entry), so per-call overhead on a cache hit is a single O(1)
        scale-agreement check on the realized values.
      vmem_limit_bytes: when set, check the plan's worst-case kernel VMEM
        working set (forward and, with ``with_grad``, the transposed
        backward instance; see :func:`repro.analysis.plan_vmem_bytes`)
        against this per-core byte limit and raise
        :class:`~repro.analysis.VmemBudgetError` at plan time — a bad
        (block, bn, unroll) knob combination fails here, not as an OOM at
        launch.  The N-tile width is taken as the executor default
        (``bn_hint`` or 512) clamped by ``pick_bn`` to the traffic hint's N
        (lane-aligned for ``backend="pallas"``).
      bn_hint: preferred executor N-tile width, used when the caller passes
        no explicit ``bn`` at execution time (set by the :mod:`repro.tune`
        search; ``None`` keeps the executor default of 512).
      prefetch: DMA schedule mode (:data:`repro.core.schedule
        .PREFETCH_MODES`).  ``"cross_pass"`` makes the SpMM kernel issue
        the next (lane, N-tile) pass's first copies — B row-tiles before A
        tiles — during the current pass's tail step instead of draining
        the pipeline at the boundary; numerically identical (the mode
        re-times copies, it never changes which items fetch).  The
        recorded traffic gains a
        ``prefetch_fetches`` entry pricing the overlapped copies, and
        every shipped kernel variant with prefetch enabled is proven
        hazard-free by :mod:`repro.analysis.order` in CI.
    """
    if backend is not None:
        resolve_backend(backend)   # fail fast on typos
    if quantize is not None and quantize not in QUANT_MODES:
        raise ValueError(f"unknown quantize dtype {quantize!r}; "
                         f"available: {QUANT_MODES} or None")
    if prefetch not in PREFETCH_MODES:
        raise ValueError(f"prefetch={prefetch!r} not in {PREFETCH_MODES}")
    block_dtype = quantize if quantize is not None else "fp32"
    out_dtype = None if out_dtype is None else jnp.dtype(out_dtype).name
    if policy == "auto":
        # dataflow selection + knob search live in repro.tune; import
        # lazily so the plain build path never pays for (or cycles with)
        # the tuner.  Explicit knobs become pins the search must honour.
        from repro.tune.search import select_schedule
        b0, hint0 = _rhs_to_hint(a, b_or_shape)
        if n_cols_hint is not None:
            hint0 = n_cols_hint
        pins: Dict[str, object] = {}
        if fold_len is not None:
            pins["fold_len"] = fold_len
        if n_lanes != 1:
            pins["n_lanes"] = n_lanes
        if unroll != 1:
            pins["unroll"] = unroll
        if bn_hint is not None:
            pins["bn"] = bn_hint
        if prefetch is not None:
            pins["prefetch"] = prefetch
        # tune for the backend the plan will actually run on: the compiled
        # model prices lanes as concurrent grid dimensions, the interpret
        # model prices the grid sequentially
        objective = ("tpu" if resolve_backend(backend) == "pallas"
                     else "interpret")
        best = select_schedule(a, b0, n_cols_hint=hint0, with_grad=with_grad,
                               quantize=quantize, objective=objective,
                               vmem_limit_bytes=vmem_limit_bytes, pins=pins)
        return plan_matmul(
            a, b_or_shape, policy=best.policy, backend=backend,
            fold_len=best.fold_len, with_grad=with_grad,
            n_cols_hint=n_cols_hint, n_lanes=best.n_lanes,
            unroll=best.unroll, cache=cache, quantize=quantize,
            out_dtype=out_dtype, verify=verify,
            vmem_limit_bytes=vmem_limit_bytes, bn_hint=best.bn,
            prefetch=best.prefetch)
    pol = get_policy(policy)       # fail fast + serial for the cache key
    b, hint = _rhs_to_hint(a, b_or_shape)
    if n_cols_hint is not None:
        hint = n_cols_hint
    if b is not None and with_grad:
        raise NotImplementedError("with_grad is only supported for SpMM plans")

    kind = SPGEMM if b is not None else SPMM
    mats = (a, b) if b is not None else (a,)
    for m in mats:
        check_block_shape(m.block_shape, backend)
    key = pattern_fingerprint(kind, f"{policy}#{pol.serial}", fold_len,
                              with_grad, *mats, n_lanes=n_lanes,
                              unroll=unroll, block_dtype=block_dtype,
                              n_bucket=_bucket_hint(hint) if b is None
                              else None,
                              bn_hint=bn_hint, prefetch=prefetch)
    level = _resolve_verify(verify)
    tpl = _CACHE.get(key) if cache else None
    if tpl is None:
        if kind == SPMM:
            tpl = _build_spmm_template(a, policy, fold_len, with_grad,
                                       n_lanes, unroll, key, block_dtype,
                                       bn_hint=bn_hint, prefetch=prefetch)
        else:
            tpl = _build_spgemm_template(a, b, policy, fold_len, n_lanes,
                                         unroll, key, block_dtype,
                                         bn_hint=bn_hint, prefetch=prefetch)
        _STATS["misses"] += 1   # a build is a miss whether or not it's kept
        if cache:
            _CACHE[key] = tpl
    else:
        _STATS["hits"] += 1
    if level is not None:
        covered = ("fast", "full") if level == "fast" else ("full",)
        if tpl.verified_level not in covered:
            # verify the value-free template once; the result is remembered
            # on the cache entry so repeated realizations stay O(1)
            verify_plan(tpl.plan, level=level).raise_if_findings()
            tpl.verified_level = level
    plan = tpl.realize(a, b, backend, hint, out_dtype)
    if level is not None:
        # the only per-realize degree of freedom is the value leaves —
        # check just their dtype/shape agreement on every call (the direct
        # single-invariant call keeps the cache-hit path O(1))
        findings = check_scale_agreement(plan)
        if findings:
            raise PlanVerificationError(VerifyResult(
                findings=tuple(findings), level=level,
                checked=("scale-agreement",)))
    if vmem_limit_bytes is not None:
        # lazy imports: the executor for bn clamping, the analyzer for the
        # budget — neither belongs on the plain plan-build path
        from repro.analysis.budget import check_plan_vmem

        from .executor import pick_bn
        bn_eff, _ = pick_bn(max(1, hint), bn_hint or 512,
                            align=LANE if backend == "pallas" else 1)
        check_plan_vmem(plan, bn=bn_eff, limit=vmem_limit_bytes,
                        label=f"plan_matmul[{kind}]")
    return plan
