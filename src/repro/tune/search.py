"""Analytical schedule search + adaptive dataflow selection.

:func:`autotune_matmul` sweeps the knob grid — ``fold_len`` × ``n_lanes`` ×
``unroll`` × ``bn`` × ``prefetch`` — across every registered schedule policy
and scores each candidate with the unified :class:`~repro.tune.cost.CostModel`
(lane-aware revisiting-model traffic bytes + a per-grid-step overhead term;
imbalance and padding are priced structurally through the padded lane
length).  Nothing executes during the search: candidates are priced from the
host-side schedule arrays, statically rejected against the closed-form VMEM
budget (:func:`repro.analysis.budget.spmm_vmem_bytes`), and the ranked
winner is then built once and gated through
:func:`repro.analysis.verify_plan(level="full")` plus
:func:`repro.analysis.budget.check_plan_vmem` before it is declared — a
candidate that fails either static check falls through to the runner-up.

Dataflow selection rides on top: the registered static policies expose
closed-form ``cost_hint`` estimators (see
:func:`repro.sim.baselines.dataflow_estimates`), the dynamic ``segment``
policy is priced by building its schedule, and the analytic ``"inner"``
dataflow competes for comparison only — when it wins on paper the tuner
falls back to the best *dispatchable* policy and counts a
``dataflow_fallbacks`` tick in :func:`repro.api.plan_cache_stats`.

Winning schedules are cached by a pattern fingerprint (pattern bytes +
bucketed dense-N hint + search configuration), so repeat patterns pay zero
search cost; the cache empties together with the plan cache on
:func:`repro.api.clear_plan_cache`.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, Optional, Tuple

import numpy as np

from repro.analysis.budget import (DEFAULT_VMEM_LIMIT_BYTES, check_plan_vmem,
                                   spgemm_vmem_bytes, spmm_vmem_bytes)
from repro.analysis.invariants import verify_plan
from repro.core.formats import BSR
from repro.core.policies import available_policies, get_policy
from repro.core.schedule import (build_spgemm_schedule, build_spmm_schedule,
                                 finalize_schedule, lane_select,
                                 lane_traffic_spgemm, lane_traffic_spmm,
                                 partition_lanes)
from repro.sim.baselines import dataflow_estimates

from .cost import DEFAULT_INTERPRET, DEFAULT_TPU, CostModel

#: plan ``block_dtype`` names → numpy-ish dtype names the VMEM formulas take
_VMEM_DTYPE = {"fp32": "float32", "int8": "int8", "fp8": "float8_e4m3fn"}


def _vmem_dtype(block_dtype: str) -> str:
    from repro.core.formats import quant_base_dtype
    return _VMEM_DTYPE[quant_base_dtype(block_dtype)]


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point of the knob grid: a (dataflow, schedule-shape) choice."""

    policy: str
    fold_len: Optional[int]
    n_lanes: int
    unroll: int
    bn: int
    prefetch: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class SearchSpace:
    """Knob axes the search sweeps.  The default space always contains the
    planner's default point (``segment``, no fold, 1 lane, unroll 1,
    ``bn=512``, no prefetch), so the winner can never be worse than the
    default under the model being optimized.  ``policies=None`` sweeps
    every registered policy."""

    fold_lens: Tuple[Optional[int], ...] = (None, 8)
    n_lanes: Tuple[int, ...] = (1, 2, 4)
    unrolls: Tuple[int, ...] = (1, 2)
    bns: Tuple[int, ...] = (128, 512)
    prefetches: Tuple[Optional[str], ...] = (None, "cross_pass")
    policies: Optional[Tuple[str, ...]] = None


@dataclasses.dataclass(frozen=True)
class Scored:
    """A feasible candidate with its model price."""

    candidate: Candidate
    cost_us: float
    traffic: Tuple[Tuple[str, float], ...]   # frozen lane_traffic dict
    lane_len: int                            # padded per-lane items
    n_tiles_n: int
    vmem_bytes: int

    @property
    def traffic_total(self) -> float:
        return dict(self.traffic)["total"]


@dataclasses.dataclass
class TuneResult:
    """Outcome of one schedule search (possibly served from the cache)."""

    best: Scored
    candidates: Tuple[Scored, ...]           # ranked, best first
    dataflow_scores: Dict[str, float]        # analytic bytes per dataflow
    dataflow_choice: str                     # analytically best dataflow
    dataflow_dispatched: str                 # ...the dispatchable one used
    objective: str
    n_rejected_vmem: int
    from_cache: bool = False

    def plan_kwargs(self) -> Dict[str, object]:
        """Keyword arguments that make :func:`repro.api.plan_matmul` build
        the winning schedule."""
        c = self.best.candidate
        return dict(policy=c.policy, fold_len=c.fold_len, n_lanes=c.n_lanes,
                    unroll=c.unroll, bn_hint=c.bn, prefetch=c.prefetch)


#: fingerprint → TuneResult; cleared by repro.api.clear_plan_cache
_SEARCH_CACHE: Dict[str, TuneResult] = {}


def _pin(pins: Dict[str, object], key: str, axis: tuple) -> tuple:
    """An explicitly pinned knob collapses its axis to the pinned value."""
    if key in pins:
        return (pins[key],)
    return axis


def _resolve_model(objective, cost_model) -> Tuple[CostModel, str]:
    if cost_model is not None:
        return cost_model, "custom"
    if isinstance(objective, CostModel):
        return objective, "custom"
    if objective == "tpu":
        return DEFAULT_TPU, "tpu"
    if objective == "interpret":
        return DEFAULT_INTERPRET, "interpret"
    raise ValueError(f"objective must be 'tpu', 'interpret' or a CostModel, "
                     f"got {objective!r}")


def _search_key(kind: str, mats, n_bucket: Optional[int], with_grad: bool,
                block_dtype: str, space: SearchSpace, model: CostModel,
                objective: str, limit: int, pins: Dict[str, object]) -> str:
    from repro.api.planner import _pattern_bytes
    h = hashlib.sha1()
    h.update(repr((kind, n_bucket, with_grad, block_dtype,
                   dataclasses.astuple(space),
                   dataclasses.astuple(model),
                   objective, limit, tuple(sorted(pins.items())))).encode())
    for m in mats:
        _pattern_bytes(h, m)
    return h.hexdigest()


def _rank_key(s: Scored, policy_order: Tuple[str, ...]):
    """Total order on scored candidates: model cost, then traffic bytes,
    then every tie broken toward the planner's default point (segment
    first, fewer lanes, smaller unroll, no fold, no prefetch, wider
    bn)."""
    c = s.candidate
    return (s.cost_us, s.traffic_total,
            policy_order.index(c.policy) if c.policy in policy_order
            else len(policy_order),
            c.n_lanes, c.unroll,
            c.fold_len is not None, c.fold_len or 0,
            c.prefetch is not None, -c.bn)


def _score_spmm(a: BSR, hint: int, block_dtype: str, model: CostModel,
                space: SearchSpace, limit: int, pins: Dict[str, object]):
    from repro.api.executor import pick_bn
    from repro.api.planner import _quantize_a_traffic
    bm, bk = a.block_shape
    policies = _pin(pins, "policy",
                    space.policies or available_policies())
    scored, rejected = [], 0
    for policy in policies:
        pol = get_policy(policy)
        folds = (_pin(pins, "fold_len", space.fold_lens)
                 if pol.supports_fold else (None,))
        for fold in folds:
            sched = build_spmm_schedule(a, policy=policy, fold_len=fold)
            fin = finalize_schedule(sched.seg_start, sched.m,
                                    n_slots=sched.n_m_blocks)
            for lanes in _pin(pins, "n_lanes", space.n_lanes):
                for un in _pin(pins, "unroll", space.unrolls):
                    layout = partition_lanes(
                        sched.m, lanes, unroll=un, policy=policy,
                        seg_start=sched.seg_start, seg_write=sched.seg_write,
                        accum_prev=fin.accum_prev)
                    lane_m = lane_select(layout, sched.m)
                    lane_k = lane_select(layout, sched.k)
                    ss = lane_select(layout, sched.seg_start, zero_pads=True)
                    valid = layout.valid.reshape(-1)
                    for pf in _pin(pins, "prefetch", space.prefetches):
                        traffic = _quantize_a_traffic(lane_traffic_spmm(
                            lane_m, lane_k, ss, valid, layout.n_lanes,
                            bm, bk, hint, unroll=un, prefetch=pf),
                            block_dtype, bm, bk)
                        for bn in _pin(pins, "bn", space.bns):
                            bn_eff, pad = pick_bn(max(1, hint), bn)
                            n_tiles = (max(1, hint) + pad) // bn_eff
                            vbytes = spmm_vmem_bytes(
                                bm=bm, bk=bk, bn=bn_eff, unroll=un,
                                block_dtype=_vmem_dtype(block_dtype),
                                quantized=block_dtype != "fp32",
                                rowwise=block_dtype.endswith(".rowwise"))
                            if vbytes > limit:
                                rejected += 1
                                continue
                            cost = model.cost_us(
                                traffic_bytes=traffic["total"],
                                n_lanes=layout.n_lanes,
                                lane_len=layout.lane_len, unroll=un,
                                n_tiles_n=n_tiles, prefetch=pf is not None)
                            scored.append(Scored(
                                Candidate(policy, fold, lanes, un, bn, pf),
                                cost, tuple(sorted(traffic.items())),
                                layout.lane_len, n_tiles, vbytes))
    return scored, rejected, tuple(policies)


def _score_spgemm(a: BSR, b: BSR, block_dtype: str, model: CostModel,
                  space: SearchSpace, limit: int, pins: Dict[str, object]):
    from repro.api.planner import _quantize_spgemm_traffic
    bm, bk = a.block_shape
    bn = b.block_shape[1]   # SpGEMM's N tile is B's block width — not a knob
    policies = _pin(pins, "policy",
                    space.policies or available_policies())
    scored, rejected = [], 0
    for policy in policies:
        pol = get_policy(policy)
        folds = (_pin(pins, "fold_len", space.fold_lens)
                 if pol.supports_fold else (None,))
        for fold in folds:
            sched = build_spgemm_schedule(a, b, policy=policy, fold_len=fold)
            fin = finalize_schedule(sched.seg_start, sched.c_idx)
            for lanes in _pin(pins, "n_lanes", space.n_lanes):
                for un in _pin(pins, "unroll", space.unrolls):
                    layout = partition_lanes(
                        sched.c_idx, lanes, unroll=un, policy=policy,
                        seg_start=sched.seg_start, seg_write=sched.seg_write,
                        accum_prev=fin.accum_prev)
                    lane_a = lane_select(layout, sched.a_idx)
                    lane_b = lane_select(layout, sched.b_idx)
                    lane_c = lane_select(layout, sched.c_idx)
                    ss = lane_select(layout, sched.seg_start, zero_pads=True)
                    valid = layout.valid.reshape(-1)
                    traffic = _quantize_spgemm_traffic(lane_traffic_spgemm(
                        lane_a, lane_b, lane_c, ss, valid, layout.n_lanes,
                        bm, bk, bn),
                        block_dtype, bm, bk, bn)
                    vbytes = spgemm_vmem_bytes(
                        bm=bm, bk=bk, bn=bn, unroll=un,
                        block_dtype=_vmem_dtype(block_dtype),
                        quant_a=block_dtype != "fp32",
                        quant_b=block_dtype != "fp32",
                        rowwise=block_dtype.endswith(".rowwise"))
                    if vbytes > limit:
                        rejected += 1
                        continue
                    cost = model.cost_us(
                        traffic_bytes=traffic["total"],
                        n_lanes=layout.n_lanes,
                        lane_len=layout.lane_len, unroll=un, n_tiles_n=1)
                    scored.append(Scored(
                        Candidate(policy, fold, lanes, un, bn,
                                  pins.get("prefetch")),
                        cost, tuple(sorted(traffic.items())),
                        layout.lane_len, 1, vbytes))
    return scored, rejected, tuple(policies)


def _dataflow_scores(kind: str, a: BSR, b: Optional[BSR], hint: int,
                     scored, policies: Tuple[str, ...]) -> Dict[str, float]:
    """Analytic bytes per dataflow at default knobs: closed-form estimates
    for the hint-carrying policies + ``"inner"``, overlaid with each swept
    policy's own default-knob (1 lane, unroll 1, no fold, no prefetch)
    candidate — that is how the hint-less ``segment`` gets scored."""
    bm, bk = a.block_shape
    if kind == "spmm":
        est = dataflow_estimates("spmm", bm=bm, bk=bk, n_cols=hint,
                                 m=a.brow.astype(np.int64),
                                 k=a.bcol.astype(np.int64))
    else:
        sched = build_spgemm_schedule(a, b, policy=policies[0])
        est = dataflow_estimates(
            "spgemm", bm=bm, bk=bk, bn=b.block_shape[1],
            m=sched.m.astype(np.int64), n=sched.n.astype(np.int64),
            k=sched.k.astype(np.int64), c=sched.c_idx.astype(np.int64),
            a_idx=sched.a_idx.astype(np.int64),
            b_idx=sched.b_idx.astype(np.int64))
    scores = {name: float(e["total"]) for name, e in est.items()}
    for s in scored:
        c = s.candidate
        if (c.fold_len is None and c.n_lanes == 1 and c.unroll == 1
                and c.prefetch is None):
            scores[c.policy] = s.traffic_total
    return scores


def autotune_matmul(a: BSR, b_or_shape=None, *,
                    space: Optional[SearchSpace] = None,
                    objective="tpu", cost_model: Optional[CostModel] = None,
                    n_cols_hint: Optional[int] = None, with_grad: bool = False,
                    quantize: Optional[str] = None,
                    vmem_limit_bytes: Optional[int] = None,
                    cache: bool = True,
                    pins: Optional[Dict[str, object]] = None) -> TuneResult:
    """Search the knob grid for the cheapest feasible schedule of ``a``'s
    pattern (× ``b``'s for SpGEMM) under the given cost model.

    Purely static: no candidate is ever executed.  Infeasible candidates
    are rejected by the closed-form VMEM budget; the ranked winner is built
    once and must pass ``verify_plan(level="full")`` plus the plan-level
    VMEM gate, else the runner-up is promoted.  ``pins`` maps knob names
    (``policy``/``fold_len``/``n_lanes``/``unroll``/``bn``/``prefetch``) to
    values the search must keep fixed.  Results are cached by pattern
    fingerprint (``cache=True``) so repeat patterns skip the sweep."""
    from repro.api import planner as _planner
    b, hint = _planner._rhs_to_hint(a, b_or_shape)
    if n_cols_hint is not None:
        hint = int(n_cols_hint)
    if b is not None and with_grad:
        raise NotImplementedError("with_grad is only supported for SpMM plans")
    model, obj_name = _resolve_model(objective, cost_model)
    limit = (DEFAULT_VMEM_LIMIT_BYTES if vmem_limit_bytes is None
             else vmem_limit_bytes)
    space = space or SearchSpace()
    pins = dict(pins or {})
    block_dtype = quantize if quantize is not None else "fp32"
    kind = "spgemm" if b is not None else "spmm"
    mats = (a, b) if b is not None else (a,)
    key = _search_key(kind, mats,
                      _planner._bucket_hint(hint) if b is None else None,
                      with_grad, block_dtype, space, model, obj_name, limit,
                      pins)
    if cache and key in _SEARCH_CACHE:
        _planner._STATS["search_cache_hits"] += 1
        return dataclasses.replace(_SEARCH_CACHE[key], from_cache=True)
    _planner._STATS["searched"] += 1

    if kind == "spmm":
        scored, rejected, policies = _score_spmm(a, hint, block_dtype, model,
                                                 space, limit, pins)
    else:
        scored, rejected, policies = _score_spgemm(a, b, block_dtype, model,
                                                   space, limit, pins)
    if not scored:
        raise ValueError(
            f"autotune_matmul: every candidate in the search space exceeds "
            f"the {limit}-byte VMEM budget ({rejected} rejected); widen the "
            f"space or raise vmem_limit_bytes")
    ranked = tuple(sorted(scored, key=lambda s: _rank_key(s, policies)))

    scores = _dataflow_scores(kind, a, b, hint, scored, policies)
    choice = min(scores, key=lambda n: (scores[n], n != "segment"))
    dispatchable = {s.candidate.policy for s in scored}
    if choice not in dispatchable:
        _planner._STATS["dataflow_fallbacks"] += 1
        dispatched = min((n for n in scores if n in dispatchable),
                         key=lambda n: (scores[n], n != "segment"))
    else:
        dispatched = choice

    # static winner gate: the best candidate must survive the full verifier
    # and the plan-level VMEM budget; a failure promotes the runner-up
    from repro.api.executor import pick_bn
    best = None
    for s in ranked:
        c = s.candidate
        plan = _planner.plan_matmul(
            a, b_or_shape, policy=c.policy, fold_len=c.fold_len,
            with_grad=with_grad, n_cols_hint=hint, n_lanes=c.n_lanes,
            unroll=c.unroll, cache=False, quantize=quantize,
            bn_hint=c.bn, prefetch=c.prefetch)
        try:
            verify_plan(plan, level="full").raise_if_findings()
            bn_eff, _ = pick_bn(max(1, hint), c.bn)
            check_plan_vmem(plan, bn=bn_eff, limit=limit,
                            label=f"autotune[{kind}]")
        except Exception:
            continue
        best = s
        break
    if best is None:
        raise ValueError("autotune_matmul: no candidate passed the static "
                         "verifier + VMEM gate")

    result = TuneResult(best=best, candidates=ranked,
                        dataflow_scores=scores, dataflow_choice=choice,
                        dataflow_dispatched=dispatched, objective=obj_name,
                        n_rejected_vmem=rejected)
    if cache:
        _SEARCH_CACHE[key] = result
    return result


def select_schedule(a: BSR, b: Optional[BSR] = None, *,
                    n_cols_hint: Optional[int] = None,
                    with_grad: bool = False, quantize: Optional[str] = None,
                    vmem_limit_bytes: Optional[int] = None,
                    pins: Optional[Dict[str, object]] = None,
                    objective="tpu",
                    space: Optional[SearchSpace] = None,
                    cost_model: Optional[CostModel] = None,
                    cache: bool = True) -> Candidate:
    """The planner's ``policy="auto"`` entry point: run (or replay from the
    search cache) the schedule search and return the winning
    :class:`Candidate` — the knobs ``plan_matmul`` should re-enter with."""
    res = autotune_matmul(a, b, space=space, objective=objective,
                          cost_model=cost_model, n_cols_hint=n_cols_hint,
                          with_grad=with_grad, quantize=quantize,
                          vmem_limit_bytes=vmem_limit_bytes, cache=cache,
                          pins=pins)
    return res.best.candidate
