"""Schedule-policy registry: dataflows as a pluggable configuration space.

The paper's thesis is that one *dynamic* dataflow (``segment``) subsumes the
static ones; Flexagon (PAPERS.md) frames dataflows as configurations to be
selected per workload.  This registry is the code form of that framing: a
policy is a named pair of ordering functions — one for SpMM work items, one
for SpGEMM triples — and everything downstream (schedule builders, the
``repro.api`` planner, benchmarks) enumerates or looks up policies here
instead of hard-coding ``if/elif`` string chains.

Built-in policies (``segment``, ``gustavson``, ``outer``) are registered by
:mod:`repro.core.schedule` when it defines their ordering functions; user
policies register via :func:`register_policy` (re-exported as
``repro.api.register_policy``).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import numpy as np

# (m, k) per-item block coordinates -> permutation of item indices
SpmmOrderFn = Callable[[np.ndarray, np.ndarray], np.ndarray]
# (m, n, k, c) per-triple coordinates + C slot -> permutation of triple indices
SpgemmOrderFn = Callable[[np.ndarray, np.ndarray, np.ndarray, np.ndarray],
                         np.ndarray]
# kind ("spmm"/"spgemm") + keyword coordinate/tile args -> traffic dict | None
CostHintFn = Callable[..., Optional[dict]]


@dataclasses.dataclass(frozen=True)
class SchedulePolicy:
    """A named work-item ordering for both Segment kernels.

    ``spmm_order(m, k)`` and ``spgemm_order(m, n, k, c)`` return permutations;
    ``supports_fold`` marks policies whose output runs may be split by
    temporal folding (static orders have fixed run structure, so folding them
    is meaningless and is ignored by the builders).

    ``cost_hint`` is an optional closed-form traffic estimator the autotuner
    (:mod:`repro.tune`) and :func:`repro.sim.baselines.dataflow_estimates`
    use to score this dataflow against others *without* building a full
    plan.  The call convention is keyword-based::

        cost_hint("spmm",   m=brow, k=bcol, bm=..., bk=..., n_cols=...)
        cost_hint("spgemm", m=..., n=..., k=..., c=..., a_idx=..., b_idx=...,
                  bm=..., bk=..., bn=...)

    returning a dict shaped like :func:`repro.core.schedule.lane_traffic_spmm`
    output (``a_bytes``/``b_bytes``/``c_bytes``/``total``/fetch counts) at
    default knobs (one lane, fp32), or ``None`` when the policy
    cannot estimate that kind analytically.  Dynamic policies whose order
    *is* the schedule (``segment``) leave this unset — the tuner evaluates
    them by building the schedule.
    """

    name: str
    spmm_order: SpmmOrderFn
    spgemm_order: SpgemmOrderFn
    supports_fold: bool = False
    description: str = ""
    # monotone registration serial: plan caches key on (name, serial) so a
    # re-registered policy can never be served another definition's schedule
    serial: int = 0
    cost_hint: Optional[CostHintFn] = None


_REGISTRY: Dict[str, SchedulePolicy] = {}
_SERIAL = 0


def register_policy(name: str, *, spmm_order: SpmmOrderFn,
                    spgemm_order: SpgemmOrderFn, supports_fold: bool = False,
                    description: str = "",
                    cost_hint: Optional[CostHintFn] = None,
                    overwrite: bool = False) -> SchedulePolicy:
    """Register a schedule policy under ``name``.

    Raises ``ValueError`` on duplicate names unless ``overwrite=True`` —
    silent replacement of a built-in would change numerics-by-traffic
    behaviour everywhere at once.  ``"auto"`` is reserved: it names the
    planner's adaptive dataflow-selection mode, not a policy.
    """
    if not name or not isinstance(name, str):
        raise ValueError(f"policy name must be a non-empty string, got {name!r}")
    if name == "auto":
        raise ValueError("policy name 'auto' is reserved for "
                         "plan_matmul(policy='auto') dataflow selection")
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"policy {name!r} is already registered "
                         f"(pass overwrite=True to replace it)")
    global _SERIAL
    _SERIAL += 1
    policy = SchedulePolicy(name=name, spmm_order=spmm_order,
                            spgemm_order=spgemm_order,
                            supports_fold=supports_fold,
                            description=description, serial=_SERIAL,
                            cost_hint=cost_hint)
    _REGISTRY[name] = policy
    return policy


def unregister_policy(name: str) -> None:
    """Remove a policy (primarily for tests registering throwaway policies)."""
    _REGISTRY.pop(name, None)


def get_policy(name: str) -> SchedulePolicy:
    try:
        return _REGISTRY[name]
    except KeyError:
        if name == "auto":
            raise ValueError(
                "'auto' is not a registered policy — it is the planner's "
                "dataflow-selection mode; pass policy='auto' to "
                "repro.api.plan_matmul (which dispatches to the winning "
                "registered policy) instead of resolving it here") from None
        raise ValueError(
            f"unknown policy {name!r}; available: {available_policies()}"
        ) from None


def available_policies() -> Tuple[str, ...]:
    """Registered policy names, registration order (built-ins first)."""
    return tuple(_REGISTRY)
