"""``repro.api`` — the one way to run Segment-dataflow matmuls.

The paper's thesis is that a single *dynamic* dataflow subsumes the static
ones; this package is the code form of that thesis: one plan abstraction
(:class:`SegmentPlan`, a JAX pytree), one policy registry (dataflows as a
configuration space), one backend switch (compiled / interpret / reference),
and one differentiable executor shared by serving and training.

Typical lifecycle::

    from repro import api

    plan = api.plan_matmul(A, x.shape, policy="segment")   # build (cached)
    y = plan(x)                                            # execute
    y = jax.jit(lambda p, x: api.apply_plan(p, x))(plan, x)  # jit'd + grads

See ``docs/API.md`` for the full plan lifecycle, the policy registry
contract, and the deprecation shims (``repro.kernels.ops.plan_spmm`` /
``plan_spgemm`` now delegate here).
"""
from repro.analysis.invariants import (Finding, PlanVerificationError,
                                       VerifyResult, verify_plan)
from repro.core.formats import (QUANT_DTYPES, QuantizedBlocks,
                                dequantize_blocks, quant_error_bound,
                                quantize_blocks)
from repro.core.policies import (SchedulePolicy, available_policies,
                                 get_policy, register_policy,
                                 unregister_policy)

from .backends import (BACKENDS, LANE, BlockShapeError, available_backends,
                       default_backend, resolve_backend, set_default_backend,
                       use_backend)
from .executor import apply_plan, execute_plan, pick_bn
from .plan import SPGEMM, SPMM, SegmentPlan
from .planner import (clear_plan_cache, pattern_fingerprint, plan_cache_stats,
                      plan_matmul)

__all__ = [
    # plans
    "SegmentPlan", "SPMM", "SPGEMM",
    "plan_matmul", "execute_plan", "apply_plan", "pick_bn",
    "clear_plan_cache", "plan_cache_stats", "pattern_fingerprint",
    # static verification (full surface lives in repro.analysis)
    "verify_plan", "Finding", "VerifyResult", "PlanVerificationError",
    # quantized block storage
    "QUANT_DTYPES", "QuantizedBlocks", "quantize_blocks",
    "dequantize_blocks", "quant_error_bound",
    # policy registry
    "SchedulePolicy", "register_policy", "unregister_policy", "get_policy",
    "available_policies",
    # backends
    "BACKENDS", "LANE", "BlockShapeError", "available_backends",
    "default_backend", "set_default_backend", "resolve_backend", "use_backend",
]
