"""Static Pallas kernel hazard linter over traced jaxprs.

The Segment kernels hand-schedule their DMA pipeline — async copies into
ring-buffered VMEM scratch, gated by scalar-prefetch fetch flags, waited on
per-slot semaphores — and two hazard classes have already bitten at
runtime (CHANGES.md): reading ``pl.program_id`` *inside* a ``pl.when``
branch (interpret mode evaluates both arms, so the read observes a grid
position the guard excluded), and consuming a VMEM destination before its
DMA wait.  Neither is caught by the type system or by a passing parity
test on a lucky schedule; both are visible in the kernel's jaxpr.

This module traces kernel-bearing callables with :func:`jax.make_jaxpr`
(pure tracing — nothing is compiled or lowered, so it runs on any host),
digs the ``pallas_call`` kernel jaxprs out, and walks them for a small
rule catalog:

* ``program-id-in-when`` — a ``program_id`` read nested under a ``cond``
  (what ``pl.when`` lowers to);
* ``dma-start-without-wait`` — a semaphore with ``dma_start`` issues but
  no ``dma_wait`` anywhere in the kernel (the copy's completion is never
  observed, so slot reuse races the hardware);
* ``read-before-wait`` — the first ``get`` of a DMA destination buffer
  precedes every ``dma_wait`` on that buffer in kernel program order
  (cond branches walked in order).

The walk is ref-base-granular: a ``(depth, …)`` ring buffer is one base,
so per-slot false negatives are possible, but the discipline the shipped
kernels follow (issue step ``s+1``, wait, then read) is exactly what the
rules check.  ``python -m repro.analysis.jaxpr_lint`` lints the shipped
SpMM/SpGEMM kernel variants and exits 1 on any finding — the CI gate.

Imports: ``repro.api`` / ``repro.kernels`` are imported *lazily* inside
:func:`lint_segment_kernels` only — this module must stay importable from
anywhere in the layering (tests lint toy kernels without touching the
planner).
"""
from __future__ import annotations

import dataclasses
import sys
from typing import Dict, List, Optional, Set, Tuple

import jax

RULES: Dict[str, str] = {
    "program-id-in-when":
        "pl.program_id must be read once at the kernel top level, never "
        "inside a pl.when branch (interpret mode evaluates both arms)",
    "dma-start-without-wait":
        "every semaphore that gates make_async_copy starts needs a "
        "matching wait before its slot can be reused",
    "read-before-wait":
        "a VMEM DMA destination may only be read after a dma_wait on it "
        "in kernel program order",
}


@dataclasses.dataclass(frozen=True)
class LintFinding:
    """One hazard flagged in a kernel jaxpr."""

    rule: str
    message: str
    kernel: str = "<kernel>"
    severity: str = "error"

    def __str__(self) -> str:
        return f"[{self.rule}] kernel {self.kernel!r}: {self.message}"


def _is_sem(var) -> bool:
    aval = getattr(var, "aval", None)
    return aval is not None and "semaphore" in str(aval).lower()


def _is_ref(var) -> bool:
    aval = getattr(var, "aval", None)
    return (aval is not None and "Ref" in type(aval).__name__
            and not _is_sem(var))


def _is_var(v) -> bool:
    # Literals carry .val; proper jaxpr variables do not
    return hasattr(v, "aval") and not hasattr(v, "val")


def _iter_subjaxprs(value):
    """Yield every (Closed)Jaxpr reachable from one eqn param value."""
    vals = value if isinstance(value, (tuple, list)) else (value,)
    for v in vals:
        inner = getattr(v, "jaxpr", None)
        if inner is not None and hasattr(inner, "eqns"):
            yield inner          # ClosedJaxpr
        elif hasattr(v, "eqns"):
            yield v              # bare Jaxpr


class _KernelWalk:
    """Linearized walk of one kernel jaxpr with ref canonicalization.

    ``base`` maps sub-jaxpr invars back to the outer variable they alias
    (cond branch invars ↔ cond operands), so reads/waits on a buffer are
    attributed to one canonical base no matter how deep the branch.
    """

    def __init__(self, kernel_name: str):
        self.kernel = kernel_name
        self.findings: List[LintFinding] = []
        self.base: Dict[object, object] = {}
        self.sem_starts: Dict[object, int] = {}
        self.sem_waits: Dict[object, int] = {}
        self.dma_dst: Set[object] = set()
        self.waited: Set[object] = set()
        self.read_before_wait: Set[object] = set()

    def canon(self, v):
        while v in self.base:
            v = self.base[v]
        return v

    def _alias(self, sub_invars, operands):
        for bv, ov in zip(sub_invars, operands):
            if _is_var(ov):
                self.base[bv] = self.canon(ov)

    def walk(self, jaxpr, when_depth: int = 0) -> None:
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "cond":
                for br in eqn.params.get("branches", ()):
                    sub = getattr(br, "jaxpr", br)
                    self._alias(sub.invars, eqn.invars[1:])
                    self.walk(sub, when_depth + 1)
                continue
            if name == "program_id":
                if when_depth >= 1:
                    self.findings.append(LintFinding(
                        "program-id-in-when",
                        f"program_id(axis={eqn.params.get('axis')}) read "
                        f"inside a pl.when branch (cond nesting depth "
                        f"{when_depth}) — hoist the read to the kernel top "
                        f"level and close over the value",
                        kernel=self.kernel))
                continue
            if name == "dma_start":
                refs = [self.canon(v) for v in eqn.invars
                        if _is_var(v) and _is_ref(v)]
                sems = [self.canon(v) for v in eqn.invars
                        if _is_var(v) and _is_sem(v)]
                for s in sems:
                    self.sem_starts[s] = self.sem_starts.get(s, 0) + 1
                if refs:
                    dst = refs[-1]   # (src_ref, ..., dst_ref, ..., sem)
                    self.dma_dst.add(dst)
                    self.waited.discard(dst)   # a fresh copy is in flight
                continue
            if name == "dma_wait":
                for v in eqn.invars:
                    if not _is_var(v):
                        continue
                    if _is_sem(v):
                        s = self.canon(v)
                        self.sem_waits[s] = self.sem_waits.get(s, 0) + 1
                    elif _is_ref(v):
                        self.waited.add(self.canon(v))
                continue
            if name == "get" and eqn.invars and _is_var(eqn.invars[0]):
                b = self.canon(eqn.invars[0])
                if b in self.dma_dst and b not in self.waited \
                        and b not in self.read_before_wait:
                    self.read_before_wait.add(b)
                    self.findings.append(LintFinding(
                        "read-before-wait",
                        "VMEM DMA destination is read before any dma_wait "
                        "on it in kernel program order — the buffer may "
                        "still hold the previous tile (or garbage) when "
                        "the MXU consumes it",
                        kernel=self.kernel))
                continue
            # generic recursion (run_scoped, pjit-in-kernel, loops):
            # sub-jaxpr invars alias the eqn operands where they line up
            for pv in eqn.params.values():
                for sub in _iter_subjaxprs(pv):
                    if len(sub.invars) == len(eqn.invars):
                        self._alias(sub.invars, eqn.invars)
                    self.walk(sub, when_depth)

    def finish(self) -> List[LintFinding]:
        for s, n in self.sem_starts.items():
            if self.sem_waits.get(s, 0) == 0:
                self.findings.append(LintFinding(
                    "dma-start-without-wait",
                    f"semaphore sees {n} dma_start(s) but no dma_wait "
                    f"anywhere in the kernel — completion is never "
                    f"observed, so ring-slot reuse races the copy engine",
                    kernel=self.kernel))
        return self.findings


def lint_kernel_jaxpr(jaxpr, kernel_name: str = "<kernel>"
                      ) -> List[LintFinding]:
    """Run the rule catalog over one already-extracted kernel jaxpr."""
    w = _KernelWalk(kernel_name)
    w.walk(jaxpr)
    return w.finish()


def find_pallas_kernels(jaxpr) -> List[Tuple[str, object]]:
    """Collect ``(name, kernel_jaxpr)`` for every pallas_call reachable."""
    out: List[Tuple[str, object]] = []

    def rec(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                kj = eqn.params.get("jaxpr")
                info = eqn.params.get("name_and_src_info")
                name = (getattr(info, "name", None)
                        or eqn.params.get("name") or "<pallas_call>")
                if kj is not None:
                    out.append((str(name), getattr(kj, "jaxpr", kj)))
            for pv in eqn.params.values():
                for sub in _iter_subjaxprs(pv):
                    rec(sub)

    rec(getattr(jaxpr, "jaxpr", jaxpr))
    return out


def lint_callable(fn, *args, label: Optional[str] = None,
                  **kwargs) -> List[LintFinding]:
    """Trace ``fn(*args, **kwargs)`` and lint every Pallas kernel inside.

    Tracing never compiles or lowers — safe on hosts with no accelerator
    (the CI gate runs this on CPU).  Raises ``ValueError`` when the trace
    contains no ``pallas_call`` at all: linting nothing silently would
    make the CI stage vacuous.
    """
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    kernels = find_pallas_kernels(closed)
    if not kernels:
        raise ValueError(
            f"no pallas_call found while tracing "
            f"{label or getattr(fn, '__name__', fn)!r} — nothing to lint")
    findings: List[LintFinding] = []
    for name, kj in kernels:
        findings.extend(lint_kernel_jaxpr(
            kj, kernel_name=f"{label}:{name}" if label else name))
    return findings


# ---------------------------------------------------------------------------
# Shipped-kernel entry point (the CI gate)
# ---------------------------------------------------------------------------


def lint_segment_kernels(verbose: bool = False) -> List[LintFinding]:
    """Lint every shipped Segment kernel variant the executor can emit.

    Builds tiny plans and traces the real executor paths: SpMM (fp32 +
    quantized + the transposed backward schedule via the custom VJP) and
    SpGEMM.  ``repro.api`` is imported lazily here — the linter core must
    not depend on the planner.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.api import apply_plan, execute_plan, plan_matmul
    from repro.core.formats import BSR

    findings: List[LintFinding] = []
    a = BSR.random(np.random.default_rng(0), (128, 128), (32, 32), 0.5)
    b = BSR.random(np.random.default_rng(1), (128, 128), (32, 32), 0.5)
    x = jnp.zeros((128, 64), jnp.float32)

    plan = plan_matmul(a, policy="segment", n_lanes=2, unroll=2,
                       with_grad=True, cache=False)
    qplan = plan_matmul(a, policy="segment", n_lanes=2, unroll=2,
                        quantize="int8", cache=False)
    gplan = plan_matmul(a, b, policy="segment", n_lanes=2, unroll=2,
                        cache=False)

    traces = [
        ("spmm-pipelined",
         lambda: jax.make_jaxpr(
             lambda xx: execute_plan(plan, xx, bn=64,
                                     backend="interpret"))(x)),
        ("spmm-grad",
         lambda: jax.make_jaxpr(jax.grad(
             lambda xx: apply_plan(plan, xx, bn=64,
                                   backend="interpret").sum()))(x)),
        ("spmm-quantized",
         lambda: jax.make_jaxpr(
             lambda xx: execute_plan(qplan, xx, bn=64,
                                     backend="interpret"))(x)),
        ("spgemm-pipelined",
         lambda: jax.make_jaxpr(
             lambda: execute_plan(gplan, backend="interpret"))()),
    ]
    for label, trace in traces:
        kernels = find_pallas_kernels(trace())
        if not kernels:
            raise ValueError(f"variant {label!r} traced to no pallas_call "
                             f"— the lint gate would be vacuous")
        for name, kj in kernels:
            fs = lint_kernel_jaxpr(kj, kernel_name=f"{label}:{name}")
            findings.extend(fs)
            if verbose:
                state = (f"{len(fs)} finding(s)" if fs else "clean")
                print(f"  lint {label}:{name}: {state}")
    return findings


# ---------------------------------------------------------------------------
# Symbolic analysis entry points (abstract interpretation; see accesses.py,
# ranges.py, races.py, budget.py — imported lazily so the syntactic linter
# stays importable on its own)
# ---------------------------------------------------------------------------


def _analyze_trace(closed, args, label: str, vmem_limit=None,
                   verbose: bool = False) -> List[LintFinding]:
    """Syntactic lint + every symbolic rule over one traced jaxpr."""
    from .accesses import find_kernel_invocations, kernel_ir_from_eqn
    from .budget import DEFAULT_VMEM_LIMIT_BYTES, check_vmem_budget
    from .order import check_order
    from .races import (check_parallel_races, check_ring_war,
                        check_sem_balance)
    from .ranges import check_ranges

    limit = DEFAULT_VMEM_LIMIT_BYTES if vmem_limit is None else vmem_limit
    kernels = find_pallas_kernels(closed)
    if not kernels:
        raise ValueError(f"no pallas_call found while tracing {label!r} "
                         f"— nothing to analyze")
    findings: List[LintFinding] = []
    for name, kj in kernels:
        findings.extend(lint_kernel_jaxpr(kj, kernel_name=f"{label}:{name}"))
    for name, eqn, scalars in find_kernel_invocations(closed, args):
        ir = kernel_ir_from_eqn(eqn, name=f"{label}:{name}", scalars=scalars)
        before = len(findings)
        findings.extend(check_ranges(ir))
        findings.extend(check_parallel_races(ir))
        findings.extend(check_ring_war(ir))
        findings.extend(check_sem_balance(ir))
        findings.extend(check_order(ir))
        findings.extend(check_vmem_budget(ir, limit))
        if verbose:
            n = len(findings) - before
            state = f"{n} finding(s)" if n else "proved clean"
            print(f"  analyze {ir.name}: grid={ir.grid} "
                  f"parallel={ir.parallel_axes} {state}")
    return findings


def analyze_callable(fn, *args, label: Optional[str] = None,
                     vmem_limit: Optional[int] = None,
                     **kwargs) -> List[LintFinding]:
    """Trace ``fn(*args, **kwargs)`` and run the syntactic linter plus the
    full symbolic rule set (index-range, parallel-race, ring-slot-war,
    sem-balance, vmem-budget, and the inter-pass ordering rules
    cross-pass-war / sem-carryover / prefetch-raw / dma-priority) on every
    Pallas kernel inside.

    Scalar-prefetch operands are resolved from the trace's constants and
    the concrete ``args``, so the proofs are exact over the traced grid.
    Raises ``ValueError`` when the trace holds no ``pallas_call``.
    """
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return _analyze_trace(closed, args,
                          label or getattr(fn, "__name__", str(fn)),
                          vmem_limit=vmem_limit)


def analyze_shipped_kernels(verbose: bool = False) -> List[LintFinding]:
    """The full static gate: syntactic lint + symbolic proofs over every
    shipped Pallas kernel × a knob grid.

    Covers the four Segment variants :func:`lint_segment_kernels` traces
    (SpMM fwd/grad/quantized, SpGEMM) plus
    extra (n_lanes, unroll) and fp8 knob points, and extends the gate to
    the non-Segment kernels — ``flash_attention`` (causal, and
    windowed+GQA to exercise the ``rem``-guarded skip path), ``moe_gemm``,
    and ``rg_lru`` — so their ``parallel`` axes get the same race proof.

    The ``prefetch="cross_pass"`` variants run at ``bn=32`` so the traced
    grid carries two N tiles — with a single tile the cross-pass tail
    guard is never true and the ordering proofs would be vacuous.  Every
    prefetch-enabled variant must prove clean under the inter-pass rules
    (cross-pass-war, sem-carryover, prefetch-raw, dma-priority) before CI
    lets it ship.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.api import apply_plan, execute_plan, plan_matmul
    from repro.core.formats import BSR
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.moe_gemm import moe_gemm
    from repro.kernels.rg_lru import rg_lru

    a = BSR.random(np.random.default_rng(0), (128, 128), (32, 32), 0.5)
    b = BSR.random(np.random.default_rng(1), (128, 128), (32, 32), 0.5)
    x = jnp.zeros((128, 64), jnp.float32)

    def spmm(n_lanes, unroll, bn=64, **kw):
        p = plan_matmul(a, policy="segment", n_lanes=n_lanes, unroll=unroll,
                        cache=False, **kw)
        return p, lambda: jax.make_jaxpr(
            lambda xx: execute_plan(p, xx, bn=bn, backend="interpret"))(x)

    plan, _ = spmm(2, 2, with_grad=True)
    gplan = plan_matmul(a, b, policy="segment", n_lanes=2, unroll=2,
                        cache=False)
    gplan1 = plan_matmul(a, b, policy="segment", n_lanes=1, unroll=1,
                         cache=False)
    # cross-pass prefetch variants: bn=32 over the 64-wide rhs → two N
    # tiles, so the traced grid actually contains the tail-issue pass
    # boundary the ordering rules certify
    pf_plan, _ = spmm(2, 2, bn=32, with_grad=True, prefetch="cross_pass")
    gplan_pf = plan_matmul(a, b, policy="segment", n_lanes=2, unroll=2,
                           cache=False, prefetch="cross_pass")

    q = jnp.zeros((2, 256, 64), jnp.float32)
    kv = jnp.zeros((2, 256, 64), jnp.float32)
    xt = jnp.zeros((2, 256, 16), jnp.float32)
    h0 = jnp.zeros((2, 16), jnp.float32)
    ap = jnp.zeros((16,), jnp.float32)
    n_experts = 4
    # drop-free layout: one spare chunk past the used ones, which skips
    chunk_expert = jnp.array([0, 1, 2, 3, 3], jnp.int32)
    n_used = jnp.array([4], jnp.int32)
    layer = jnp.array([1], jnp.int32)
    xs = jnp.zeros((5 * 128, 32), jnp.float32)
    w = jnp.zeros((2, n_experts, 32, 64), jnp.float32)

    traces = [
        ("spmm-pipelined",
         lambda: jax.make_jaxpr(
             lambda xx: execute_plan(plan, xx, bn=64,
                                     backend="interpret"))(x), (x,)),
        ("spmm-grad",
         lambda: jax.make_jaxpr(jax.grad(
             lambda xx: apply_plan(plan, xx, bn=64,
                                   backend="interpret").sum()))(x), (x,)),
        ("spmm-quantized-int8", spmm(2, 2, quantize="int8")[1], (x,)),
        ("spmm-quantized-fp8", spmm(1, 1, quantize="fp8")[1], (x,)),
        ("spmm-lanes1", spmm(1, 1)[1], (x,)),
        ("spmm-lanes4", spmm(4, 2)[1], (x,)),
        ("spmm-prefetch",
         lambda: jax.make_jaxpr(
             lambda xx: execute_plan(pf_plan, xx, bn=32,
                                     backend="interpret"))(x), (x,)),
        ("spmm-prefetch-grad",
         lambda: jax.make_jaxpr(jax.grad(
             lambda xx: apply_plan(pf_plan, xx, bn=32,
                                   backend="interpret").sum()))(x), (x,)),
        ("spmm-prefetch-quant-int8",
         spmm(2, 2, bn=32, quantize="int8", prefetch="cross_pass")[1], (x,)),
        ("spmm-prefetch-lanes1",
         spmm(1, 1, bn=32, prefetch="cross_pass")[1], (x,)),
        ("spgemm-pipelined",
         lambda: jax.make_jaxpr(
             lambda: execute_plan(gplan, backend="interpret"))(), ()),
        ("spgemm-lanes1",
         lambda: jax.make_jaxpr(
             lambda: execute_plan(gplan1, backend="interpret"))(), ()),
        ("spgemm-prefetch",
         lambda: jax.make_jaxpr(
             lambda: execute_plan(gplan_pf, backend="interpret"))(), ()),
        ("flash-causal",
         lambda: jax.make_jaxpr(lambda qq, kk, vv: flash_attention(
             qq, kk, vv, causal=True, interpret=True))(q, kv, kv),
         (q, kv, kv)),
        ("flash-window-gqa",
         lambda: jax.make_jaxpr(lambda qq, kk, vv: flash_attention(
             qq, kk, vv, causal=True, window=128, q_period=128,
             interpret=True))(q, kv, kv), (q, kv, kv)),
        ("moe-gemm",
         lambda: jax.make_jaxpr(lambda xx, ww, ce, nu, ly: moe_gemm(
             xx, ww, ce, nu, ly, chunk_rows=128, bn=64,
             interpret=True))(xs, w, chunk_expert, n_used, layer),
         (xs, w, chunk_expert, n_used, layer)),
        ("rg-lru",
         lambda: jax.make_jaxpr(lambda *args: rg_lru(
             *args, ct=128, interpret=True))(xt, xt, xt, ap, h0),
         (xt, xt, xt, ap, h0)),
    ]
    findings: List[LintFinding] = []
    for label, trace, args in traces:
        findings.extend(_analyze_trace(trace(), args, label,
                                       verbose=verbose))
    return findings


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    verbose = "-q" not in argv
    if "--syntactic" in argv:
        print("linting shipped Segment kernel variants "
              f"({len(RULES)} rules: {', '.join(sorted(RULES))})")
        findings = lint_segment_kernels(verbose=verbose)
    else:
        from .order import ORDER_RULES
        from .races import ANALYZER_RULES
        rules = sorted(set(RULES) | set(ANALYZER_RULES) | set(ORDER_RULES))
        print("analyzing shipped Pallas kernels "
              f"({len(rules)} rules: {', '.join(rules)})")
        findings = analyze_shipped_kernels(verbose=verbose)
    if findings:
        print(f"FAIL: {len(findings)} hazard(s)")
        for f in findings:
            print(f"  {f}")
        return 1
    print("OK: all kernel variants analyze clean")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
