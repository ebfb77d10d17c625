"""Mutation-kill battery for the symbolic kernel analyzer.

Five deliberately buggy toy Pallas kernels, one per analyzer rule:

* cross-lane scratch accumulation   -> ``parallel-race``
* out-of-bounds ``pl.ds``           -> ``index-range``
* ring-buffer slot off-by-one read  -> ``ring-slot-war``
* semaphore waited on one branch    -> ``sem-balance``
* oversized VMEM scratch            -> ``vmem-budget``

Each toy must be caught by *exactly* its targeted rule and none of the
others (including the syntactic linter's rules — ``analyze_callable``
merges both layers, so the set-equality assertions double as a
no-collateral-findings proof).  The ring toy additionally pins the
documented ref-base false negative: the syntactic ``read-before-wait``
rule is provably silent on it, only the slot-granular symbolic rule
fires.

The second half pins the static VMEM budget: the analytic per-variant
formulas must agree byte-for-byte with the budget derived from the traced
kernel IR (scratch + BlockSpec windows), and the planner's
``vmem_limit_bytes`` gate must reject an impossible budget at plan time.
"""
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import (
    VmemBudgetError,
    analyze_callable,
    kernel_vmem_bytes,
    lint_callable,
    plan_vmem_bytes,
    spgemm_vmem_bytes,
    spmm_vmem_bytes,
    trace_kernel_irs,
)
from repro.api import execute_plan, plan_matmul
from repro.core.formats import BSR


def _rules(findings):
    return set(f.rule for f in findings)


# ---------------------------------------------------------------------------
# toy kernels
# ---------------------------------------------------------------------------


def _cross_lane_scratch(x):
    """BUG: scratch accumulator initialized only on lane 0 but accumulated
    on every grid point — lane 1 reads lane 0's leftover partial sums."""

    def kernel(in_ref, out_ref, acc_ref):
        lane = pl.program_id(0)
        step = pl.program_id(1)

        @pl.when((lane == 0) & (step == 0))
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += in_ref[...]
        out_ref[...] = acc_ref[...]

    return pl.pallas_call(
        kernel, grid=(2, 2),
        in_specs=[pl.BlockSpec((8, 128), lambda l, s: (l * 2 + s, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda l, s: (l * 2 + s, 0)),
        scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
        interpret=True,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(x)


def _oob_dynamic_slice(x):
    """BUG: grid point 3 reads ``[24, 32)`` from a 24-element ref."""

    def kernel(in_ref, out_ref):
        i = pl.program_id(0)
        out_ref[...] = in_ref[pl.ds(i * 8, 8)]

    return pl.pallas_call(
        kernel, grid=(4,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((8,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((32,), jnp.float32),
        interpret=True,
    )(x)


def _ring_toy(x, *, read_next_slot):
    """Depth-2 DMA ring.  Correct when reading the waited slot
    (``s % 2``); ``read_next_slot=True`` plants the off-by-one — reading
    the slot whose fetch was just issued.  The semaphore accounting stays
    perfectly balanced either way, so only the slot-granular WAR rule can
    tell the two apart."""
    n = 2

    def kernel(hbm_ref, out_ref, buf_ref, sem_ref):
        s = pl.program_id(0)
        slot = s % 2
        nxt = (s + 1) % 2

        @pl.when(s == 0)
        def _prologue():
            pltpu.make_async_copy(hbm_ref.at[pl.ds(0, 8)], buf_ref.at[0],
                                  sem_ref.at[0]).start()

        @pl.when(s + 1 < n)
        def _issue_ahead():
            pltpu.make_async_copy(hbm_ref.at[pl.ds((s + 1) * 8, 8)],
                                  buf_ref.at[nxt], sem_ref.at[nxt]).start()

        pltpu.make_async_copy(hbm_ref.at[pl.ds(s * 8, 8)],
                              buf_ref.at[slot], sem_ref.at[slot]).wait()
        out_ref[...] = buf_ref[nxt if read_next_slot else slot]

    return pl.pallas_call(
        kernel, grid=(n,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((8,), lambda s: (s,)),
        scratch_shapes=[pltpu.VMEM((2, 8), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))],
        out_shape=jax.ShapeDtypeStruct((n * 8,), jnp.float32),
        interpret=True,
    )(x)


def _one_branch_wait(x):
    """BUG: a DMA start on every step but the wait sits under
    ``pl.when(s % 2 == 0)`` — odd steps leak an un-waited start."""
    n = 4

    def kernel(hbm_ref, out_ref, buf_ref, sem_ref):
        s = pl.program_id(0)
        pltpu.make_async_copy(hbm_ref.at[pl.ds(s * 8, 8)], buf_ref.at[0],
                              sem_ref.at[0]).start()

        @pl.when(s % 2 == 0)
        def _even_only():
            pltpu.make_async_copy(hbm_ref.at[pl.ds(s * 8, 8)],
                                  buf_ref.at[0], sem_ref.at[0]).wait()

        out_ref[...] = jnp.ones_like(out_ref)

    return pl.pallas_call(
        kernel, grid=(n,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((8,), lambda s: (s,)),
        scratch_shapes=[pltpu.VMEM((1, 8), jnp.float32),
                        pltpu.SemaphoreType.DMA((1,))],
        out_shape=jax.ShapeDtypeStruct((n * 8,), jnp.float32),
        interpret=True,
    )(x)


def _vmem_hog(x):
    """BUG: a 32 MiB f32 scratch — double the 16 MiB per-core VMEM."""

    def kernel(in_ref, out_ref, big_ref):
        out_ref[...] = in_ref[...]

    return pl.pallas_call(
        kernel, grid=(1,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
        scratch_shapes=[pltpu.VMEM((2048, 4096), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        interpret=True,
    )(x)


# ---------------------------------------------------------------------------
# mutation-kill assertions: exactly one rule each
# ---------------------------------------------------------------------------


def test_cross_lane_scratch_is_killed_by_parallel_race_only():
    x = jnp.zeros((32, 128), jnp.float32)
    findings = analyze_callable(_cross_lane_scratch, x, label="toy-race")
    assert _rules(findings) == {"parallel-race"}, findings
    assert any("scratch" in f.message for f in findings)


def test_oob_ds_is_killed_by_index_range_only():
    x = jnp.zeros((24,), jnp.float32)
    findings = analyze_callable(_oob_dynamic_slice, x, label="toy-oob")
    assert _rules(findings) == {"index-range"}, findings
    # the message names the proven bad footprint
    assert any("[24, 32)" in f.message or "24" in f.message
               for f in findings)


def test_ring_off_by_one_is_killed_by_ring_slot_war_only():
    x = jnp.zeros((16,), jnp.float32)
    buggy = lambda xx: _ring_toy(xx, read_next_slot=True)
    findings = analyze_callable(buggy, x, label="toy-ring")
    assert _rules(findings) == {"ring-slot-war"}, findings
    # the documented ref-base false negative: the syntactic linter sees a
    # wait on the buffer before the read and stays silent
    assert lint_callable(buggy, x, label="toy-ring-syntactic") == []


def test_correct_ring_proves_clean():
    x = jnp.zeros((16,), jnp.float32)
    good = lambda xx: _ring_toy(xx, read_next_slot=False)
    assert analyze_callable(good, x, label="toy-ring-good") == []


def test_one_branch_wait_is_killed_by_sem_balance_only():
    x = jnp.zeros((32,), jnp.float32)
    findings = analyze_callable(_one_branch_wait, x, label="toy-sem")
    assert _rules(findings) == {"sem-balance"}, findings
    assert any("never waited" in f.message for f in findings)


def test_vmem_hog_is_killed_by_vmem_budget_only():
    x = jnp.zeros((8, 128), jnp.float32)
    findings = analyze_callable(_vmem_hog, x, label="toy-vmem")
    assert _rules(findings) == {"vmem-budget"}, findings
    # and a raised limit clears it — the rule reads the knob, not a
    # hard-coded constant
    assert analyze_callable(_vmem_hog, x, label="toy-vmem-big",
                            vmem_limit=64 * 2 ** 20) == []


def test_data_dependent_guard_is_unprovable_not_silent():
    """A wait under a guard the interpreter cannot resolve must produce an
    explicit sem-balance "unprovable" finding, never a silent pass."""

    def fn(x):
        def kernel(hbm_ref, gate_ref, out_ref, buf_ref, sem_ref):
            s = pl.program_id(0)
            pltpu.make_async_copy(hbm_ref.at[pl.ds(s * 8, 8)],
                                  buf_ref.at[0], sem_ref.at[0]).start()

            @pl.when(gate_ref[0] > 0)       # data-dependent
            def _maybe():
                pltpu.make_async_copy(hbm_ref.at[pl.ds(s * 8, 8)],
                                      buf_ref.at[0], sem_ref.at[0]).wait()

            out_ref[...] = jnp.ones_like(out_ref)

        return pl.pallas_call(
            kernel, grid=(2,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((4,), lambda s: (0,))],
            out_specs=pl.BlockSpec((8,), lambda s: (s,)),
            scratch_shapes=[pltpu.VMEM((1, 8), jnp.float32),
                            pltpu.SemaphoreType.DMA((1,))],
            out_shape=jax.ShapeDtypeStruct((16,), jnp.float32),
            interpret=True,
        )(x, jnp.ones((4,), jnp.float32))

    findings = analyze_callable(fn, jnp.zeros((16,), jnp.float32),
                                label="toy-datadep")
    assert _rules(findings) == {"sem-balance"}, findings
    assert any("unprovable" in f.message for f in findings)


# ---------------------------------------------------------------------------
# VMEM budget: analytic formulas == traced-IR accounting, planner gate
# ---------------------------------------------------------------------------


def _traced_total(fn, *args, label):
    irs = trace_kernel_irs(fn, *args, label=label)
    return max(kernel_vmem_bytes(ir)["total"] for ir in irs)


@pytest.fixture(scope="module")
def small_plans():
    a = BSR.random(np.random.default_rng(0), (128, 128), (32, 32), 0.5)
    b = BSR.random(np.random.default_rng(1), (128, 128), (32, 32), 0.5)
    spmm = plan_matmul(a, policy="segment", n_lanes=2, unroll=2, cache=False)
    quant = plan_matmul(a, policy="segment", n_lanes=2, unroll=2,
                        quantize="int8", cache=False)
    spgemm = plan_matmul(a, b, policy="segment", n_lanes=2, unroll=2,
                         cache=False)
    return spmm, quant, spgemm


def test_spmm_budget_matches_traced_kernel(small_plans):
    spmm, _, _ = small_plans
    x = jnp.zeros((128, 64), jnp.float32)
    traced = _traced_total(
        lambda xx: execute_plan(spmm, xx, bn=64, backend="interpret"),
        x, label="budget-spmm")
    analytic = spmm_vmem_bytes(bm=32, bk=32, bn=64, unroll=2,
                               pipelined=True)
    assert traced == analytic == plan_vmem_bytes(spmm, bn=64)


def test_quantized_spmm_budget_matches_traced_kernel(small_plans):
    _, quant, _ = small_plans
    x = jnp.zeros((128, 64), jnp.float32)
    traced = _traced_total(
        lambda xx: execute_plan(quant, xx, bn=64, backend="interpret"),
        x, label="budget-quant")
    analytic = spmm_vmem_bytes(bm=32, bk=32, bn=64, unroll=2,
                               block_dtype="int8", quantized=True,
                               pipelined=True)
    assert traced == analytic == plan_vmem_bytes(quant, bn=64)


@pytest.mark.parametrize("pipelined", [True, False])
def test_rowwise_spmm_budget_matches_traced_kernel(pipelined):
    """Rowwise scales are VMEM-resident on both executor paths (windowed
    operand pipelined, per-item windows legacy) — the closed form must
    track the traced kernels byte-for-byte like the per-block pin above."""
    a = BSR.random(np.random.default_rng(3), (128, 128), (32, 32), 0.5)
    plan = plan_matmul(a, policy="segment", n_lanes=2, unroll=2,
                       quantize="int8.rowwise", pipeline=pipelined,
                       cache=False)
    x = jnp.zeros((128, 64), jnp.float32)
    traced = _traced_total(
        lambda xx: execute_plan(plan, xx, bn=64, backend="interpret"),
        x, label=f"budget-rowwise-{pipelined}")
    analytic = spmm_vmem_bytes(bm=32, bk=32, bn=64, unroll=2,
                               block_dtype="int8", quantized=True,
                               rowwise=True, pipelined=pipelined)
    assert traced == analytic == plan_vmem_bytes(plan, bn=64)


def test_rowwise_spgemm_budget_matches_traced_kernel():
    a = BSR.random(np.random.default_rng(4), (128, 128), (32, 32), 0.5)
    b = BSR.random(np.random.default_rng(5), (128, 128), (32, 32), 0.5)
    plan = plan_matmul(a, b, policy="segment", n_lanes=2, unroll=2,
                       quantize="fp8.rowwise", cache=False)
    traced = _traced_total(
        lambda: execute_plan(plan, backend="interpret"),
        label="budget-rowwise-spgemm")
    analytic = spgemm_vmem_bytes(bm=32, bk=32, bn=32, unroll=2,
                                 block_dtype="float8_e4m3fn",
                                 rhs_dtype="float8_e4m3fn",
                                 quant_a=True, quant_b=True, rowwise=True,
                                 pipelined=True)
    assert traced == analytic == plan_vmem_bytes(plan)


def test_spgemm_budget_matches_traced_kernel(small_plans):
    _, _, spgemm = small_plans
    traced = _traced_total(
        lambda: execute_plan(spgemm, backend="interpret"),
        label="budget-spgemm")
    analytic = spgemm_vmem_bytes(bm=32, bk=32, bn=32, unroll=2,
                                 pipelined=True)
    assert traced == analytic == plan_vmem_bytes(spgemm)


def test_planner_vmem_gate(small_plans):
    a = BSR.random(np.random.default_rng(2), (128, 128), (32, 32), 0.5)
    # a budget no kernel instance fits: named error at plan time
    with pytest.raises(VmemBudgetError, match="VMEM working set"):
        plan_matmul(a, policy="segment", n_lanes=2, unroll=2, cache=False,
                    vmem_limit_bytes=64 * 1024)
    # the default 16 MiB budget admits every shipped knob point
    plan = plan_matmul(a, policy="segment", n_lanes=2, unroll=2,
                       cache=False, vmem_limit_bytes=16 * 2 ** 20)
    assert 0 < plan_vmem_bytes(plan, bn=64) <= 16 * 2 ** 20


def test_shipped_spmm_variant_proves_clean(small_plans):
    """Representative end-to-end proof on a real shipped kernel (the full
    variant grid runs in scripts/ci.sh via `python -m
    repro.analysis.jaxpr_lint`)."""
    spmm, _, _ = small_plans
    x = jnp.zeros((128, 64), jnp.float32)
    findings = analyze_callable(
        lambda xx: execute_plan(spmm, xx, bn=64, backend="interpret"),
        x, label="shipped-spmm")
    assert findings == []


# ---------------------------------------------------------------------------
# verify_plans artifact
# ---------------------------------------------------------------------------


def test_verify_plans_json_artifact(tmp_path):
    out = tmp_path / "verify.json"
    repo = Path(__file__).resolve().parents[1]
    proc = subprocess.run(
        [sys.executable, str(repo / "scripts" / "verify_plans.py"),
         "--fast", "--scale", "64", "-q", "--json", str(out)],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    d = json.loads(out.read_text())
    assert d["level"] == "fast"
    assert d["summary"]["ok"] and d["summary"]["n_findings"] == 0
    assert d["summary"]["n_plans"] == len(d["plans"]) > 0
    for rec in d["plans"]:
        assert rec["ok"] and rec["findings"] == []
        assert rec["kind"] in ("spmm", "spgemm")
        assert rec["checked"] > 0
