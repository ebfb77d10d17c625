"""Kernel-level benchmarks: Segment-schedule traffic savings (the TPU reuse
metric) + lane-parallel interpret wall time vs the dense oracle.

Emits ``BENCH_kernels.json`` (CI smoke target — the kernel perf trajectory
is tracked from this file, alongside ``BENCH_serve.json`` for serving):

    PYTHONPATH=src python -m benchmarks.kernel_bench --out BENCH_kernels.json

Policies are enumerated from the registry (``repro.api.available_policies``)
so newly registered dataflows show up in the sweep without editing this file.
The lane sweep runs the 512×512 SpMM case at 1/2/4 lanes and reports
interpret-mode wall time (median of ``--repeats`` interleaved warm calls),
max error vs the dense oracle, modeled HBM traffic, and the LPT load
imbalance.  The quant sweep runs the standard weight-bound case at
fp32/int8/fp8 block storage and reports traffic-bytes ratios vs fp32 plus
normalized max error vs the dense fp32 oracle (CI gates both).  The
pipeline sweep checks the DMA-pipeline fetch contract (modeled fetch count
== schedule fetch-flag count, exactly, both kernels) and tracks interpret
wall time.  The prefetch sweep runs the lane
case with ``prefetch="cross_pass"`` across a two-N-tile grid and gates the
mode end to end: bit-exact parity vs the drained schedule, the traffic
model's ``prefetch_fetches`` against an independent head-window fetch-flag
sum, a clean full-level verify, zero inter-pass ordering findings from
``repro.analysis.order`` over the traced kernels, and an interpret
wall-time ratio (the overlap win itself needs real hardware — interpret
replays every copy inline, so CI only gates against regressions).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np
import jax.numpy as jnp

from repro import api
from repro.analysis import (analyze_callable, check_scale_agreement,
                            plan_vmem_bytes, verify_plan)
from repro.core.formats import BSR

from .common import Csv

LANE_CASE = dict(shape=(512, 512), block=(64, 64), blocks_per_row=2,
                 n_cols=256, bn=128)
LANES = (1, 2, 4)

# Standard quantization case: a weight-bound SpMM (decode-like narrow rhs)
# where A-tile bytes dominate the modeled traffic — the configuration
# quantized block storage targets.
QUANT_CASE = dict(shape=(1024, 2048), block=(128, 128), density=0.25,
                  n_cols=32, bn=32)
QUANT_MODES = ("fp32", "int8", "fp8")


def traffic_sweep() -> dict:
    """Schedule-traffic ratios of every registered policy vs ``segment``."""
    rng = np.random.default_rng(0)
    policies = api.available_policies()
    out = {}
    for (m, k, blk, dens) in [(1024, 1024, 128, 0.25), (2048, 1024, 128, 0.1),
                              (512, 2048, 64, 0.3)]:
        a = BSR.random(rng, (m, k), (blk, blk), dens)
        tr = {p: api.plan_matmul(a, n_cols_hint=1024, policy=p).traffic
              for p in policies}
        base = {p: t["total"] for p, t in tr.items() if p != "segment"}
        key = f"M{m}_K{k}_b{blk}_d{dens}"
        out[key] = {f"segment_traffic_saving_vs_{p}": base[p] / tr["segment"]["total"]
                    for p in base}
    return out


def _balanced_bsr(rng) -> BSR:
    """Uniform blocks-per-row 512×512 pattern (0.25 block density).

    Load-balanced sparsity is the lane feature's target configuration:
    chains pack into lanes with zero padding, so the interpret-mode wall
    time (which emulates the grid *sequentially* — lanes can only tie, the
    concurrency win needs real hardware) compares equal step counts.
    """
    m, k = LANE_CASE["shape"]
    bm, bk = LANE_CASE["block"]
    gm, gk = m // bm, k // bk
    brow, bcol = [], []
    for r in range(gm):
        cols = rng.choice(gk, size=LANE_CASE["blocks_per_row"], replace=False)
        for c in sorted(cols.tolist()):
            brow.append(r)
            bcol.append(c)
    return BSR(shape=(m, k), block_shape=(bm, bk),
               brow=np.asarray(brow, np.int32),
               bcol=np.asarray(bcol, np.int32),
               blocks=rng.standard_normal(
                   (len(brow), bm, bk)).astype(np.float32))


def lane_sweep(repeats: int = 12) -> dict:
    """Interpret wall time + dense-oracle parity for 1/2/4 lanes.

    Timing is interleaved round-robin across lane counts (kills drift bias)
    and reported as min/median of ``repeats`` warm calls.
    """
    rng = np.random.default_rng(1)
    a = _balanced_bsr(rng)
    bd = jnp.asarray(rng.standard_normal(
        (LANE_CASE["shape"][1], LANE_CASE["n_cols"])).astype(np.float32))
    want = a.to_dense() @ np.asarray(bd)

    runs = {}
    for lanes in LANES:
        plan = api.plan_matmul(a, bd.shape, n_lanes=lanes)
        fn = jax.jit(lambda p, x: api.execute_plan(
            p, x, bn=LANE_CASE["bn"], backend="interpret"))
        got = np.asarray(fn(plan, bd))                 # compile + warm
        runs[lanes] = (plan, fn, float(np.abs(got - want).max()))
    times = {lanes: [] for lanes in LANES}
    for _ in range(repeats):
        for lanes, (plan, fn, _err) in runs.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn(plan, bd))
            times[lanes].append((time.perf_counter() - t0) * 1e6)

    out = {}
    for lanes, (plan, _fn, err) in runs.items():
        ts = sorted(times[lanes])
        tr = plan.traffic
        out[str(lanes)] = {
            "effective_lanes": plan.n_lanes,
            "interpret_us": ts[len(ts) // 2],          # median
            "interpret_us_min": ts[0],
            "max_err": err,
            "traffic_total_bytes": tr["total"],
            "b_fetches": tr["b_fetches"],
            "lane_imbalance": tr.get("imbalance", 1.0),
            "padded_items": tr.get("padded_items", 0),
            # static analyzer's VMEM working set at this case's bn (the
            # budget the planner's vmem_limit_bytes knob would enforce)
            "vmem_bytes": plan_vmem_bytes(plan, bn=LANE_CASE["bn"]),
        }
    return out


def quant_sweep() -> dict:
    """Quantized block storage: traffic bytes + dense-fp32-oracle parity.

    Runs the standard quant case (``QUANT_CASE``) at fp32 / int8 / fp8
    block storage and reports the modeled HBM traffic (quantized payload +
    per-block scales vs fp32 tiles) and ``max_err`` — the max absolute
    deviation from the dense fp32 oracle, normalized by the oracle's max
    magnitude (so the bound is scale-free and K-independent enough to gate
    in CI; see docs/API.md for the documented bounds).
    """
    rng = np.random.default_rng(3)
    m, k = QUANT_CASE["shape"]
    a = BSR.random(rng, (m, k), QUANT_CASE["block"], QUANT_CASE["density"])
    x = jnp.asarray(rng.standard_normal(
        (k, QUANT_CASE["n_cols"])).astype(np.float32))
    want = a.to_dense() @ np.asarray(x)
    norm = float(np.abs(want).max())
    out = {}
    for mode in QUANT_MODES:
        plan = api.plan_matmul(a, x.shape,
                               quantize=None if mode == "fp32" else mode)
        got = np.asarray(plan(x, bn=QUANT_CASE["bn"], backend="interpret"))
        tr = plan.traffic
        out[mode] = {
            "traffic_total_bytes": tr["total"],
            "a_bytes": tr["a_bytes"],
            "max_err": float(np.abs(got - want).max() / norm),
            "vmem_bytes": plan_vmem_bytes(plan, bn=QUANT_CASE["bn"]),
        }
    for mode in QUANT_MODES[1:]:
        out[mode]["traffic_ratio_vs_fp32"] = (
            out["fp32"]["traffic_total_bytes"]
            / out[mode]["traffic_total_bytes"])
    return out


def pipeline_sweep(repeats: int = 12) -> dict:
    """DMA-pipeline contract + interpret wall time.

    Three gates ride this section in CI:

    * **static verification** — ``repro.analysis.verify_plan(level="full")``
      must report zero findings on both kernels' bench plans
      (``verify_findings``).  The full level includes the
      ``traffic-agreement`` invariant — the model-vs-fetch-flag exact count
      equality this bench used to assert inline, now one catalog entry
      among twelve (the flags gate the in-kernel ``make_async_copy``
      issues, so the model's byte pricing is kernel reality, not an
      estimate); the raw model/flag counts stay in the JSON for trending;
    * **verification overhead** — ``verify_build_overhead`` is the
      amortized wall-time cost of ``plan_matmul(..., verify="full")`` over
      a cache-miss build plus warm realizes of this case's plan, gated
      < 10% (verification runs once per cached template);
    * **wall time** — interpret-mode median and min for the executor path.
      Interpret mode *emulates* every DMA and semaphore op sequentially, so
      the overlap win needs real hardware — the time is tracked for
      trending, not as a speedup claim.
    """
    rng = np.random.default_rng(2)
    a = _balanced_bsr(rng)
    bd = jnp.asarray(rng.standard_normal(
        (LANE_CASE["shape"][1], LANE_CASE["n_cols"])).astype(np.float32))
    want = a.to_dense() @ np.asarray(bd)
    plan = api.plan_matmul(a, bd.shape, n_lanes=2)
    tr = plan.traffic
    out = {
        "model_a_fetches": int(tr["a_fetches"]),
        "flag_a_fetches": int(np.asarray(plan.a_fetch).sum()),
        "model_b_fetches": int(tr["b_fetches"]),
        "flag_b_fetches": int(np.asarray(plan.b_fetch).sum()),
    }
    # spgemm fetch contract (A and B block streams both flag-gated); dense
    # enough that the symbolic intersection is guaranteed non-empty — an
    # empty triple list would gate 0 == 0 and check nothing
    ga = BSR.random(np.random.default_rng(4), (256, 256), (32, 32), 0.5)
    gb = BSR.random(np.random.default_rng(5), (256, 256), (32, 32), 0.5)
    gplan = api.plan_matmul(ga, gb, n_lanes=2)
    gtr = gplan.traffic
    out.update(
        spgemm_model_a_fetches=int(gtr["a_fetches"]),
        spgemm_flag_a_fetches=int(np.asarray(gplan.a_fetch).sum()),
        spgemm_model_b_fetches=int(gtr["b_fetches"]),
        spgemm_flag_b_fetches=int(np.asarray(gplan.b_fetch).sum()))

    # static verification of both bench plans (the full level subsumes the
    # fetch contract via the traffic-agreement invariant)
    findings = (verify_plan(plan, level="full").findings
                + verify_plan(gplan, level="full").findings)
    out["verify_findings"] = len(findings)
    out["verify_finding_ids"] = sorted({f.invariant for f in findings})

    # analyzer VMEM budgets for the two kernel instances this sweep
    # exercises (SpMM at this bn, SpGEMM)
    out["vmem_bytes_pipelined"] = plan_vmem_bytes(plan, bn=LANE_CASE["bn"])
    out["vmem_bytes_spgemm"] = plan_vmem_bytes(gplan)

    # amortized cost of verify="full": the hook adds exactly two things to
    # plan_matmul — one full-catalog template verification per cache miss
    # and one O(1) scale check per realize — so the overhead over a
    # cache-miss build plus 24 warm realizes is measured component-wise
    # ((verify + 25*scale) / (miss + 24*hit), each term min-of-many) rather
    # than by differencing whole cycles, which on a loaded runner buries
    # the ~6% signal in run-to-run variance.  The 24:1 hit:miss ratio is
    # the conservative end of steady state: any serving or training loop
    # realizes one fingerprint thousands of times per miss.
    def _min_t(fn, repeats, inner=1):
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(inner):
                fn()
            best = min(best, (time.perf_counter() - t0) / inner)
        return best

    def _miss():
        api.clear_plan_cache()
        api.plan_matmul(a, bd.shape, n_lanes=2)

    verify_plan(plan, level="full")   # warm the verifier's dispatch caches
    t_miss = _min_t(_miss, 30)
    t_hit = _min_t(lambda: api.plan_matmul(a, bd.shape, n_lanes=2), 5,
                   inner=50)
    t_verify = _min_t(lambda: verify_plan(plan, level="full"), 5, inner=20)
    t_scale = _min_t(lambda: check_scale_agreement(plan), 5, inner=200)
    out["verify_build_overhead"] = ((t_verify + 25 * t_scale)
                                    / (t_miss + 24 * t_hit))

    bn = LANE_CASE["bn"]
    pip = jax.jit(lambda p, x: api.execute_plan(
        p, x, bn=bn, backend="interpret"))
    out["max_err_pipelined"] = float(
        np.abs(np.asarray(pip(plan, bd)) - want).max())
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(pip(plan, bd))
        ts.append((time.perf_counter() - t0) * 1e6)
    ts = sorted(ts)
    out["pipelined_us"] = ts[len(ts) // 2]
    out["pipelined_us_min"] = ts[0]
    return out


def prefetch_sweep(repeats: int = 12) -> dict:
    """Cross-pass DMA prefetch vs the drained schedule, end to end.

    Runs the lane case (256 columns at ``bn=128`` — two N-tile passes, so
    the cross-pass tail actually executes) with and without
    ``prefetch="cross_pass"`` and reports everything CI gates:

    * ``parity_err`` — max abs difference between the two modes' outputs
      (the fetch flags are identical under both, so this must be 0.0);
    * ``model_prefetch_fetches`` / ``flag_prefetch_fetches`` — the traffic
      model's overlapped-fetch count vs an independent sum of the
      schedule's fetch flags over each lane's first-``unroll`` head window
      (the copies the kernel issues from the previous pass's tail) —
      gated exactly equal;
    * ``verify_findings`` / ``order_findings`` — full-level plan
      verification plus the :mod:`repro.analysis.order` happens-before
      rules (``cross-pass-war``/``sem-carryover``/``prefetch-raw``/
      ``dma-priority``) over the traced kernels of both modes, all gated
      at zero — no prefetch schedule ships uncertified;
    * wall time — interleaved interpret medians for both modes.  The
      interpreter replays every DMA inline and additionally evaluates the
      prefetch schedule's extra tail/prologue guards every grid step, so
      prefetch cannot win here (steady state measures ~1.25-1.3x); the
      ratio is gated ≤ 1.5 to catch pathological regressions, and the
      overlap win itself is a real-TPU follow-up.
    """
    rng = np.random.default_rng(7)
    a = _balanced_bsr(rng)
    bd = jnp.asarray(rng.standard_normal(
        (LANE_CASE["shape"][1], LANE_CASE["n_cols"])).astype(np.float32))
    want = a.to_dense() @ np.asarray(bd)
    bn = LANE_CASE["bn"]

    plans = {
        "no_prefetch": api.plan_matmul(a, bd.shape, n_lanes=2, unroll=2,
                                       cache=False),
        "prefetch": api.plan_matmul(a, bd.shape, n_lanes=2, unroll=2,
                                    cache=False, prefetch="cross_pass"),
    }
    fn = jax.jit(lambda p, x: api.execute_plan(
        p, x, bn=bn, backend="interpret"))
    got = {label: np.asarray(fn(p, bd)) for label, p in plans.items()}

    pf = plans["prefetch"]
    tr = pf.traffic
    n_lanes, unroll = pf.n_lanes, pf.unroll
    head = slice(0, unroll)
    flag_sum = int(
        np.asarray(pf.a_fetch).reshape(n_lanes, -1)[:, head].sum()
        + np.asarray(pf.b_fetch).reshape(n_lanes, -1)[:, head].sum())
    out = {
        "n_tiles_n": LANE_CASE["n_cols"] // bn,
        "parity_err": float(
            np.abs(got["prefetch"] - got["no_prefetch"]).max()),
        "max_err": float(np.abs(got["prefetch"] - want).max()),
        "model_prefetch_fetches": int(tr["prefetch_fetches"]),
        "flag_prefetch_fetches": flag_sum,
        "verify_findings": len(verify_plan(pf, level="full").findings),
    }

    # inter-pass ordering certification of both executions (the merged
    # analyzer includes ORDER_RULES; prefetch's traced grid carries the
    # demoted N-tile axis, so the cross-pass rules are non-vacuous)
    n_order = 0
    for label, p in plans.items():
        n_order += len(analyze_callable(
            lambda x: api.execute_plan(p, x, bn=bn, backend="interpret"),
            bd, label=f"bench-{label}"))
    out["order_findings"] = n_order

    times = {label: [] for label in plans}
    for _ in range(repeats):
        for label, p in plans.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn(p, bd))
            times[label].append((time.perf_counter() - t0) * 1e6)
    for label, ts in times.items():
        ts = sorted(ts)
        out[f"{label}_us"] = ts[len(ts) // 2]
        out[f"{label}_us_min"] = ts[0]
    out["interpret_ratio_vs_no_prefetch"] = (
        out["prefetch_us_min"] / out["no_prefetch_us_min"])
    return out


AUTOTUNE_N_COLS = 256

# pattern generators for the autotune sweep: the three traffic-sweep cases
# plus the banded "staircase" pattern whose row k-sets (r0={0}, r1={0},
# r2={0,1}, r3={1}, repeated down the diagonal) defeat SELECTA's greedy
# longest-run-first chaining — the canonical case where the cost model must
# hand the plan to a static dataflow (gustavson's m-order chains perfectly)


def _staircase_bsr(rng, bm=32, bk=32, stack=4) -> BSR:
    base_r = np.array([0, 1, 2, 2, 3])
    base_c = np.array([0, 0, 0, 1, 1])
    brow = np.concatenate([base_r + 4 * s for s in range(stack)])
    bcol = np.concatenate([base_c + 2 * s for s in range(stack)])
    return BSR(shape=(4 * stack * bm, 2 * stack * bk), block_shape=(bm, bk),
               brow=brow.astype(np.int64), bcol=bcol.astype(np.int64),
               blocks=rng.standard_normal(
                   (brow.size, bm, bk)).astype(np.float32))


def autotune_sweep(repeats: int = 12) -> dict:
    """Tuned vs default-knob schedules: traffic bytes + interpret wall time.

    For each case the :mod:`repro.tune` search runs under the interpret
    objective (the backend this bench times), the winner is rebuilt and
    statically verified, and both plans execute jitted/warm with interleaved
    repeats.  CI gates every case on ``tuned_traffic_bytes <=
    default_traffic_bytes`` and ``tuned_us_min <= default_us_min * 1.25``
    (interpret emulates the grid sequentially, so the tuner's wins here are
    step-count and traffic wins; lane concurrency needs real hardware), and
    asserts the staircase case dispatches a non-segment dataflow.  The
    measured ``(bytes, steps, us)`` triples re-fit the cost-model
    coefficients (``repro.tune.calibrate``) on every run, so drift between
    the shipped ``DEFAULT_INTERPRET`` model and reality stays visible in
    the JSON."""
    from repro import tune
    from repro.api.executor import pick_bn
    rng = np.random.default_rng(6)
    cases = {}
    for (m, k, blk, dens) in [(1024, 1024, 128, 0.25), (2048, 1024, 128, 0.1),
                              (512, 2048, 64, 0.3)]:
        cases[f"M{m}_K{k}_b{blk}_d{dens}"] = BSR.random(
            rng, (m, k), (blk, blk), dens)
    cases["staircase_4x"] = _staircase_bsr(rng)

    n = AUTOTUNE_N_COLS
    out = {}
    samples = []
    for name, a in cases.items():
        res = tune.autotune_matmul(a, n_cols_hint=n, objective="interpret",
                                   cache=False)
        default = api.plan_matmul(a, n, cache=False)
        tuned = api.plan_matmul(a, n, cache=False, **res.plan_kwargs())
        findings = (verify_plan(default, level="full").findings
                    + verify_plan(tuned, level="full").findings)

        variants = {}
        for label, plan in (("default", default), ("tuned", tuned)):
            bn_req = plan.bn_hint or 512
            bn_eff, pad = pick_bn(n, bn_req)
            bd = jnp.asarray(rng.standard_normal(
                (a.shape[1], n)).astype(np.float32))
            fn = jax.jit(lambda p, x: api.execute_plan(
                p, x, backend="interpret"))
            got = np.asarray(fn(plan, bd))              # compile + warm
            err = float(np.abs(got - a.to_dense() @ np.asarray(bd)).max())
            variants[label] = dict(plan=plan, fn=fn, bd=bd, err=err,
                                   n_tiles=(n + pad) // bn_eff,
                                   bn_eff=bn_eff)
        times = {label: [] for label in variants}
        for _ in range(repeats):
            for label, v in variants.items():
                t0 = time.perf_counter()
                jax.block_until_ready(v["fn"](v["plan"], v["bd"]))
                times[label].append((time.perf_counter() - t0) * 1e6)

        row = {
            "policy": tuned.policy,
            "knobs": dict(fold_len=res.best.candidate.fold_len,
                          n_lanes=tuned.n_lanes, unroll=tuned.unroll,
                          bn=res.best.candidate.bn),
            "dataflow_choice": res.dataflow_choice,
            "dataflow_scores": {k: float(v)
                                for k, v in res.dataflow_scores.items()},
            "model_cost_us": res.best.cost_us,
            "verify_findings": len(findings),
            "vmem_bytes": plan_vmem_bytes(tuned,
                                          bn=variants["tuned"]["bn_eff"]),
        }
        seq = tune.DEFAULT_INTERPRET
        for label, v in variants.items():
            ts = sorted(times[label])
            plan = v["plan"]
            row[f"{label}_traffic_bytes"] = plan.traffic["total"]
            row[f"{label}_us"] = ts[len(ts) // 2]
            row[f"{label}_us_min"] = ts[0]
            row[f"{label}_max_err"] = v["err"]
            samples.append((plan.traffic["total"],
                            seq.steps(n_lanes=plan.n_lanes,
                                      lane_len=plan.lane_len,
                                      unroll=plan.unroll,
                                      n_tiles_n=v["n_tiles"]),
                            ts[0]))
        out[name] = row

    fit = tune.calibrate(samples, lane_parallel=False)
    out["cost_model"] = {
        "objective": "interpret",
        "bytes_per_us": fit.bytes_per_us,
        "step_us": fit.step_us,
        "shipped_bytes_per_us": tune.DEFAULT_INTERPRET.bytes_per_us,
        "shipped_step_us": tune.DEFAULT_INTERPRET.step_us,
        "n_samples": len(samples),
    }
    return out


def run(csv: Csv) -> dict:
    """CSV entry point for ``benchmarks.run`` (the figure-suite driver)."""
    ratios = traffic_sweep()
    for key, r in ratios.items():
        csv.add(f"kernel/spmm_traffic_{key}", 0.0,
                ";".join(f"{name}={v:.3f}" for name, v in sorted(r.items())))
    lanes = lane_sweep()
    for n, row in lanes.items():
        csv.add(f"kernel/spmm_interpret_512_lanes{n}", row["interpret_us"],
                f"max_err={row['max_err']:.2e};"
                f"imbalance={row['lane_imbalance']:.3f}")
    quant = quant_sweep()
    for mode, row in quant.items():
        csv.add(f"kernel/spmm_quant_{mode}", row["traffic_total_bytes"],
                f"max_err={row['max_err']:.2e}")
    pipe = pipeline_sweep()
    csv.add("kernel/spmm_pipeline_interpret", pipe["pipelined_us"],
            f"max_err={pipe['max_err_pipelined']:.2e}")
    pf = prefetch_sweep()
    csv.add("kernel/spmm_prefetch_interpret", pf["prefetch_us"],
            f"baseline={pf['no_prefetch_us']:.0f}us;"
            f"parity_err={pf['parity_err']:.2e};"
            f"order_findings={pf['order_findings']}")
    tuned = autotune_sweep()
    for name, row in tuned.items():
        if name == "cost_model":
            continue
        csv.add(f"kernel/spmm_autotune_{name}", row["tuned_us"],
                f"policy={row['policy']};"
                f"bytes_ratio={row['default_traffic_bytes'] / max(1, row['tuned_traffic_bytes']):.3f}")
    return {"traffic": ratios, "lanes": lanes, "quant": quant,
            "pipeline": pipe, "prefetch": pf, "autotune": tuned}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repeats", type=int, default=12)
    ap.add_argument("--out", default="BENCH_kernels.json")
    args = ap.parse_args()

    result = {"traffic": traffic_sweep(), "lanes": lane_sweep(args.repeats),
              "quant": quant_sweep(), "pipeline": pipeline_sweep(args.repeats),
              "prefetch": prefetch_sweep(args.repeats),
              "autotune": autotune_sweep(args.repeats),
              # case configs as native JSON types (tuples become arrays) so
              # trend tooling can compare run-to-run numerically — str(v)
              # used to turn (512, 512) into an unparseable "(512, 512)"
              "lane_case": LANE_CASE,
              "quant_case": QUANT_CASE,
              "plan_cache": api.plan_cache_stats()}
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result["lanes"], indent=2))
    print(json.dumps(result["quant"], indent=2))
    print(json.dumps(result["pipeline"], indent=2))
    print(json.dumps(result["prefetch"], indent=2))
    print(json.dumps(result["autotune"], indent=2))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
