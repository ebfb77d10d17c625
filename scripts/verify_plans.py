#!/usr/bin/env python
"""Sweep the static plan verifier over a corpus of planner-built plans.

Builds plans from the ``repro.sim.matrices`` pattern generators (the same
structural families the paper benchmarks run) across the planner knob grid
— lanes × unroll × quantize × prefetch × policy, SpMM and SpGEMM, plus the
degenerate shapes the verifier must tolerate (single-block schedules,
empty symbolic C patterns, unpadded ``n_lanes=1``) — and runs
``repro.analysis.verify_plan`` on each.  Any finding is a bug in either
the planner or the verifier; the process exits 1 so ``scripts/ci.sh`` can
gate on it.  The sweep also autotunes every pattern under both cost-model
objectives (``repro.tune.autotune_matmul``) and pushes each search winner
through the same full-level verifier plus the static VMEM gate — no
schedule the search can emit escapes static checking.

``--json OUT`` additionally writes a machine-readable findings artifact
(per-plan records + per-finding invariant/message + summary) for CI upload
and run-to-run diffing.  ``--fast`` is shorthand for ``--level fast`` —
the structural catalog without the full-level independent traffic-model
count recomputation (the expensive half of a full sweep).

Usage::

    PYTHONPATH=src python scripts/verify_plans.py [--level fast|full]
        [--fast] [--scale 256] [--seed 7] [--json OUT.json] [-q]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from repro import api, tune
from repro.analysis import check_plan_vmem, verify_plan
from repro.api.executor import pick_bn
from repro.core.formats import BSR
from repro.sim import matrices

BLOCK = (32, 32)

#: (pattern-name, generator) — small dims keep the sweep host-cheap while
#: still exercising banded/power-law/mesh segment structure.
PATTERNS = (
    ("banded", matrices.banded),
    ("mesh2d", matrices.mesh2d),
    ("powerlaw", matrices.powerlaw),
    ("powernet", matrices.powernet),
    ("uniform", matrices.uniform),
    ("blockrand", matrices.blockrand),
)

SPMM_GRID = tuple(
    dict(n_lanes=l, unroll=u, quantize=q, prefetch=p)
    for l in (1, 2, 4) for u in (1, 2)
    for q in (None, "int8", "int8.rowwise")
    for p in (None, "cross_pass"))
SPGEMM_GRID = tuple(
    dict(n_lanes=l, unroll=u, prefetch=p)
    for l in (1, 2) for u in (1, 2) for p in (None, "cross_pass"))


def _pattern_bsr(gen, rng, dim: int, density: float) -> BSR:
    dense = gen(rng, dim, dim, density).to_dense()
    return BSR.from_dense(dense, BLOCK)


def sweep(level: str, scale: int, seed: int, quiet: bool,
          json_out=None) -> int:
    rng = np.random.default_rng(seed)
    records = []
    n_findings = 0
    n_autotuned = 0
    t0 = time.perf_counter()

    def check(label: str, plan) -> None:
        nonlocal n_findings
        res = verify_plan(plan, level=level)
        rec = {"plan": label, "kind": plan.kind, "ok": bool(res.ok),
               "checked": len(res.checked),
               "findings": [{"invariant": f.invariant,
                             "message": f.message,
                             "severity": getattr(f, "severity", "error")}
                            for f in res.findings]}
        records.append(rec)
        if not res.ok:
            n_findings += len(res.findings)
            print(f"FAIL {label}:")
            for f in res.findings:
                print(f"  {f}")
        elif not quiet:
            print(f"  ok {label} ({len(res.checked)} invariants)")

    for name, gen in PATTERNS:
        a = _pattern_bsr(gen, rng, scale, 0.05)
        if a.nblocks == 0:
            print(f"  skip {name}: pattern quantizes to zero blocks")
            continue
        for kw in SPMM_GRID:
            label = (f"spmm/{name} lanes={kw['n_lanes']} "
                     f"unroll={kw['unroll']} quant={kw['quantize']} "
                     f"pf={kw['prefetch']}")
            check(label, api.plan_matmul(a, policy="segment", fold_len=4,
                                         with_grad=kw["quantize"] is None,
                                         cache=False, **kw))
        b = _pattern_bsr(gen, rng, scale, 0.05)
        if b.nblocks:
            for kw in SPGEMM_GRID:
                label = (f"spgemm/{name} lanes={kw['n_lanes']} "
                         f"unroll={kw['unroll']} pf={kw['prefetch']}")
                check(label, api.plan_matmul(a, b, policy="segment",
                                             cache=False, **kw))

    # random BSR patterns (denser than the structural families)
    for density in (0.25, 0.6):
        a = BSR.random(rng, (scale, scale), BLOCK, density)
        for kw in SPMM_GRID:
            label = (f"spmm/random{density} lanes={kw['n_lanes']} "
                     f"unroll={kw['unroll']} quant={kw['quantize']} "
                     f"pf={kw['prefetch']}")
            check(label, api.plan_matmul(a, policy="segment", cache=False,
                                         **kw))

    # --- degenerate regression cases --------------------------------------
    # single stored block: one item, one lane, no pads
    single = BSR.random(rng, BLOCK, BLOCK, 1.0)
    check("degenerate/single-block", api.plan_matmul(single, cache=False))
    check("degenerate/single-block-lanes",
          api.plan_matmul(single, n_lanes=4, cache=False))
    # n_lanes=1 unpadded
    a = BSR.random(rng, (scale, scale), BLOCK, 0.4)
    check("degenerate/one-lane", api.plan_matmul(a, n_lanes=1, cache=False))
    # empty symbolic C: A's columns never meet B's rows
    gb = scale // BLOCK[0]
    a_lo = BSR(shape=(scale, scale), block_shape=BLOCK,
               brow=np.zeros(1, np.int64), bcol=np.zeros(1, np.int64),
               blocks=np.ones((1,) + BLOCK, np.float32))
    b_hi = BSR(shape=(scale, scale), block_shape=BLOCK,
               brow=np.full(1, gb - 1, np.int64),
               bcol=np.zeros(1, np.int64),
               blocks=np.ones((1,) + BLOCK, np.float32))
    check("degenerate/empty-C", api.plan_matmul(a_lo, b_hi, cache=False))

    # --- autotuned winners -------------------------------------------------
    # every schedule the search can emit must pass the same full-level
    # verifier + VMEM gate the hand-built corpus does (ISSUE satellite 3):
    # autotune each pattern under both objectives and check the winner.
    n_cols = 256
    for name, gen in PATTERNS:
        a = _pattern_bsr(gen, rng, scale, 0.05)
        if a.nblocks == 0:
            continue
        for objective in ("interpret", "tpu"):
            res = tune.autotune_matmul(a, n_cols_hint=n_cols,
                                       objective=objective, cache=False)
            kw = res.plan_kwargs()
            plan = api.plan_matmul(a, cache=False, n_cols_hint=n_cols, **kw)
            bn_eff, _ = pick_bn(n_cols, kw["bn_hint"] or 512)
            check_plan_vmem(plan, bn=bn_eff)  # raises over budget
            label = (f"autotuned/{name} obj={objective} "
                     f"policy={kw['policy']} lanes={kw['n_lanes']} "
                     f"unroll={kw['unroll']} fold={kw['fold_len']} "
                     f"bn={kw['bn_hint']} "
                     f"pf={kw['prefetch']}")
            check(label, plan)
            n_autotuned += 1

    dt = time.perf_counter() - t0
    status = "FAIL" if n_findings else "OK"
    print(f"{status}: verified {len(records)} plans "
          f"({n_autotuned} autotuned winners) at level={level!r} in "
          f"{dt:.1f}s, {n_findings} finding(s)")
    if json_out:
        artifact = {
            "level": level, "scale": scale, "seed": seed,
            "elapsed_s": round(dt, 3),
            "summary": {"n_plans": len(records),
                        "n_autotuned": n_autotuned,
                        "n_findings": n_findings,
                        "ok": n_findings == 0},
            "plans": records,
        }
        with open(json_out, "w") as f:
            json.dump(artifact, f, indent=2)
        print(f"wrote {json_out}")
    return 1 if n_findings else 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--level", choices=("fast", "full"), default="full")
    p.add_argument("--fast", action="store_true",
                   help="shorthand for --level fast (skips the full-level "
                        "traffic-agreement recomputation)")
    p.add_argument("--scale", type=int, default=256,
                   help="square matrix dimension for the pattern corpus")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--json", metavar="OUT", default=None,
                   help="write a machine-readable findings artifact here")
    p.add_argument("-q", "--quiet", action="store_true",
                   help="only print failures and the summary line")
    args = p.parse_args(argv)
    level = "fast" if args.fast else args.level
    return sweep(level, args.scale, args.seed, args.quiet,
                 json_out=args.json)


if __name__ == "__main__":
    sys.exit(main())
