"""Run a pass of SpGEMM calls through the program's public library API.

Set-up draws the pass's matrices (``A`` and ``A.T`` of each, as block-sparse
arrays at the configuration's block size), plans every product with
``plan_matmul(A, A.T, backend=...)`` at the library's default knobs (timed
as the planner's share of set-up; plans are built once, as a library user
with a fixed pattern builds them), and runs every plan once to compile it.
The window repeats the pass — one ``execute_plan`` per matrix, eagerly, as a
library user calls it, then ``block_until_ready`` on the pass's outputs —
for ``seconds``.  Every output block of the last pass is then compared with
the configuration's plain reference.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Optional

from harness import spec
from harness.context import Context
from harness.record import Run
from harness.work import spgemm_work


def to_program(m):
    """The program's BSR input type holding the benchmark's arrays."""
    from repro.core.formats import BSR
    return BSR(shape=m.shape, block_shape=(m.block, m.block), brow=m.brow,
               bcol=m.bcol, blocks=m.blocks)


def run(ctx: Context, *, plan_kw: Optional[Dict] = None,
        corrupt: Optional[Callable] = None) -> Run:
    """One SpGEMM run.  ``plan_kw`` switches on the program's own
    low-precision path for the control; ``corrupt(outputs)`` lets a test
    break what the timed path returns."""
    import jax
    from repro.api import execute_plan, plan_matmul

    cfg, traffic, spans = ctx.config, ctx.traffic, ctx.spans
    gen = spec.generator(traffic["generator"])
    reference = spec.reference(cfg["reference"])
    with spans("generate"):
        mats = gen.build(cfg, traffic, ctx.seed)
        pairs = [(name, a, a.transpose()) for name, a in mats]
    plans = []
    for name, a, at in pairs:
        with spans("plan." + name):
            plans.append(plan_matmul(to_program(a), to_program(at),
                                     backend=cfg["backend"],
                                     **(plan_kw or {})))
    with spans("warmup"):
        jax.block_until_ready([execute_plan(p) for p in plans])

    setup_s = time.time() - ctx.t_start
    ctx.counter.armed = True
    if ctx.tracer is not None:
        ctx.tracer.start()
    passes = 0
    t0 = time.perf_counter()
    t1 = t0 + ctx.seconds
    with spans("window"):
        while True:
            with spans("pass"):
                outs = [execute_plan(p) for p in plans]
                jax.block_until_ready(outs)
            passes += 1
            if time.perf_counter() >= t1:
                break
    t_end = time.perf_counter()
    if ctx.tracer is not None:
        ctx.tracer.stop()
    ctx.counter.armed = False
    device = ctx.describe()

    if corrupt is not None:
        outs = corrupt(outs)
    answers = [(name, a, at, plan.c_brow, plan.c_bcol, out)
               for (name, a, at), plan, out in zip(pairs, plans, outs)]
    del plans, outs
    checks = reference.compare(answers)
    work = {name: spgemm_work(a.brow, a.bcol, at.brow, at.bcol,
                              (a.block, a.block, a.block),
                              a.blocks.dtype.itemsize)
            for name, a, at in pairs}
    plan_s = {name[len("plan."):]: e - s for name, s, e in spans.items
              if name.startswith("plan.")}
    result = Run(workload=ctx.workload, config=cfg, traffic=traffic,
                 device_kind=ctx.device_kind, setup_s=setup_s,
                 window=(t0, t_end), spans=spans)
    result.counters.update(compiles_in_window=ctx.counter.count,
                           passes=passes)
    result.extra.update(checks=checks, device=device, attempted=passes,
                        failed=0, plan_s=plan_s, work=work)
    return result
