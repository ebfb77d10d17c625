"""Static-analysis battery: plan-verifier mutation kills + kernel linter.

Every invariant in ``repro.analysis.INVARIANTS`` gets a mutation-kill
test: take a clean planner-built plan, apply ONE targeted corruption, and
assert the verifier reports exactly that invariant (after its specificity
suppression).  Clean plans across the knob grid must verify with zero
findings, degenerate plans must not crash, and the jaxpr linter must flag
deliberately hazardous toy kernels while passing the shipped ones.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from _hypothesis_compat import given, settings, st
from repro import api
from repro.analysis import (INVARIANTS, PlanVerificationError, lint_callable,
                            lint_segment_kernels, verify_plan)
from repro.core.formats import BSR


def _spmm_plan(**kw):
    a = BSR.random(np.random.default_rng(2), (256, 256), (32, 32), 0.5)
    kw.setdefault("policy", "segment")
    kw.setdefault("cache", False)
    return api.plan_matmul(a, **kw)


def _ids(plan, **kw):
    """Reported invariant ids (post-suppression) at level='full'."""
    return sorted({f.invariant
                   for f in verify_plan(plan, level="full", **kw).findings})


@pytest.fixture(scope="module")
def plan():
    p = _spmm_plan(n_lanes=2, unroll=2)
    assert p.has_pads, "mutation battery expects a padded schedule"
    assert verify_plan(p, level="full").ok
    return p


# ---------------------------------------------------------------------------
# mutation kills — one targeted corruption per invariant class
# ---------------------------------------------------------------------------


def test_kill_shape_agreement(plan):
    bad = plan.replace(seg_write=np.asarray(plan.seg_write)[:-1])
    assert _ids(bad) == ["shape-agreement"]


def test_kill_lane_divisibility(plan):
    # a non-divisible lane count over the same arrays
    bad = plan.replace(n_lanes=3)
    assert _ids(bad) == ["lane-divisibility"]


def test_kill_lane_divisibility_unroll(plan):
    bad = plan.replace(unroll=plan.lane_len * 2)
    assert _ids(bad) == ["lane-divisibility"]


def test_kill_index_bounds(plan):
    slot = np.asarray(plan.slot_idx).copy()
    slot[0] = plan.n_blocks + 7
    assert _ids(plan.replace(slot_idx=slot)) == ["index-bounds"]


def test_kill_slot_out_of_ring(plan):
    s = np.asarray(plan.a_slot).copy()
    s[0] = 2 * plan.unroll   # one past the ring
    assert "index-bounds" in _ids(plan.replace(a_slot=s))


def test_kill_segment_structure(plan):
    m = np.asarray(plan.m_idx)
    v = np.asarray(plan.valid)
    ss = np.asarray(plan.seg_start).copy()
    lane_len = plan.lane_len
    i = next(i for i in range(1, plan.n_items)
             if v[i] and v[i - 1] and m[i] != m[i - 1] and ss[i] == 1
             and i % lane_len != 0)
    ss[i] = 0   # owner changes without a segment head
    assert _ids(plan.replace(seg_start=ss)) == ["segment-structure"]


def test_kill_accum_prev_order(plan):
    v = np.asarray(plan.valid)
    ss = np.asarray(plan.seg_start)
    ap = np.asarray(plan.accum_prev).copy()
    heads = [i for i in range(plan.n_items)
             if v[i] and ss[i] == 1 and ap[i] == 0]
    ap[heads[0]] = 1   # RMW-read a tile nothing wrote earlier in the lane
    assert _ids(plan.replace(accum_prev=ap)) == ["accum-prev-order"]


def test_kill_pads_fetch_nothing(plan):
    pads = np.nonzero(np.asarray(plan.valid) == 0)[0]
    f = np.asarray(plan.a_fetch).copy()
    f[pads[0]] = 1   # a pad that issues a DMA
    assert _ids(plan.replace(a_fetch=f)) == ["pads-fetch-nothing"]


def test_kill_lane_first_fetch(plan):
    f = np.asarray(plan.b_fetch).copy()
    f[0] = 0   # lane head inheriting residency it cannot have
    assert _ids(plan.replace(b_fetch=f)) == ["lane-first-fetch"]


def test_kill_fetch_on_change(plan):
    v = np.asarray(plan.valid)
    f = np.asarray(plan.b_fetch).copy()
    i = next(i for i in range(plan.n_items)
             if v[i] and f[i] == 0 and i % plan.lane_len != 0)
    f[i] = 1   # spurious re-fetch of the resident tile
    assert _ids(plan.replace(b_fetch=f)) == ["fetch-on-change"]


def test_kill_slot_advance(plan):
    f = np.asarray(plan.a_fetch)
    s = np.asarray(plan.a_slot).copy()
    fi = np.nonzero(f == 1)[0]
    i1, i2 = int(fi[1]), int(fi[2])
    assert s[i1] != s[i2]
    s[i1], s[i2] = s[i2], s[i1]   # ring advances out of order
    assert _ids(plan.replace(a_slot=s)) == ["slot-advance"]


def test_kill_ring_war(plan):
    # Redirect a fetch onto the slot whose tile is still being read at the
    # fetch's issue step.  Any such corruption also breaks slot-advance's
    # exact cumsum contract (which subsumes WAR safety on planner-built
    # rings), so the liveness property is judged in isolation via the
    # invariants filter — the documented use of that parameter.
    f = np.asarray(plan.a_fetch)
    s = np.asarray(plan.a_slot).copy()
    lane_len, unroll = plan.lane_len, plan.unroll
    for j in np.nonzero(f == 1)[0]:
        j = int(j)
        if j % lane_len == 0:
            continue
        lane = j // lane_len
        issue_step = max(j // unroll - 1, 0)
        live = s[lane * lane_len + issue_step * unroll]
        if s[j] != live:
            s[j] = live
            break
    else:
        pytest.skip("no redirectable fetch in this schedule")
    mutated = plan.replace(a_slot=s)
    assert _ids(mutated, invariants=("ring-war",)) == ["ring-war"]
    # the default run roots the same corruption at the slot contract
    assert _ids(mutated) == ["slot-advance"]


def test_kill_scale_agreement():
    q = _spmm_plan(n_lanes=2, quantize="int8")
    bad = q.replace(lhs_scales=jnp.ones((3,), jnp.float32))
    assert _ids(bad) == ["scale-agreement"]
    # fp32 plan carrying scales is the inverse corruption
    p = _spmm_plan(n_lanes=2)
    bad = p.replace(lhs_scales=jnp.ones((p.n_blocks,), jnp.float32))
    assert _ids(bad) == ["scale-agreement"]


def test_kill_traffic_agreement(plan):
    items = tuple((k, v + 1 if k == "a_fetches" else v)
                  for k, v in plan.traffic_items)
    bad = plan.replace(traffic_items=items)
    assert _ids(bad) == ["traffic-agreement"]
    # fast level deliberately skips the model recomputation
    assert verify_plan(bad, level="fast").ok


def test_every_invariant_has_a_kill():
    """The catalog and this file's kill coverage must not drift apart."""
    covered = {
        "shape-agreement", "lane-divisibility", "index-bounds",
        "segment-structure", "accum-prev-order", "pads-fetch-nothing",
        "lane-first-fetch", "fetch-on-change", "slot-advance", "ring-war",
        "scale-agreement", "traffic-agreement",
    }
    assert covered == set(INVARIANTS)


# ---------------------------------------------------------------------------
# clean plans verify clean — knob grid + hypothesis sweep
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kw", [
    dict(),
    dict(n_lanes=2),
    dict(n_lanes=4, unroll=2),
    dict(n_lanes=2, unroll=2, quantize="int8"),
    dict(n_lanes=2, unroll=2, quantize="fp8"),
    dict(n_lanes=3, unroll=2, fold_len=3, with_grad=True),
])
def test_knob_grid_verifies_clean(kw):
    res = verify_plan(_spmm_plan(**kw), level="full")
    assert res.ok, res.summary()
    assert set(res.checked) == set(INVARIANTS)


def test_spgemm_verifies_clean():
    a = BSR.random(np.random.default_rng(4), (256, 256), (32, 32), 0.5)
    b = BSR.random(np.random.default_rng(5), (256, 256), (32, 32), 0.5)
    for kw in (dict(), dict(n_lanes=2, unroll=2)):
        res = verify_plan(api.plan_matmul(a, b, cache=False, **kw),
                          level="full")
        assert res.ok, res.summary()


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16), n_lanes=st.integers(1, 4),
       unroll=st.sampled_from([1, 2]),
       quantize=st.sampled_from([None, "int8"]))
def test_verifies_clean_hypothesis(seed, n_lanes, unroll, quantize):
    a = BSR.random(np.random.default_rng(seed), (160, 160), (32, 32), 0.4)
    if a.nblocks == 0:
        return
    plan = api.plan_matmul(a, policy="segment", n_lanes=n_lanes,
                           unroll=unroll, fold_len=3, quantize=quantize,
                           cache=False)
    res = verify_plan(plan, level="full")
    assert res.ok, res.summary()


# ---------------------------------------------------------------------------
# degenerate plans — must verify clean, not crash
# ---------------------------------------------------------------------------


def test_degenerate_single_block():
    a = BSR.random(np.random.default_rng(0), (32, 32), (32, 32), 1.0)
    for kw in (dict(), dict(n_lanes=4, unroll=1)):
        res = verify_plan(api.plan_matmul(a, cache=False, **kw),
                          level="full")
        assert res.ok, res.summary()


def test_degenerate_one_lane_unpadded():
    p = _spmm_plan(n_lanes=1)
    assert not p.has_pads
    assert verify_plan(p, level="full").ok


def test_degenerate_empty_symbolic_c():
    # A's only column never meets B's only row: zero symbolic C blocks
    blk = (32, 32)
    a = BSR(shape=(128, 128), block_shape=blk,
            brow=np.zeros(1, np.int64), bcol=np.zeros(1, np.int64),
            blocks=np.ones((1,) + blk, np.float32))
    b = BSR(shape=(128, 128), block_shape=blk,
            brow=np.full(1, 3, np.int64), bcol=np.zeros(1, np.int64),
            blocks=np.ones((1,) + blk, np.float32))
    plan = api.plan_matmul(a, b, cache=False)
    assert plan.n_out_blocks == 0
    res = verify_plan(plan, level="full")
    assert res.ok, res.summary()
    # the executor short-circuit stays intact under verify=
    out = api.execute_plan(plan, backend="reference", verify="full")
    assert out.shape[0] == 0


# ---------------------------------------------------------------------------
# verifier API surface
# ---------------------------------------------------------------------------


def test_verify_rejects_bad_level_and_ids(plan):
    with pytest.raises(ValueError, match="level must be"):
        verify_plan(plan, level="paranoid")
    with pytest.raises(ValueError, match="unknown invariant"):
        verify_plan(plan, invariants=("no-such-check",))


def test_plan_verify_method(plan):
    res = plan.verify(level="full")
    assert res.ok
    bad = plan.replace(seg_write=np.asarray(plan.seg_write)[:-1])
    with pytest.raises(PlanVerificationError, match="shape-agreement"):
        bad.verify().raise_if_findings()


def test_grad_plan_findings_carry_path():
    p = _spmm_plan(n_lanes=2, with_grad=True)
    g = p.grad_plan
    f = np.asarray(g.a_fetch).copy()
    f[0] = 0
    bad = p.replace(grad_plan=g.replace(a_fetch=f))
    findings = verify_plan(bad).findings
    assert findings and all(x.path == "plan.grad_plan" for x in findings)
    assert {x.invariant for x in findings} == {"lane-first-fetch"}


def test_plan_matmul_verify_hook_and_template_cache():
    api.clear_plan_cache()
    a = BSR.random(np.random.default_rng(6), (128, 128), (32, 32), 0.5)
    p1 = api.plan_matmul(a, n_lanes=2, verify="full")
    assert verify_plan(p1, level="full").ok
    # cache hit: the template's verified level is remembered, and the
    # realized plan still passes the per-call scale check
    p2 = api.plan_matmul(a, n_lanes=2, verify="full")
    assert p2.fingerprint == p1.fingerprint
    with pytest.raises(ValueError, match="verify must be"):
        api.plan_matmul(a, verify="paranoid")
    api.clear_plan_cache()


def test_execute_plan_verify_rejects_corrupt(plan):
    pads = np.nonzero(np.asarray(plan.valid) == 0)[0]
    f = np.asarray(plan.a_fetch).copy()
    f[pads[0]] = 1
    bad = plan.replace(a_fetch=f)
    x = jnp.zeros((256, 32), jnp.float32)
    with pytest.raises(PlanVerificationError, match="pads-fetch-nothing"):
        api.execute_plan(bad, x, backend="reference", verify=True)


def test_partition_lanes_accum_check_routes_through_verifier():
    """The planner-path validation and the verifier share one
    implementation (repro.analysis.check_lane_accum) — same message."""
    from repro.core.schedule import partition_lanes
    owner = np.array([0, 1])
    with pytest.raises(ValueError,
                       match=r"accum_prev=1 but no earlier seg_write"):
        partition_lanes(owner, 1, seg_start=np.array([1, 1]),
                        seg_write=np.array([0, 1]),
                        accum_prev=np.array([0, 1]))


# ---------------------------------------------------------------------------
# spgemm validation battery (satellite: named ValueErrors)
# ---------------------------------------------------------------------------


def _spgemm_args(plan):
    return (plan.lhs_blocks, plan.rhs_blocks, plan.a_idx, plan.b_idx,
            plan.c_idx, plan.seg_start, plan.seg_write, plan.accum_prev,
            plan.valid)


@pytest.fixture(scope="module")
def gplan():
    a = BSR.random(np.random.default_rng(7), (128, 128), (32, 32), 0.5)
    b = BSR.random(np.random.default_rng(8), (128, 128), (32, 32), 0.5)
    return api.plan_matmul(a, b, n_lanes=2, cache=False)


def test_spgemm_rejects_contraction_mismatch(gplan):
    from repro.kernels.segment_spgemm import segment_spgemm
    args = list(_spgemm_args(gplan))
    args[1] = jnp.zeros((gplan.rhs_blocks.shape[0], 16, 32), jnp.float32)
    with pytest.raises(ValueError, match=r"contraction blocks disagree"):
        segment_spgemm(*args, n_c_blocks=gplan.n_out_blocks,
                       n_lanes=gplan.n_lanes, interpret=True)


def test_spgemm_rejects_empty_output_with_work(gplan):
    from repro.kernels.segment_spgemm import segment_spgemm
    with pytest.raises(ValueError, match=r"n_c_blocks=0 with a non-empty"):
        segment_spgemm(*_spgemm_args(gplan), n_c_blocks=0,
                       n_lanes=gplan.n_lanes, interpret=True)


def test_spgemm_rejects_length_mismatch(gplan):
    from repro.kernels.segment_spgemm import segment_spgemm
    args = list(_spgemm_args(gplan))
    args[3] = jnp.asarray(np.asarray(gplan.b_idx)[:-1])
    with pytest.raises(ValueError, match=r"b_idx has shape"):
        segment_spgemm(*args, n_c_blocks=gplan.n_out_blocks,
                       n_lanes=gplan.n_lanes, interpret=True)


def test_spgemm_rejects_pipeline_without_flags(gplan):
    from repro.kernels.segment_spgemm import segment_spgemm
    with pytest.raises(ValueError, match=r"a_fetch is missing"):
        segment_spgemm(*_spgemm_args(gplan), n_c_blocks=gplan.n_out_blocks,
                       n_lanes=gplan.n_lanes, interpret=True)
    with pytest.raises(ValueError, match=r"b_fetch is missing"):
        segment_spgemm(*_spgemm_args(gplan), n_c_blocks=gplan.n_out_blocks,
                       n_lanes=gplan.n_lanes, interpret=True,
                       a_fetch=gplan.a_fetch, a_slot=gplan.a_slot,
                       b_slot=gplan.b_slot)


# ---------------------------------------------------------------------------
# jaxpr linter — toy hazards flagged, shipped kernels clean
# ---------------------------------------------------------------------------


_X = jnp.zeros((8, 128), jnp.float32)


def _toy_pid_call(x):
    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

        @pl.when(pl.program_id(0) == 0)
        def _():
            # deliberately reintroduced hazard: program_id read inside when
            o_ref[...] = x_ref[...] * pl.program_id(0)

    return pl.pallas_call(
        kernel, grid=(2,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        interpret=True)(x)


def _toy_dma_call(mode, x):
    def kernel(hbm_ref, o_ref, buf, sem):
        cp = pltpu.make_async_copy(hbm_ref, buf, sem)
        cp.start()
        if mode == "clean":
            cp.wait()
            o_ref[...] = buf[...]
        elif mode == "no-wait":
            o_ref[...] = jnp.zeros_like(o_ref)
        elif mode == "read-early":
            o_ref[...] = buf[...]
            cp.wait()

    return pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((8, 128), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32),
                        pltpu.SemaphoreType.DMA],
        interpret=True)(x)


def test_lint_flags_program_id_in_when():
    findings = lint_callable(_toy_pid_call, _X, label="toy")
    assert [f.rule for f in findings] == ["program-id-in-when"]


def test_lint_flags_dma_start_without_wait():
    findings = lint_callable(functools.partial(_toy_dma_call, "no-wait"), _X)
    assert [f.rule for f in findings] == ["dma-start-without-wait"]


def test_lint_flags_read_before_wait():
    findings = lint_callable(functools.partial(_toy_dma_call, "read-early"),
                             _X)
    assert [f.rule for f in findings] == ["read-before-wait"]


def test_lint_clean_toy_kernel():
    assert lint_callable(functools.partial(_toy_dma_call, "clean"), _X) == []


def test_lint_requires_a_pallas_call():
    with pytest.raises(ValueError, match="no pallas_call"):
        lint_callable(lambda x: x + 1, _X)


def test_shipped_kernels_lint_clean():
    findings = lint_segment_kernels()
    assert findings == [], [str(f) for f in findings]
