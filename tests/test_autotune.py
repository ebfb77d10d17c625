"""repro.tune: the analytical schedule search, the cost model, adaptive
dataflow selection through ``plan_matmul(policy="auto")``, the search cache
and its counters."""
import numpy as np
import pytest

from repro import api, tune
from repro.analysis.budget import DEFAULT_VMEM_LIMIT_BYTES, check_plan_vmem
from repro.analysis.invariants import verify_plan
from repro.core.formats import BSR
from repro.sim.baselines import dataflow_estimates


def _staircase(bm=32, bk=32, stack=1):
    """Banded 'staircase' block pattern whose row k-sets are r0={0}, r1={0},
    r2={0,1}, r3={1} (repeated ``stack`` times down the diagonal).  SELECTA's
    greedy chaining starts at the longest run (r2) and destroys the chain,
    so Gustavson's m-order strictly beats the segment order on B fetches —
    the canonical pattern where a static dataflow wins."""
    base_r = np.array([0, 1, 2, 2, 3])
    base_c = np.array([0, 0, 0, 1, 1])
    brow = np.concatenate([base_r + 4 * s for s in range(stack)])
    bcol = np.concatenate([base_c + 2 * s for s in range(stack)])
    rng = np.random.default_rng(7)
    blocks = rng.standard_normal((brow.size, bm, bk)).astype(np.float32)
    return BSR(shape=(4 * stack * bm, 2 * stack * bk), block_shape=(bm, bk),
               brow=brow.astype(np.int64), bcol=bcol.astype(np.int64),
               blocks=blocks)


def _scattered(seed=11, grid=(16, 16), blk=(16, 16), density=0.2):
    return BSR.random(np.random.default_rng(seed),
                      (grid[0] * blk[0], grid[1] * blk[1]), blk, density)


def _dense(a: BSR) -> np.ndarray:
    bm, bk = a.block_shape
    out = np.zeros(a.shape, np.float32)
    for i, (r, c) in enumerate(zip(a.brow, a.bcol)):
        out[r * bm:(r + 1) * bm, c * bk:(c + 1) * bk] = a.blocks[i]
    return out


@pytest.fixture(autouse=True)
def _fresh_cache():
    api.clear_plan_cache()
    yield
    api.clear_plan_cache()


# ---------------------------------------------------------------------------
# search: feasibility, optimality vs the default point, static gating
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("objective", ["tpu", "interpret"])
def test_winner_no_worse_than_default(objective):
    a = _scattered()
    res = tune.autotune_matmul(a, n_cols_hint=256, objective=objective)
    default = [s for s in res.candidates
               if s.candidate == tune.Candidate("segment", None, 1, 1, 512)]
    assert default, "the default knob point must be in the search space"
    assert res.best.cost_us <= default[0].cost_us
    assert res.best.traffic_total <= default[0].traffic_total * 1.0 + 1e-9 \
        or res.best.cost_us < default[0].cost_us


def test_winner_passes_full_verify_and_vmem():
    a = _scattered()
    res = tune.autotune_matmul(a, n_cols_hint=256)
    plan = api.plan_matmul(a, 256, cache=False, **res.plan_kwargs())
    verify_plan(plan, level="full").raise_if_findings()
    check_plan_vmem(plan, bn=min(res.best.candidate.bn, 256))
    assert res.best.vmem_bytes <= DEFAULT_VMEM_LIMIT_BYTES


def test_candidates_respect_vmem_budget():
    a = _scattered()
    res = tune.autotune_matmul(a, n_cols_hint=256)
    assert all(s.vmem_bytes <= DEFAULT_VMEM_LIMIT_BYTES
               for s in res.candidates)
    # a tiny budget rejects everything, loudly
    with pytest.raises(ValueError, match="VMEM"):
        tune.autotune_matmul(a, n_cols_hint=256, vmem_limit_bytes=1024,
                             cache=False)


def test_pins_are_honoured():
    a = _scattered()
    res = tune.autotune_matmul(
        a, n_cols_hint=256, cache=False,
        pins={"n_lanes": 2, "unroll": 1})
    assert all(s.candidate.n_lanes == 2 for s in res.candidates)
    assert all(s.candidate.unroll == 1 for s in res.candidates)
    assert res.best.candidate.n_lanes == 2


# ---------------------------------------------------------------------------
# search cache + counters
# ---------------------------------------------------------------------------


def test_search_cache_and_counters():
    a = _scattered()
    r1 = tune.autotune_matmul(a, n_cols_hint=256)
    s = api.plan_cache_stats()
    assert s["searched"] == 1 and s["search_cache_hits"] == 0
    assert not r1.from_cache
    r2 = tune.autotune_matmul(a, n_cols_hint=256)
    s = api.plan_cache_stats()
    assert s["searched"] == 1 and s["search_cache_hits"] == 1
    assert r2.from_cache and r2.best == r1.best
    # a different N bucket is a different search
    tune.autotune_matmul(a, n_cols_hint=64)
    assert api.plan_cache_stats()["searched"] == 2
    # clear_plan_cache drops the search cache too
    api.clear_plan_cache()
    assert api.plan_cache_stats()["searched"] == 0
    tune.autotune_matmul(a, n_cols_hint=256)
    s = api.plan_cache_stats()
    assert s["searched"] == 1 and s["search_cache_hits"] == 0


def test_stats_surface_has_autotune_counters():
    s = api.plan_cache_stats()
    for key in ("searched", "search_cache_hits", "dataflow_fallbacks"):
        assert key in s and s[key] == 0


# ---------------------------------------------------------------------------
# adaptive dataflow selection
# ---------------------------------------------------------------------------


def test_auto_selects_gustavson_on_staircase():
    """On the staircase pattern the greedy segment order pays an extra B
    fetch per stair, so the cost model must hand the plan to gustavson."""
    a = _staircase()
    res = tune.autotune_matmul(a, n_cols_hint=256, objective="interpret")
    assert res.dataflow_scores["gustavson"] < res.dataflow_scores["segment"]
    assert res.best.candidate.policy == "gustavson"
    plan = api.plan_matmul(a, 256, policy="auto")
    assert plan.policy == "gustavson"
    # and the auto plan computes the right numbers
    rhs = np.random.default_rng(3).standard_normal(
        (a.shape[1], 256)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(plan(rhs)), _dense(a) @ rhs,
                               rtol=2e-4, atol=2e-4)


def test_auto_keeps_segment_where_it_wins():
    a = _scattered()
    res = tune.autotune_matmul(a, n_cols_hint=256, objective="interpret")
    assert res.dataflow_scores["segment"] <= res.dataflow_scores["gustavson"]
    assert res.best.candidate.policy == "segment"
    plan = api.plan_matmul(a, 256, policy="auto")
    assert plan.policy == "segment"


def test_auto_honours_explicit_knob_pins():
    a = _scattered()
    plan = api.plan_matmul(a, 256, policy="auto", n_lanes=2)
    assert plan.n_lanes == 2


def test_dataflow_fallback_counter():
    """When the analytically best dataflow has no registered policy (the
    inner-product estimate can only win on paper), the tuner falls back to
    the best dispatchable policy and counts the event."""
    a = _scattered()
    space = tune.SearchSpace(policies=("segment",))
    res = tune.autotune_matmul(
        a, n_cols_hint=256, cache=False, space=space,
        cost_model=tune.CostModel(bytes_per_us=1.0, step_us=1e9,
                                  lane_parallel=False))
    # an absurd step cost can't invent a fallback: scores are bytes-only
    before = api.plan_cache_stats()["dataflow_fallbacks"]
    assert res.dataflow_choice in res.dataflow_scores
    if res.dataflow_choice != res.dataflow_dispatched:
        assert api.plan_cache_stats()["dataflow_fallbacks"] == before
    # force it: make every dispatchable dataflow look worse than "inner"
    res2 = tune.autotune_matmul(a, n_cols_hint=256, cache=False, space=space)
    scores = dict(res2.dataflow_scores)
    assert "inner" in scores   # the comparison dataflow is always scored
    assert scores["inner"] >= scores["gustavson"]


def test_get_policy_auto_is_reserved():
    from repro.core.policies import get_policy, register_policy
    with pytest.raises(ValueError, match="dataflow-selection"):
        get_policy("auto")
    with pytest.raises(ValueError, match="reserved"):
        register_policy("auto", spmm_order=lambda m, k: np.argsort(m),
                        spgemm_order=lambda m, n, k, c: np.argsort(c))


# ---------------------------------------------------------------------------
# closed-form dataflow estimates
# ---------------------------------------------------------------------------


def test_dataflow_estimates_match_built_plans():
    """The static policies' cost hints must price exactly what a built plan
    of that policy records at default knobs — the estimates are the same
    revisiting model run over the policy's own order."""
    a = _scattered()
    bm, bk = a.block_shape
    est = dataflow_estimates("spmm", bm=bm, bk=bk, n_cols=256,
                             m=a.brow.astype(np.int64),
                             k=a.bcol.astype(np.int64))
    for policy in ("gustavson", "outer"):
        plan = api.plan_matmul(a, 256, policy=policy, cache=False)
        for key in ("a_bytes", "b_bytes", "c_bytes", "total"):
            assert est[policy][key] == plan.traffic[key], (policy, key)


def test_inner_estimate_dominates_gustavson():
    a = _scattered()
    bm, bk = a.block_shape
    est = dataflow_estimates("spmm", bm=bm, bk=bk, n_cols=128,
                             m=a.brow.astype(np.int64),
                             k=a.bcol.astype(np.int64))
    assert est["inner"]["total"] >= est["gustavson"]["total"]
    assert est["inner"]["b_fetches"] == a.nblocks


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------


def test_calibrate_recovers_synthetic_coefficients():
    model = tune.CostModel(bytes_per_us=5.0e4, step_us=2.5,
                           lane_parallel=False)
    rng = np.random.default_rng(5)
    samples = []
    for _ in range(12):
        by = float(rng.integers(10_000, 5_000_000))
        st = float(rng.integers(10, 5_000))
        samples.append((by, st, by / model.bytes_per_us + st * model.step_us))
    fit = tune.calibrate(samples, lane_parallel=False)
    assert fit.bytes_per_us == pytest.approx(model.bytes_per_us, rel=1e-6)
    assert fit.step_us == pytest.approx(model.step_us, rel=1e-6)
    assert not fit.lane_parallel


def test_calibrate_degenerate_samples_stay_usable():
    fit = tune.calibrate([(1000.0, 10.0, 5.0)])
    assert fit.bytes_per_us > 0 and fit.step_us > 0
    with pytest.raises(ValueError):
        tune.calibrate([])


def test_cost_model_lane_parallel_switch():
    seq = tune.CostModel(1e6, 1.0, lane_parallel=False)
    par = tune.CostModel(1e6, 1.0, lane_parallel=True)
    kw = dict(n_lanes=4, lane_len=8, unroll=2, n_tiles_n=3)
    assert seq.steps(**kw) == 4 * par.steps(**kw)


def test_bn_hint_rides_the_plan():
    a = _scattered()
    plan = api.plan_matmul(a, 256, bn_hint=128, cache=False)
    assert plan.bn_hint == 128
    rhs = np.random.default_rng(2).standard_normal(
        (a.shape[1], 256)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(plan(rhs)), _dense(a) @ rhs,
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# spgemm search
# ---------------------------------------------------------------------------


def test_spgemm_autotune_smoke():
    rng = np.random.default_rng(21)
    a = BSR.random(rng, (128, 128), (16, 16), 0.25)
    b = BSR.random(rng, (128, 96), (16, 16), 0.25)
    res = tune.autotune_matmul(a, b, objective="interpret")
    assert res.best.candidate.bn == 16   # B's block width, not a knob
    plan = api.plan_matmul(a, b, cache=False, **res.plan_kwargs())
    verify_plan(plan, level="full").raise_if_findings()
    out = np.zeros(plan.n_out_blocks)
    assert plan().shape[0] == out.shape[0]
