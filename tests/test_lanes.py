"""Lane-parallel Segment execution: partitioning invariants, backend parity
across lane counts, the zero-copy realize contract, and the transposed
backward path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # optional-dep guard

from repro import api
from repro.api import planner
from repro.core.formats import BSR
from repro.core.schedule import build_spmm_schedule, partition_lanes

RNG = np.random.default_rng(0)


def _patterns():
    """Pattern classes the lane partitioner must not corrupt."""
    rand = BSR.random(np.random.default_rng(1), (128, 160), (32, 32), 0.35)
    # empty block rows
    d = np.random.default_rng(2).standard_normal((128, 96)).astype(np.float32)
    d[0:32] = 0.0
    d[64:96] = 0.0
    holes = BSR.from_dense(d, (32, 32))
    # one giant segment: a single output row holding every k block — with
    # n_lanes > 1 the whole chain must stay in one lane (extra lanes clamp)
    one_row = BSR.from_dense(
        np.random.default_rng(3).standard_normal((32, 256)).astype(np.float32),
        (32, 32))
    return {"random": rand, "empty_rows": holes, "one_segment": one_row}


# ---------------------------------------------------------------------------
# partition_lanes invariants
# ---------------------------------------------------------------------------


def test_partition_lanes_covers_items_and_keeps_owners_atomic():
    a = BSR.random(np.random.default_rng(4), (256, 256), (32, 32), 0.3)
    s = build_spmm_schedule(a, "segment", fold_len=3)
    for n_lanes in (1, 2, 4, 8):
        lay = partition_lanes(s.m, n_lanes, unroll=2)
        real = lay.perm[lay.perm >= 0]
        assert sorted(real.tolist()) == list(range(s.n_items))
        # owner chains (incl. folded continuations) never span lanes
        owner_lane = {}
        for li in range(lay.n_lanes):
            for it in lay.perm[li][lay.perm[li] >= 0]:
                o = int(s.m[it])
                assert owner_lane.setdefault(o, li) == li
        # unroll alignment: every grid step's items share one owner
        for li in range(lay.n_lanes):
            owners = np.where(lay.perm[li] >= 0,
                              s.m[lay.filled[li]], -1)
            for j0 in range(0, lay.lane_len, 2):
                step = [o for o in owners[j0:j0 + 2] if o >= 0]
                assert len(set(step)) <= 1


def test_partition_lanes_clamps_to_segment_count():
    lay = partition_lanes(np.array([7, 7, 7, 7]), 4)
    assert lay.n_lanes == 1          # one owner group → one lane
    lay = partition_lanes(np.array([0, 0, 1, 2]), 16)
    assert lay.n_lanes == 3


def test_lane_traffic_unroll_keeps_per_item_reuse():
    """The explicit-DMA kernels fetch per *item*, so revisit credit spans
    every consecutive pair — unroll included."""
    from repro.core.schedule import lane_traffic_spmm
    # two chains of two items; k = [0, 5, 5, 7]
    m = np.array([0, 0, 1, 1])
    k = np.array([0, 5, 5, 7])
    seg_start = np.array([1, 0, 1, 0])
    valid = np.ones(4, bool)
    t1 = lane_traffic_spmm(m, k, seg_start, valid, 1, 8, 8, 1)
    # per-item model: items 1->2 share k=5 across the chain boundary
    assert t1["b_fetches"] == 3
    t2 = lane_traffic_spmm(m, k, seg_start, valid, 1, 8, 8, 1, unroll=2)
    # the pipelined kernel's fetch flags don't change with unroll
    assert t2["b_fetches"] == 3


def test_unrolled_plan_traffic_matches_fetch_flags():
    """Plan traffic is priced from the same fetch flags the kernel's DMA
    pipeline is gated by — predicted counts ARE the schedule's counts."""
    a = _patterns()["random"]
    plan = api.plan_matmul(a, n_cols_hint=64, n_lanes=2, unroll=2,
                           fold_len=3, cache=False)
    k = np.asarray(plan.k_idx)
    valid = np.asarray(plan.valid).astype(bool)
    k2 = k.reshape(plan.n_lanes, -1)
    delta = np.ones_like(k2, dtype=bool)
    delta[:, 1:] = k2[:, 1:] != k2[:, :-1]
    n_fetch = int((delta.reshape(-1) & valid).sum())
    assert plan.traffic["b_fetches"] == n_fetch
    assert int(np.asarray(plan.b_fetch).sum()) == n_fetch
    assert plan.traffic["a_fetches"] == int(np.asarray(plan.a_fetch).sum())


def test_lane_traffic_accounts_boundary_breaks():
    """Cutting the schedule into lanes re-fetches B at every lane start —
    modeled traffic must not claim cross-lane boundary reuse."""
    a = BSR.random(np.random.default_rng(5), (512, 512), (64, 64), 0.25)
    t1 = api.plan_matmul(a, n_cols_hint=256, n_lanes=1).traffic
    t4 = api.plan_matmul(a, n_cols_hint=256, n_lanes=4).traffic
    assert t4["b_fetches"] >= t1["b_fetches"]
    assert t4["total"] >= t1["total"]
    assert t4["imbalance"] >= 1.0


# ---------------------------------------------------------------------------
# numeric parity across lane counts / folding / backends
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_lanes", [1, 2, 4])
@pytest.mark.parametrize("fold_len", [None, 2])
def test_lane_parity_vs_dense_oracle(n_lanes, fold_len):
    for name, a in _patterns().items():
        plan = api.plan_matmul(a, policy="segment", n_lanes=n_lanes,
                               fold_len=fold_len)
        x = jnp.asarray(
            RNG.standard_normal((a.shape[1], 64)).astype(np.float32))
        want = a.to_dense() @ np.asarray(x)
        got = np.asarray(plan(x, bn=32, backend="interpret"))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4,
                                   err_msg=f"{name}/lanes={n_lanes}")
        got_ref = np.asarray(plan(x, backend="reference"))
        np.testing.assert_allclose(got_ref, want, rtol=1e-4, atol=1e-4)


def test_unroll_parity():
    a = _patterns()["random"]
    x = jnp.asarray(RNG.standard_normal((a.shape[1], 64)).astype(np.float32))
    want = a.to_dense() @ np.asarray(x)
    plan = api.plan_matmul(a, n_lanes=2, unroll=2, fold_len=3)
    assert plan.unroll == 2 and plan.n_items % (2 * plan.n_lanes) == 0
    got = np.asarray(plan(x, bn=32, backend="interpret"))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_spgemm_lane_parity():
    a = BSR.random(np.random.default_rng(6), (128, 160), (32, 32), 0.3)
    b = BSR.random(np.random.default_rng(7), (160, 96), (32, 32), 0.3)
    want = a.to_dense() @ b.to_dense()
    for n_lanes in (1, 3):
        plan = api.plan_matmul(a, b, n_lanes=n_lanes)
        got = np.asarray(plan(backend="interpret"))
        for i, (r, c) in enumerate(zip(plan.c_brow, plan.c_bcol)):
            np.testing.assert_allclose(
                got[i], want[r * 32:(r + 1) * 32, c * 32:(c + 1) * 32],
                rtol=1e-4, atol=1e-4, err_msg=f"lanes={n_lanes}")


@pytest.mark.parametrize("backend", ["interpret", "reference"])
def test_lane_vjp_matches_dense(backend):
    a = BSR.random(np.random.default_rng(8), (96, 128), (32, 32), 0.4)
    plan = api.plan_matmul(a, with_grad=True, n_lanes=2)
    assert plan.grad_plan.transpose_lhs
    x = jnp.asarray(RNG.standard_normal((128, 48)).astype(np.float32))

    def loss(blocks, xx):
        return jnp.sum(api.apply_plan(plan.with_values(blocks), xx,
                                      backend=backend) ** 2)

    gb, gx = jax.grad(loss, argnums=(0, 1))(plan.lhs_blocks, x)
    w = jnp.asarray(a.to_dense())
    gw, gx_d = jax.grad(
        lambda w_, xx: jnp.sum((w_ @ xx) ** 2), argnums=(0, 1))(w, x)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_d),
                               rtol=1e-3, atol=1e-3)
    brow, bcol = np.asarray(plan.a_brow), np.asarray(plan.a_bcol)
    for s in range(plan.n_blocks):
        r, c = int(brow[s]), int(bcol[s])
        np.testing.assert_allclose(
            np.asarray(gb)[s],
            np.asarray(gw)[r * 32:(r + 1) * 32, c * 32:(c + 1) * 32],
            rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# edge cases: empty symbolic output pattern, single-block matrices
# ---------------------------------------------------------------------------


def test_spgemm_empty_output_pattern():
    """No A column meets a B row → the symbolic phase yields zero C blocks;
    the plan must build and execute (empty C array) on every backend."""
    a = BSR(shape=(64, 64), block_shape=(32, 32),
            brow=np.array([0, 1], np.int32), bcol=np.array([0, 0], np.int32),
            blocks=np.ones((2, 32, 32), np.float32))
    b = BSR(shape=(64, 64), block_shape=(32, 32),
            brow=np.array([1], np.int32), bcol=np.array([0], np.int32),
            blocks=np.ones((1, 32, 32), np.float32))
    assert not (a.block_mask() @ b.block_mask()).any()
    for quantize in (None, "int8"):
        plan = api.plan_matmul(a, b, quantize=quantize)
        assert plan.n_out_blocks == 0 and plan.n_items == 0
        for backend in ("interpret", "reference"):
            out = plan(backend=backend)
            assert out.shape == (0, 32, 32)


def test_single_block_matrix_spmm_and_spgemm():
    rng = np.random.default_rng(20)
    one = BSR(shape=(32, 32), block_shape=(32, 32),
              brow=np.array([0], np.int32), bcol=np.array([0], np.int32),
              blocks=rng.standard_normal((1, 32, 32)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((32, 16)).astype(np.float32))
    plan = api.plan_matmul(one, x.shape, n_lanes=4)   # clamps to 1 chain
    assert plan.n_lanes == 1 and plan.n_items == 1
    got = np.asarray(plan(x, bn=16, backend="interpret"))
    np.testing.assert_allclose(got, one.to_dense() @ np.asarray(x),
                               rtol=1e-5, atol=1e-5)
    gplan = api.plan_matmul(one, one)
    assert gplan.n_out_blocks == 1
    gotg = np.asarray(gplan(backend="interpret"))
    np.testing.assert_allclose(gotg[0], one.to_dense() @ one.to_dense(),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# validate_schedule_args error paths (named ValueErrors with shapes)
# ---------------------------------------------------------------------------


def _spmm_args(n_items=2):
    """Minimal hand-built schedule: n_items same-row items of one block."""
    from repro.core.schedule import fetch_flags
    i32 = lambda *v: jnp.asarray(np.array(v, np.int32))
    valid = np.ones(n_items, bool)
    a_fetch, a_slot = fetch_flags(np.zeros(n_items), valid, 1)
    b_fetch, b_slot = fetch_flags(np.arange(n_items), valid, 1)
    return dict(
        a_blocks=jnp.ones((1, 8, 8), jnp.float32),
        slot_idx=i32(*([0] * n_items)), m_idx=i32(*([0] * n_items)),
        k_idx=i32(*range(n_items)),
        seg_start=i32(1, *([0] * (n_items - 1))),
        seg_write=i32(*([0] * (n_items - 1)), 1),
        accum_prev=i32(*([0] * n_items)), valid=i32(*([1] * n_items)),
        b_dense=jnp.ones((8, 16), jnp.float32),
        a_fetch=jnp.asarray(a_fetch), b_fetch=jnp.asarray(b_fetch),
        a_slot=jnp.asarray(a_slot), b_slot=jnp.asarray(b_slot))


def test_segment_spmm_rejects_bad_bn():
    from repro.kernels.segment_spmm import segment_spmm
    with pytest.raises(ValueError, match=r"N=16 .* not divisible by the "
                                         r"N-tile width bn=12"):
        segment_spmm(**_spmm_args(), grid_m=1, bn=12)


def test_segment_spmm_rejects_mismatched_schedule_arrays():
    from repro.kernels.segment_spmm import segment_spmm
    args = _spmm_args()
    args["seg_write"] = jnp.asarray(np.array([0, 1, 1], np.int32))
    with pytest.raises(ValueError, match=r"seg_write has shape \(3,\), "
                                         r"expected \(2,\)"):
        segment_spmm(**args, grid_m=1, bn=16)


def test_segment_spmm_rejects_bad_lane_and_unroll_combos():
    from repro.kernels.segment_spmm import segment_spmm
    with pytest.raises(ValueError, match=r"n_items=2 is not divisible by "
                                         r"n_lanes=3"):
        segment_spmm(**_spmm_args(), grid_m=1, bn=16, n_lanes=3)
    with pytest.raises(ValueError, match=r"lane length 1 is not divisible "
                                         r"by unroll=2"):
        segment_spmm(**_spmm_args(), grid_m=1, bn=16, n_lanes=2, unroll=2)


def test_segment_spmm_rejects_bad_rhs_k():
    from repro.kernels.segment_spmm import segment_spmm
    args = _spmm_args()
    args["b_dense"] = jnp.ones((12, 16), jnp.float32)
    with pytest.raises(ValueError, match=r"rhs K=12 is not a multiple"):
        segment_spmm(**args, grid_m=1, bn=16)


def test_segment_kernels_reject_bad_scale_shapes():
    from repro.kernels.segment_spmm import segment_spmm
    args = _spmm_args()
    with pytest.raises(ValueError, match=r"a_scales has shape \(2,\), "
                                         r"expected one fp32 scale"):
        segment_spmm(**args, grid_m=1, bn=16,
                     a_scales=jnp.ones((2,), jnp.float32))


# ---------------------------------------------------------------------------
# zero-copy realize (the killed O(nnz) gather)
# ---------------------------------------------------------------------------


def test_realize_does_not_copy_blocks():
    """Realizing a plan hands the caller's block buffer through untouched —
    no schedule-order gather of the values, forward or backward."""
    a = BSR.random(np.random.default_rng(9), (128, 128), (32, 32), 0.4)
    a_dev = BSR(a.shape, a.block_shape, a.brow, a.bcol,
                jnp.asarray(a.blocks))
    plan = api.plan_matmul(a_dev, with_grad=True, cache=False)
    assert plan.lhs_blocks is a_dev.blocks          # same device buffer
    # the template carries no permutation to apply at realize time
    field_names = {f.name for f in dataclasses.fields(planner._PlanTemplate)}
    assert "fwd_perm" not in field_names
    # the backward plan addresses the same storage via slot_idx + transpose
    g = plan.grad_plan
    assert g.lhs_blocks is None and g.transpose_lhs
    slot = np.asarray(g.slot_idx)[np.asarray(g.valid) == 1]
    assert sorted(set(slot.tolist())) == list(range(a.nblocks))


def test_schedule_indexes_storage_through_slot_idx():
    a = BSR.random(np.random.default_rng(10), (128, 160), (32, 32), 0.35)
    plan = api.plan_matmul(a, n_lanes=2)
    slot = np.asarray(plan.slot_idx)
    valid = np.asarray(plan.valid).astype(bool)
    m_idx, k_idx = np.asarray(plan.m_idx), np.asarray(plan.k_idx)
    # every valid item addresses the stored block with its coordinates
    np.testing.assert_array_equal(np.asarray(plan.a_brow)[slot[valid]],
                                  m_idx[valid])
    np.testing.assert_array_equal(np.asarray(plan.a_bcol)[slot[valid]],
                                  k_idx[valid])


# ---------------------------------------------------------------------------
# property test: pattern × lanes × fold × backend ≡ dense oracle
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=12)
@given(seed=st.integers(0, 10_000), gm=st.integers(1, 6),
       gk=st.integers(1, 6), density=st.floats(0.1, 1.0),
       n_lanes=st.sampled_from([1, 2, 4]),
       fold_len=st.sampled_from([None, 2]))
def test_lane_property_vs_dense(seed, gm, gk, density, n_lanes, fold_len):
    rng = np.random.default_rng(seed)
    a = BSR.random(rng, (gm * 16, gk * 16), (16, 16), density)
    x = rng.standard_normal((gk * 16, 32)).astype(np.float32)
    plan = api.plan_matmul(a, policy="segment", n_lanes=n_lanes,
                           fold_len=fold_len)
    want = a.to_dense() @ x
    got = np.asarray(plan(jnp.asarray(x), bn=32, backend="interpret"))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    got_ref = np.asarray(plan(jnp.asarray(x), backend="reference"))
    np.testing.assert_allclose(got_ref, want, rtol=1e-4, atol=1e-4)
