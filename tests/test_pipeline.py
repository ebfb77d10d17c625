"""Double-buffered DMA pipeline: fetch-flag schedule invariants, ring-slot
discipline, kernel parity across lanes × unroll × quantized ×
``transpose_lhs``, the pad-masking regression (masked is derived from the
plan's real pad state, not the lane/unroll shape), and the
``partition_lanes`` accum_prev write-before-read validation."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # optional-dep guard

from repro import api
from repro.core.formats import BSR
from repro.core.schedule import (build_spmm_schedule, fetch_flags,
                                 finalize_schedule, partition_lanes)

RNG = np.random.default_rng(0)


def _patterns():
    rand = BSR.random(np.random.default_rng(1), (128, 160), (32, 32), 0.35)
    d = np.random.default_rng(2).standard_normal((128, 96)).astype(np.float32)
    d[0:32] = 0.0
    d[64:96] = 0.0
    holes = BSR.from_dense(d, (32, 32))
    one_row = BSR.from_dense(
        np.random.default_rng(3).standard_normal((32, 256)).astype(np.float32),
        (32, 32))
    return {"random": rand, "empty_rows": holes, "one_segment": one_row}


# ---------------------------------------------------------------------------
# fetch_flags unit behavior
# ---------------------------------------------------------------------------


def test_fetch_flags_first_item_reuse_and_pads():
    # one lane: [5, 5, 7, 7(pad)] — first fetches, second reuses, third
    # fetches, pad moves nothing
    stream = np.array([5, 5, 7, 7])
    valid = np.array([1, 1, 1, 0])
    fetch, slot = fetch_flags(stream, valid, 1, depth=2)
    np.testing.assert_array_equal(fetch, [1, 0, 1, 0])
    # ring advances one slot per fetch; reuse stays on the resident slot
    np.testing.assert_array_equal(slot, [0, 0, 1, 1])


def test_fetch_flags_lane_boundary_always_fetches():
    # the same index on both sides of a lane cut must still fetch: lanes are
    # independent passes, residency never crosses them
    stream = np.array([3, 3, 3, 3])
    valid = np.ones(4, np.int64)
    fetch, _ = fetch_flags(stream, valid, 2, depth=2)
    np.testing.assert_array_equal(fetch.reshape(2, 2)[:, 0], [1, 1])


def test_fetch_flags_ring_depth():
    stream = np.arange(8)
    valid = np.ones(8, np.int64)
    fetch, slot = fetch_flags(stream, valid, 1, depth=4)
    assert fetch.sum() == 8
    np.testing.assert_array_equal(slot, np.arange(8) % 4)
    with pytest.raises(ValueError, match=r"depth must be >= 2"):
        fetch_flags(stream, valid, 1, depth=1)
    with pytest.raises(ValueError, match=r"matching shapes"):
        fetch_flags(stream, valid[:4], 1)
    with pytest.raises(ValueError, match=r"not divisible by\s+n_lanes=3"):
        fetch_flags(stream, valid, 3)


# ---------------------------------------------------------------------------
# plan-level fetch schedule invariants
# ---------------------------------------------------------------------------


def _check_fetch_schedule(plan, b_stream_leaf):
    """Shared invariant battery for a plan's DMA fetch schedule."""
    n_lanes, lane_len = plan.n_lanes, plan.lane_len
    depth = 2 * plan.unroll
    valid = np.asarray(plan.valid).reshape(n_lanes, lane_len).astype(bool)
    af = np.asarray(plan.a_fetch).reshape(n_lanes, lane_len)
    bf = np.asarray(plan.b_fetch).reshape(n_lanes, lane_len)
    # a lane's first item always fetches both streams
    np.testing.assert_array_equal(af[:, 0], 1)
    np.testing.assert_array_equal(bf[:, 0], 1)
    # pads never fetch
    assert not af[~valid].any() and not bf[~valid].any()
    # flags are the traffic model's revisit deltas (per-item, within-lane)
    b_stream = np.asarray(b_stream_leaf).reshape(n_lanes, lane_len)
    delta = np.ones_like(b_stream, dtype=bool)
    delta[:, 1:] = b_stream[:, 1:] != b_stream[:, :-1]
    np.testing.assert_array_equal(bf.astype(bool), delta & valid)
    # modeled fetch counts ARE the flag sums
    assert plan.traffic["a_fetches"] == int(af.sum())
    assert plan.traffic["b_fetches"] == int(bf.sum())
    # ring slots advance one slot per fetch and stay inside the ring
    for fl, sl in ((af, plan.a_slot), (bf, plan.b_slot)):
        sl = np.asarray(sl).reshape(n_lanes, lane_len)
        assert sl.min() >= 0 and sl.max() < depth
        want = np.maximum(np.cumsum(fl, axis=1) - 1, 0) % depth
        np.testing.assert_array_equal(sl, want)


@pytest.mark.parametrize("n_lanes,unroll", [(1, 1), (2, 1), (2, 2), (4, 2)])
def test_plan_fetch_schedule_invariants(n_lanes, unroll):
    for name, a in _patterns().items():
        plan = api.plan_matmul(a, n_cols_hint=64, n_lanes=n_lanes,
                               unroll=unroll, fold_len=3, cache=False)
        _check_fetch_schedule(plan, plan.k_idx)
        # has_pads reflects the actual schedule, not the lane/unroll shape
        assert plan.has_pads == bool(
            (np.asarray(plan.valid) == 0).any()), name


def test_spgemm_plan_fetch_schedule_invariants():
    a = BSR.random(np.random.default_rng(6), (128, 160), (32, 32), 0.4)
    b = BSR.random(np.random.default_rng(7), (160, 96), (32, 32), 0.4)
    for n_lanes in (1, 3):
        plan = api.plan_matmul(a, b, n_lanes=n_lanes, cache=False)
        _check_fetch_schedule(plan, plan.b_idx)


def test_grad_plan_carries_fetch_schedule():
    a = BSR.random(np.random.default_rng(8), (96, 128), (32, 32), 0.4)
    plan = api.plan_matmul(a, with_grad=True, n_lanes=2, cache=False)
    g = plan.grad_plan
    assert g.a_fetch is not None and g.b_slot is not None
    _check_fetch_schedule(g, g.k_idx)


# ---------------------------------------------------------------------------
# double-buffer parity: lanes × unroll × quantized × transpose_lhs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("quantize", [None, "int8"])
@pytest.mark.parametrize("n_lanes,unroll", [(1, 2), (2, 1), (3, 2)])
def test_pipeline_parity_vs_dense(n_lanes, unroll, quantize):
    for name, a in _patterns().items():
        plan = api.plan_matmul(a, policy="segment", n_lanes=n_lanes,
                               unroll=unroll, fold_len=3, quantize=quantize)
        x = jnp.asarray(
            RNG.standard_normal((a.shape[1], 64)).astype(np.float32))
        want = a.to_dense() @ np.asarray(x)
        got = np.asarray(plan(x, bn=32, backend="interpret"))
        got_ref = np.asarray(plan(x, bn=32, backend="reference"))
        if quantize is None:
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4,
                                       err_msg=f"{name}")
        else:
            norm = max(np.abs(want).max(), 1e-6)
            assert np.abs(got - want).max() / norm < 5e-2, name
        # interpret and reference agree on the *stored* (quantized) values
        np.testing.assert_allclose(got, got_ref, rtol=1e-4, atol=1e-4,
                                   err_msg=f"{name}")


def test_pipeline_parity_fp8():
    a = _patterns()["random"]
    plan = api.plan_matmul(a, n_lanes=2, unroll=2, fold_len=3,
                           quantize="fp8")
    x = jnp.asarray(RNG.standard_normal((a.shape[1], 64)).astype(np.float32))
    got = np.asarray(plan(x, bn=32, backend="interpret"))
    got_ref = np.asarray(plan(x, bn=32, backend="reference"))
    np.testing.assert_allclose(got, got_ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("quantize", [None, "int8"])
def test_transpose_lhs_pipeline_parity(quantize):
    """The backward (transpose_lhs) schedule runs the same DMA pipeline
    against forward storage — dx must match the dense oracle."""
    a = BSR.random(np.random.default_rng(9), (96, 128), (32, 32), 0.5)
    plan = api.plan_matmul(a, with_grad=True, n_lanes=2, unroll=2,
                           fold_len=4, quantize=quantize, cache=False)
    x = jnp.asarray(RNG.standard_normal((128, 48)).astype(np.float32))

    def loss(xx):
        return jnp.sum(api.apply_plan(plan, xx, backend="interpret") ** 2)

    gx = np.asarray(jax.grad(loss)(x))
    dense = (api.dequantize_blocks(
                 api.QuantizedBlocks(np.asarray(plan.lhs_blocks),
                                     np.asarray(plan.lhs_scales), quantize))
             if quantize else np.asarray(plan.lhs_blocks))
    w = np.zeros(a.shape, np.float32)
    for s in range(a.nblocks):
        r, c = int(a.brow[s]), int(a.bcol[s])
        w[r * 32:(r + 1) * 32, c * 32:(c + 1) * 32] = dense[s]
    gx_d = np.asarray(jax.grad(
        lambda xx: jnp.sum((jnp.asarray(w) @ xx) ** 2))(x))
    np.testing.assert_allclose(gx, gx_d, rtol=1e-3, atol=1e-3)


def test_spgemm_pipeline_parity_quantized():
    a = BSR.random(np.random.default_rng(10), (128, 160), (32, 32), 0.35)
    b = BSR.random(np.random.default_rng(11), (160, 96), (32, 32), 0.35)
    want = a.to_dense() @ b.to_dense()
    plan = api.plan_matmul(a, b, n_lanes=2, unroll=2, quantize="int8")
    got = np.asarray(plan(backend="interpret"))
    ref = np.asarray(plan(backend="reference"))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
    norm = max(np.abs(want).max(), 1e-6)
    for i, (r, c) in enumerate(zip(plan.c_brow, plan.c_bcol)):
        blk = want[r * 32:(r + 1) * 32, c * 32:(c + 1) * 32]
        assert np.abs(got[i] - blk).max() / norm < 5e-2


def test_pipeline_true_requires_fetch_arrays():
    from repro.kernels.segment_spmm import segment_spmm
    a = _patterns()["random"]
    plan = api.plan_matmul(a, n_lanes=2)
    x = jnp.ones((a.shape[1], 32), jnp.float32)
    # every fetch array is required; the error names the one left out
    with pytest.raises(ValueError, match=r"b_slot is missing"):
        segment_spmm(plan.lhs_blocks, plan.slot_idx, plan.m_idx, plan.k_idx,
                     plan.seg_start, plan.seg_write, plan.accum_prev,
                     plan.valid, x, grid_m=plan.grid[0],
                     n_lanes=plan.n_lanes, bn=32, interpret=True,
                     a_fetch=plan.a_fetch, b_fetch=plan.b_fetch,
                     a_slot=plan.a_slot)
    with pytest.raises(ValueError, match=r"a_fetch is missing"):
        segment_spmm(plan.lhs_blocks, plan.slot_idx, plan.m_idx, plan.k_idx,
                     plan.seg_start, plan.seg_write, plan.accum_prev,
                     plan.valid, x, grid_m=plan.grid[0],
                     n_lanes=plan.n_lanes, bn=32, interpret=True)


# ---------------------------------------------------------------------------
# masked derivation regression: pads on a single-lane unroll=1 schedule
# (the old executor keyed masking on `n_lanes > 1 or unroll > 1` and would
# silently accumulate pad garbage here)
# ---------------------------------------------------------------------------


def _insert_pad(arr, pos, value):
    arr = np.asarray(arr)
    return jnp.asarray(np.insert(arr, pos, np.asarray(value, arr.dtype)))


def test_padded_single_lane_spmm_masks_pads():
    a = BSR.from_dense(
        np.random.default_rng(12).standard_normal((64, 96)).astype(np.float32),
        (32, 32))
    plan = api.plan_matmul(a, n_cols_hint=64, cache=False)
    assert plan.n_lanes == 1 and plan.unroll == 1 and not plan.has_pads
    # inject a valid=0 item in the middle of the first segment: index leaves
    # repeat the previous item (re-addressing the resident tiles), flag
    # leaves are zero, fetch flags are zero — exactly what a fetch-flag pad
    # or a custom registry policy may produce
    pos = 1
    prev = pos - 1
    padded = plan.replace(
        slot_idx=_insert_pad(plan.slot_idx, pos, plan.slot_idx[prev]),
        m_idx=_insert_pad(plan.m_idx, pos, plan.m_idx[prev]),
        k_idx=_insert_pad(plan.k_idx, pos, plan.k_idx[prev]),
        seg_start=_insert_pad(plan.seg_start, pos, 0),
        seg_write=_insert_pad(plan.seg_write, pos, 0),
        accum_prev=_insert_pad(plan.accum_prev, pos, 0),
        valid=_insert_pad(plan.valid, pos, 0),
        a_fetch=_insert_pad(plan.a_fetch, pos, 0),
        b_fetch=_insert_pad(plan.b_fetch, pos, 0),
        a_slot=_insert_pad(plan.a_slot, pos, plan.a_slot[prev]),
        b_slot=_insert_pad(plan.b_slot, pos, plan.b_slot[prev]),
        has_pads=True)
    x = jnp.asarray(RNG.standard_normal((96, 64)).astype(np.float32))
    want = a.to_dense() @ np.asarray(x)
    got = np.asarray(padded(x, bn=32, backend="interpret"))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_padded_single_lane_spgemm_masks_pads():
    a = BSR.from_dense(
        np.random.default_rng(13).standard_normal((64, 64)).astype(np.float32),
        (32, 32))
    b = BSR.from_dense(
        np.random.default_rng(14).standard_normal((64, 64)).astype(np.float32),
        (32, 32))
    plan = api.plan_matmul(a, b, cache=False)
    assert plan.n_lanes == 1 and plan.unroll == 1 and not plan.has_pads
    pos = 1
    prev = pos - 1
    padded = plan.replace(
        a_idx=_insert_pad(plan.a_idx, pos, plan.a_idx[prev]),
        b_idx=_insert_pad(plan.b_idx, pos, plan.b_idx[prev]),
        c_idx=_insert_pad(plan.c_idx, pos, plan.c_idx[prev]),
        seg_start=_insert_pad(plan.seg_start, pos, 0),
        seg_write=_insert_pad(plan.seg_write, pos, 0),
        accum_prev=_insert_pad(plan.accum_prev, pos, 0),
        valid=_insert_pad(plan.valid, pos, 0),
        a_fetch=_insert_pad(plan.a_fetch, pos, 0),
        b_fetch=_insert_pad(plan.b_fetch, pos, 0),
        a_slot=_insert_pad(plan.a_slot, pos, plan.a_slot[prev]),
        b_slot=_insert_pad(plan.b_slot, pos, plan.b_slot[prev]),
        has_pads=True)
    want = a.to_dense() @ b.to_dense()
    got = np.asarray(padded(backend="interpret"))
    for i, (r, c) in enumerate(zip(plan.c_brow, plan.c_bcol)):
        np.testing.assert_allclose(
            got[i], want[r * 32:(r + 1) * 32, c * 32:(c + 1) * 32],
            rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# partition_lanes accum_prev write-before-read validation
# ---------------------------------------------------------------------------


def test_partition_lanes_rejects_accum_prev_without_prior_write():
    # two single-item owner chains; the second claims to continue a partial
    # sum (accum_prev=1) but its tile was never written in any lane
    owner = np.array([0, 1])
    with pytest.raises(ValueError, match=r"accum_prev=1 but no earlier "
                                         r"seg_write"):
        partition_lanes(owner, 2, seg_start=np.array([1, 1]),
                        seg_write=np.array([1, 1]),
                        accum_prev=np.array([0, 1]))


def test_partition_lanes_accepts_folded_schedules():
    a = BSR.random(np.random.default_rng(15), (256, 256), (32, 32), 0.3)
    s = build_spmm_schedule(a, "segment", fold_len=2)
    fin = finalize_schedule(s.seg_start, s.m, n_slots=s.n_m_blocks)
    for n_lanes in (1, 2, 4):
        partition_lanes(s.m, n_lanes, unroll=2, seg_start=s.seg_start,
                        seg_write=s.seg_write, accum_prev=fin.accum_prev)


def test_partition_lanes_validation_shape_mismatch():
    with pytest.raises(ValueError, match=r"seg_write has shape"):
        partition_lanes(np.array([0, 1]), 1, seg_start=np.array([1, 1]),
                        seg_write=np.array([1]),
                        accum_prev=np.array([0, 0]))


# ---------------------------------------------------------------------------
# property sweep: pad-heavy unrolled schedules ≡ dense oracle, flags sane
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=12)
@given(seed=st.integers(0, 10_000), gm=st.integers(1, 6),
       gk=st.integers(1, 6), density=st.floats(0.1, 1.0),
       n_lanes=st.sampled_from([1, 2, 4]),
       quantize=st.sampled_from([None, "int8"]))
def test_pipeline_property_vs_dense(seed, gm, gk, density, n_lanes, quantize):
    rng = np.random.default_rng(seed)
    a = BSR.random(rng, (gm * 16, gk * 16), (16, 16), density)
    x = rng.standard_normal((gk * 16, 32)).astype(np.float32)
    # unroll=2 forces group padding on every odd-length segment chain —
    # the pad-heavy configuration the fetch flags must keep silent
    plan = api.plan_matmul(a, policy="segment", n_lanes=n_lanes, unroll=2,
                           fold_len=3, quantize=quantize, cache=False)
    _check_fetch_schedule(plan, plan.k_idx)
    want = a.to_dense() @ x
    got = np.asarray(plan(jnp.asarray(x), bn=32, backend="interpret"))
    ref = np.asarray(plan(jnp.asarray(x), bn=32, backend="reference"))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
    if quantize is None:
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    else:
        norm = max(np.abs(want).max(), 1e-6)
        assert np.abs(got - want).max() / norm < 5e-2
